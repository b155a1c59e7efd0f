"""Engine: from the start of a program's own `engine.device_call` to the
start of its module event on device 0, moved to the host's clock by the
stretch's measured `offset_hi`; median over the launches made while the
device ran nothing, ms (`lib/turn.py`). The pair that sets `offset_hi`
reads 0 by construction: the median is the reading. Across the two
timelines: short by at most the clock bracket's width."""

from lib import turn


def read(metric, m):
    tn = turn.for_run(m)
    return tn.launch_to_start_ms() if tn else None
