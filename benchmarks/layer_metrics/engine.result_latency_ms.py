"""Engine: a result's way back: for every `engine.fetch` that was already
waiting when the device event it names (`call`, and `program` / `seq`
where that is a launch) ended, from that end, moved to the host's clock by
the stretch's measured `offset_hi`, to the fetch's end; median, ms
(`lib/turn.py`). Across the profile's two timelines: long by at most the
clock bracket's width, which the `device_turn` line gives."""

from lib import turn


def read(metric, m):
    tn = turn.for_run(m)
    return tn.result_latency_ms() if tn else None
