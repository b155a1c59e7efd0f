"""Engine: the mean pass at which the delivered tokens of a looped stack
left it, counted from 1: `loop_exit_p<t>` (tokens that left at pass t) of
the stretch's `ray_tpu:engine.process_block` and
`ray_tpu:engine.deliver_first` spans, from the exit passes the programs
return behind their tokens (`stackparts.exit_select`: the first pass
whose cumulative exit mass reaches `early_exit_threshold`). At the
published threshold 1 every token leaves at the last pass
(`total_ut_steps`: 4.0); under a lower one this is what a walk that
stopped at the exit would save. Nothing from a program whose spans do not
carry the counters."""

import re

from lib import progspans

EXIT = re.compile(r"^loop_exit_p(\d+)$")


def read(metric, m):
    ps = progspans.for_run(m)
    if ps is None:
        return None
    tokens = weighted = 0
    for name in ("engine.process_block", "engine.deliver_first"):
        for key, n in ps.attribute_sums(name).items():
            at = EXIT.match(key)
            if at:
                tokens += n
                weighted += int(at.group(1)) * n
    return weighted / tokens if tokens else None
