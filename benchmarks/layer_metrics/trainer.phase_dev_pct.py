"""Trainer: share of the devices' busy time by phase of the train step
(`.fwd`, `.bwd`, `.opt`), from each device operation's scope path:
`optimizer`; what autodiff transposed (backward, remat recomputation
included); the rest of `fwd` and `loss_head` (forward). Operations with
no such scope are in none of the three."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return ps.phase_pct(metric["name"].rsplit(".", 1)[1]) if ps else None
