"""Model: the device time a decode launch spends outside its steps. Per
`jit_decode_k<k>` module event joined to its program's
`engine.device_call` (its `k`; blocks of two steps or more): the event's
length less its loop, which lasts from the first to the last of the
operations whose events a launch are a multiple of k
(`lib/turn.launch_fixed`): what runs once a launch before the loop or
behind it, what was hoisted out of it, and the time there in which
nothing ran. Median over the stretch's launches, ms; the `device_turn`
line gives it by block size, its ten longest operations, and the
intercept of length on k where the stretch ran two sizes. What a one-step
block pays a step. None, with the reason logged, where the stretch joined
no such block."""

from lib import turn


def read(metric, m):
    tn = turn.for_run(m)
    return tn.decode_launch_fixed_ms() if tn else None
