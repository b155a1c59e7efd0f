"""Engine: passes a looped stack walked over its layers for a token it
delivered: sum of `loop_passes` over the tokens of the stretch's
`ray_tpu:engine.process_block` spans (`emitted`) and
`ray_tpu:engine.deliver_first` spans (`tokens`). The engine counts
`ut_steps` passes for a token where it reaches its request, whatever pass
the gate says it left at, because the program runs every pass
(`models/periodic._walk`): `total_ut_steps` exactly (Ouro-2.6B: 4.0)
while no pass is skipped and every delivered token is counted. Nothing
from a program whose spans do not carry the counter."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    if ps is None:
        return None
    blocks = ps.attribute_sums("engine.process_block")
    first = ps.attribute_sums("engine.deliver_first")
    passes = blocks.get("loop_passes", 0) + first.get("loop_passes", 0)
    tokens = blocks.get("emitted", 0) + first.get("tokens", 0)
    if not passes or not tokens:
        return None
    return passes / tokens
