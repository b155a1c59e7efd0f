"""Engine: the recurrent-state updates a request owns, as a share of
those the decode blocks' steps span: sum of `linear_slot_steps_live` over
sum of `linear_slot_steps` (`k` x slots x linear layers) of the stretch's
`ray_tpu:engine.dispatch_block` spans. A state costs the same whatever
its sequence's length, so this, and not the rows held, is what of a
step's state traffic is somebody's: 100% is every slot owned all through.
Both counts are the host's arithmetic where it dispatches a block
(`stats()["counts"]` holds the same sums); that a slot nobody owns is not
written is what the tests hold (tests/test_solar_kda.py). Nothing from a
program whose spans do not carry the counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    if not sums.get("linear_slot_steps"):
        return None
    return 100.0 * sums.get("linear_slot_steps_live", 0) \
        / sums["linear_slot_steps"]
