"""Engine: the token-expert pairs that chose an expert this chip holds, as
a share of the pairs its router routed: sum of `moe_pairs_held` over sum
of `moe_pairs` of the stretch's `ray_tpu:engine.process_block` spans (the
program counts both on the device; `stats()["counts"]` holds the same
sums). A chip that holds 16 of 256 experts keeps 6.25% under a uniform
router; 100% is a layer that holds every expert, or a router narrowed to
the experts held. Nothing from a program whose spans do not carry the
counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.process_block") if ps else {}
    if not sums.get("moe_pairs"):
        return None
    return 100.0 * sums.get("moe_pairs_held", 0) / sums["moe_pairs"]
