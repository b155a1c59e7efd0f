"""Engine: experts that held at least one row, as a share of the experts
a decode step offers (steps x routed layers x experts): sum of
`moe_experts_hit` over sum of `moe_expert_steps` of the stretch's
`ray_tpu:engine.process_block` spans (the program counts them on the
device; `stats()["counts"]` holds the same sums). What share of the
expert weights a step has to read."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.process_block") if ps else {}
    if not sums.get("moe_expert_steps"):
        return None
    return 100.0 * sums.get("moe_experts_hit", 0) / sums["moe_expert_steps"]
