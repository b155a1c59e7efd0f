"""Engine: how unevenly a decode step's rows fall on the experts: the
fullest expert's rows over the mean rows an expert, averaged over steps
and routed layers: sum of `moe_rows_max` x experts over sum of `moe_rows`
of the stretch's `ray_tpu:engine.process_block` spans. 1 is an even
spread; a grouped product's longest group sets its tail."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.process_block") if ps else {}
    experts = m.get("arch", {}).get("moe_experts")
    if not sums.get("moe_rows") or not experts:
        return None
    return sums.get("moe_rows_max", 0) * float(experts) / sums["moe_rows"]
