"""Engine: how unevenly an admission tile's rows fall on the experts: the
fullest expert's rows over the mean rows an expert, averaged over tiles
and routed layers: sum of `prefill_moe_rows_max` x experts over sum of
`prefill_moe_rows` of the stretch's `ray_tpu:engine.deliver_first` spans
(the prefill program counts them on the device, padding positions too;
`stats()["counts"]` holds the same sums). 1 is an even spread; a grouped
product's longest group sets its tail."""

from lib import prefilltime, progspans


def read(metric, m):
    ps = progspans.for_run(m)
    tile = prefilltime.routed_per_tile(ps) if ps else None
    experts = m.get("arch", {}).get("moe_experts")
    if not tile or not experts:
        return None
    return tile["rows_max"] * float(experts) / tile["rows"]
