"""Kernels: the routed products' share of their roofline in prefill. The
least time the chip could take a tile (the larger of operations over peak
FLOP/s and bytes over peak bytes/s; the configuration's reference counts
both: `moe_experts_flops` of the tile's token-expert pairs,
`moe_experts_min_bytes` of the experts hit, once, and each row in and
out), from the tiles' counters (`prefill_moe_rows`,
`prefill_moe_experts_hit` over `moe_tiles` of the stretch's
`engine.deliver_first` spans), over the device time a launch under the
scope `moe_experts` inside `jit_prefill*`: the three grouped products and
the pairs' sort, gather and weighted sum. Bound by operations from some
250 rows an expert up. A float32 activation goes in as two bf16 terms, so
the products do twice the operations counted: such a cell reads at most
half."""

from lib import peaks, prefilltime, progspans


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    by_scope = prefilltime.scope_seconds(m) if ps else None
    tile = prefilltime.routed_per_tile(ps) if ps else None
    n = prefilltime.launches(ps) if ps else 0.0
    ref = m["ctx"].spec.reference
    if not by_scope or not by_scope.get("moe_experts") or not tile or not n \
            or not hasattr(ref, "moe_experts_flops"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least_s = max(
        ref.moe_experts_min_bytes(m["arch"], tile["experts_hit"],
                                  tile["rows"]) / peak["hbm_bytes_per_s"],
        ref.moe_experts_flops(m["arch"], tile["rows"]) / peak["bf16_flops"])
    return 100.0 * least_s / (by_scope["moe_experts"] / n)
