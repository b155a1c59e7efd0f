"""Kernels: the routed products' share of their roofline in decode. The
least time the chip could take a step (the larger of operations over peak
FLOP/s and bytes over peak bytes/s; the configuration's reference counts
both: the three matrices of every expert *hit*, once, and each row in and
out: `moe_experts_min_bytes`, `moe_experts_flops`), from the engine's
counters a step (`moe_experts_hit`, `moe_rows` over the sum of `k` of the
stretch's `engine.process_block` spans), over the device time a step
under the scope `moe_experts` inside `jit_decode*`. Bound by bytes at any
batch this engine runs. A kernel that reads every expert reads lower."""

from lib import peaks, progspans, scopetime


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_ms = scopetime.decode_ms_step(m, ("moe_experts",))
    sums = ps.attribute_sums("engine.process_block") if ps else {}
    ref = m["ctx"].spec.reference
    if not spent_ms or not sums.get("k") or not sums.get("moe_rows") \
            or not hasattr(ref, "moe_experts_min_bytes"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    hit, rows = (sums.get(key, 0) / sums["k"]
                 for key in ("moe_experts_hit", "moe_rows"))
    least_s = max(
        ref.moe_experts_min_bytes(m["arch"], hit, rows)
        / peak["hbm_bytes_per_s"],
        ref.moe_experts_flops(m["arch"], rows) / peak["bf16_flops"])
    return 100.0 * least_s * 1e3 / spent_ms
