"""Kernels: latent decode attention's share of its roofline. The least
time the chip could take a step is the larger of the held latent rows'
bytes, each row read once for keys and values together, at the peak
bytes/s, and the rows' operations (every head's query against the row,
its probability times the row's latent part) at the peak FLOP/s; the
configuration's reference counts both (`latent_attn_min_bytes`,
`latent_attn_flops`) from the rows the owned slots *hold*:
`cache_rows_held` over `k` of the stretch's `engine.dispatch_block` spans,
never the rows a block rounds them up to, the lanes a row is padded to,
nor the kernel's grid. At 128 heads over rows of 512 + 64 the two bounds
meet (242 operations a byte against the chip's 240). Over the device time
a step of the events whose `kernel_metadata` reads `decode_attn`
(`ops/decode_attention`, here with one array for keys and values).
Nothing where no such event exists, where the spans carry no counter, or
where the reference counts no latent rows."""

from lib import peaks, progspans

KERNEL = "decode_attn"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_s = ps.kernel_s.get(KERNEL) if ps else None
    steps = ps.decode_steps() if ps else 0.0
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    ref = m["ctx"].spec.reference
    if not spent_s or not steps or not sums.get("k") \
            or not sums.get("cache_rows_held") \
            or not hasattr(ref, "latent_attn_min_bytes"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    rows = sums["cache_rows_held"] / sums["k"]
    least_s = max(
        ref.latent_attn_min_bytes(m["arch"], rows) / peak["hbm_bytes_per_s"],
        ref.latent_attn_flops(m["arch"], rows) / peak["bf16_flops"])
    return 100.0 * least_s / (spent_s / len(ps.devices) / steps)
