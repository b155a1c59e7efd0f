"""Kernels: the flash kernels' share of their roofline in training. The
least time the chip could take for the forward, dq and dkv calls of the
traced steps (the larger of operations over peak FLOP/s and bytes over
peak bytes/s, from the shapes) over the time their events took. Returns
nothing where the trace does not name the kernels' events."""

from lib import kernels, peaks, stats


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    tr = m.get("trace")
    if tr is None or "step_ms" not in m:
        return None
    spent = tr.ops_matching(kernels.FLASH_EVENTS)       # over all devices
    ctx = m["ctx"]
    # Steps inside the traced stretch, parts of steps included.
    steps = sum(max(0.0, min(e, ctx.trace_t1) - max(s, ctx.trace_t0))
                / (e - s) for s, e in m.get("step_intervals", []))
    if not spent or not steps:
        return None
    a = m["arch"]
    hd = a["d_model"] // a["n_heads"]
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least = 0.0
    for backward in (False, True):
        fb = stats.flash_flops_bytes(
            m["batch_size"], a["n_heads"], a["n_kv_heads"], m["seq_len"],
            m["seq_len"], hd, causal=True, backward=backward)
        # Full remat runs the forward kernel twice a step; the second is
        # recomputation, which the algorithm does not require: not counted.
        least += max(fb["flops"] / peak["bf16_flops"],
                     fb["bytes"] / peak["hbm_bytes_per_s"])
    # The whole batch's cost: chip-seconds summed over the devices, as
    # `spent` is.
    least *= a["n_layers"] * steps
    return 100.0 * least / spent
