"""Kernels: the decode-attention kernel's share of its roofline. The
least time the chip could take a step is the K and V bytes of the rows
the owned slots *hold* (never the rows a block rounds them up to, nor a
slot nobody owns) at the peak bytes/s: `cache_rows_held` over `k` of the
stretch's `engine.dispatch_block` spans, x KV heads x head size x the
cache's bytes an element x 2 (K and V) x layers x the bf16 terms a row is
kept as. Over the device time a step of the events whose
`kernel_metadata` reads `decode_attn` (`ops/decode_attention`). Bound by
bytes: a row's operations are 2-8 a byte. Nothing where no such event
exists, where the spans carry no counter, or where layers keep caches of
unlike length (a windowed stack: the counter is in rows of one length)."""

from lib import peaks, progspans

KERNEL = "decode_attn"


def held_bytes_step(arch, model, rows_step: float) -> float:
    """Bytes of K and V under `rows_step` held rows, every layer."""
    head = arch.get("head_dim") or arch["d_model"] // arch["n_heads"]
    two_terms = (model.get("dtype") == "float32"
                 and model.get("param_dtype") == "bfloat16")
    element = 2 if (two_terms or model.get("dtype") == "bfloat16") else 4
    return (rows_step * arch["n_kv_heads"] * head * element * 2
            * arch["n_layers"] * (2 if two_terms else 1))


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_s = ps.kernel_s.get(KERNEL) if ps else None
    steps = ps.decode_steps() if ps else 0.0
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    arch = m["arch"]
    if not spent_s or not steps or not sums.get("k") \
            or not sums.get("cache_rows_held") or arch.get("sliding_window"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least_s = held_bytes_step(
        arch, m["ctx"].spec.sizes.get("model", {}),
        sums["cache_rows_held"] / sums["k"]) / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (spent_s / len(ps.devices) / steps)
