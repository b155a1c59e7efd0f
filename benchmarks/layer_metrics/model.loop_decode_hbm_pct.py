"""Model: a looped stack's whole decode step as a share of the chip's
peak HBM bandwidth. The bytes a step cannot avoid reading (the
configuration's reference counts them, `decode_bytes(arch, rows_held,
live)`: the layers' weights `ut_steps` times, the head, the norms and the
gate once, K and V of the rows the owned slots hold in every (pass,
layer) slab; the step's writes left out) at the stretch's mean rows held
and mean owned slots a step (`cache_rows_held`, `active` x `k` over `k`
of its `engine.dispatch_block` spans), over the decode programs' device
time a step, over the peak bytes/s. The whole step and not a kernel: it
bounds what any change to the step can give the cell's
`serve_out_tok_s`, and cannot pass 100%. Nothing on a CPU, from a
reference without the count or from spans without the counters."""

from lib import peaks, progspans

_BYTES = {"bfloat16": 2, "float32": 4}


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    ms_step = ps.decode_ms_step() if ps else None
    if not ms_step or not hasattr(ref, "decode_bytes"):
        return None
    blocks = [b.stats for b in ps.named("engine.dispatch_block")
              if b.stats.get("k") and "cache_rows_held" in b.stats]
    steps = sum(b["k"] for b in blocks)
    if not steps:
        return None
    model = m["ctx"].spec.sizes.get("model", {})
    cache = model.get("cache_dtype") or model.get("dtype")
    least = ref.decode_bytes(
        m["arch"], sum(b["cache_rows_held"] for b in blocks) / steps,
        sum(b["k"] * b.get("active", 0) for b in blocks) / steps,
        element=_BYTES[model.get("param_dtype", "bfloat16")],
        cache_element=_BYTES[cache or "bfloat16"])
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    return 100.0 * least / peak["hbm_bytes_per_s"] / (ms_step / 1e3)
