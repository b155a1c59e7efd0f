"""Model: the whole decode step of a stack that keeps recurrent states
beside latent rows, as a share of the chip's peak HBM bandwidth. The
bytes a step cannot avoid moving (the configuration's reference counts
them, `decode_bytes(arch, rows_held, live, experts_hit)`: every weight
outside the routed experts once, the three matrices of each held expert
that took a row once, the owned slots' float32 states and their
convolutions' tails read once and written once, the held tokens' latent
rows once a latent layer; temporaries and the step's own new rows left
out) at the stretch's mean held tokens and mean owned slots a step
(`cache_rows_held`, `active` x `k` over `k` of its `engine.dispatch_block`
spans) and mean experts hit a step (`moe_experts_hit` over `k` of its
`engine.process_block` spans), over the decode programs' device time a
step, over the peak bytes/s. The whole step and not a kernel: it bounds
what any change to the step can give the cell's `serve_out_tok_s`, and
cannot pass 100%. Nothing on a CPU, from a reference without the count
(one that takes no `experts_hit`: a looped stack's has its own reader)
or from spans without the counters."""

import inspect

from lib import peaks, progspans

_BYTES = {"bfloat16": 2, "float32": 4}


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    ms_step = ps.decode_ms_step() if ps else None
    if not ms_step or "experts_hit" not in inspect.signature(
            getattr(ref, "decode_bytes", lambda: None)).parameters:
        return None
    blocks = [b.stats for b in ps.named("engine.dispatch_block")
              if b.stats.get("k") and "cache_rows_held" in b.stats
              and "linear_slot_steps_live" in b.stats]
    done = ps.attribute_sums("engine.process_block")
    steps = sum(b["k"] for b in blocks)
    if not steps or not done.get("k") or "moe_experts_hit" not in done:
        return None
    model = m["ctx"].spec.sizes.get("model", {})
    acts = model.get("dtype") or "bfloat16"
    least = ref.decode_bytes(
        m["arch"], sum(b["cache_rows_held"] for b in blocks) / steps,
        sum(b["k"] * b.get("active", 0) for b in blocks) / steps,
        done["moe_experts_hit"] / done["k"],
        element=_BYTES[model.get("param_dtype", "bfloat16")],
        cache_element=_BYTES[model.get("cache_dtype") or acts],
        tail_element=_BYTES[acts])
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    return 100.0 * least / peak["hbm_bytes_per_s"] / (ms_step / 1e3)
