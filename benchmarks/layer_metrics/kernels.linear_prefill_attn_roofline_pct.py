"""Kernels: the share of its roofline that a tile's recurrence reaches,
whatever order implements it. The work is the model's: a head's update a
real prompt token a linear layer (the decay of its state, the state
against k, the rank-one term, the state against q) and q, k, v, g, beta
and o moved once: the configuration's reference counts both
(`kda_flops_bytes(arch, linear_tokens)`: `linear_tokens` a tile of the
stretch's `engine.prefill_tile` spans, the mean over them, times the
launches of the prefill programs that ran), and the least time is the
larger of the operations at the peak bf16 FLOP/s and the bytes at the
peak bytes/s. Over the device time under the scope `kda_scan` inside the
prefill programs (`models/periodic._linear_tile`: `ops/delta_rule.
chunk_scan`, the chunks' triangular systems, their products and the
carried state). A chunked order does more operations than the model asks
(the products inside a chunk) and runs padding's positions too, and reads
that much lower. Nothing in a rehearsal, from a program without the scope
or the counter, or from a reference that counts no recurrence."""

from lib import peaks, prefilltime, progspans

SCOPE = "kda_scan"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    if not ps or not hasattr(ref, "kda_flops_bytes"):
        return None
    spent_s = (prefilltime.scope_seconds(m) or {}).get(SCOPE)
    launches = prefilltime.launches(ps)
    tiles = [t for t in ps.named(prefilltime.TILE)
             if t.stats.get("linear_tokens")]
    if not spent_s or not launches or not tiles:
        return None
    asked = ref.kda_flops_bytes(m["arch"], sum(
        t.stats["linear_tokens"] for t in tiles) / len(tiles) * launches)
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least_s = max(asked["flops"] / peak["bf16_flops"],
                  asked["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least_s / spent_s
