"""Kernels: the share of the chip's peak that a tile's attention over the
chosen pairs reaches, whatever order implements it. The work is the
model's: query t attends min(t + 1, index_topk) rows, per head, scores
nope + rope wide and values `v_head_dim` wide, every layer
(`sparse_prefill_attn_flops(arch, tokens)` of the configuration's
reference, at each tile's real prompt: `tokens` over `rows` of the
stretch's `engine.prefill_tile` spans, the mean over them). Times the
launches of the prefill programs that ran, at the peak bf16 FLOP/s, over
the device time under the scope `attn_sparse` inside them
(`models/latent.py`: a chunk's rows into the cache, the rows of every
chunk of keys read back and attended under the chosen sets as a bias,
and the parts' merge). A program that attends every pair a chunk may see
and masks the unchosen ones does (t + 1) / min(t + 1, index_topk) of this
work, and reads that much lower. Nothing in a rehearsal, from a program
without the scope, or from a reference that counts no chosen pairs."""

from lib import peaks, prefilltime, progspans

SCOPE = "attn_sparse"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    if not ps or not hasattr(ref, "sparse_prefill_attn_flops"):
        return None
    spent_s = (prefilltime.scope_seconds(m) or {}).get(SCOPE)
    launches = prefilltime.launches(ps)
    tiles = [t for t in ps.named(prefilltime.TILE)
             if t.stats.get("rows") and t.stats.get("tokens")]
    if not spent_s or not launches or not tiles:
        return None
    asked = sum(t.stats["rows"] * ref.sparse_prefill_attn_flops(
        m["arch"], round(t.stats["tokens"] / t.stats["rows"]))
        for t in tiles) / len(tiles)
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    return 100.0 * asked * launches / spent_s / peak["bf16_flops"]
