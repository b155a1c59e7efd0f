"""Kernels: the share of its roofline that a tile's attention reaches,
per head after the up-projection, whatever implements it. The least time
the chip could take a launch is, a layer, the larger of the operations of
the pairs at or under the diagonal (scores nope + rope wide, values
`v_head_dim` wide) at the peak FLOP/s and q, k, v read and o written once
at the peak bytes/s; the configuration's reference counts both
(`prefill_attn_flops_bytes`) at the *tile's* shape, `tile_rows` x `bucket`
of the stretch's `engine.prefill_tile` spans (the mean over them: the work
as the engine asks for it, padding included), every layer. Times the
launches of the prefill programs that ran, over the device time under the
scope `attn_latent` inside them (`models/latent.py`: the kernel, the
heads' keys put together, the tile's rows into the cache). By shapes and
never by a kernel's grid. Nothing in a rehearsal, from a program without
the scope, or from a reference that counts no such attention."""

from lib import peaks, prefilltime, progspans

SCOPE = "attn_latent"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    if not ps or not hasattr(ref, "prefill_attn_flops_bytes"):
        return None
    spent_s = (prefilltime.scope_seconds(m) or {}).get(SCOPE)
    launches = prefilltime.launches(ps)
    tiles = [(s.stats["tile_rows"], s.stats["bucket"])
             for s in ps.named(prefilltime.TILE)
             if isinstance(s.stats.get("tile_rows"), int)
             and isinstance(s.stats.get("bucket"), int)]
    if not spent_s or not launches or not tiles:
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)

    def least_s(rows, bucket):
        fb = ref.prefill_attn_flops_bytes(m["arch"], rows, bucket)
        return m["arch"]["n_layers"] * max(
            fb["flops"] / peak["bf16_flops"],
            fb["bytes"] / peak["hbm_bytes_per_s"])

    least = sum(least_s(*t) for t in tiles) / len(tiles)
    return 100.0 * least * launches / spent_s
