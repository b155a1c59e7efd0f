"""Model: the prefill programs' share of the chip's peak, the whole of a
tile: the operations the prompts of the stretch's tiles ask for (the
configuration's reference counts them: `prefill_flops(arch, tokens)`:
products of the parameters a token uses, the attention of the pairs the
mask lets through, the head at one position; a tile of several rows by
its mean prompt) over the device time of `jit_prefill*` /
`jit_first_token*` at the peak bf16 FLOP/s. Tiles and launches are
matched as `model.prefill_dev_ms_req` matches them: the mean tile of the
stretch's `engine.prefill_tile` spans times the launches, an edge launch
by its part. Padding, masked-out pairs and the second bf16 term of a
float32 activation are work of the program's and not of the model's, so
they lower this share. It bounds what any kernel's gain can give the
cell's `ttft_p90_ms`."""

from lib import peaks, prefilltime, progspans


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    if ps is None or not ps.devices or not hasattr(ref, "prefill_flops"):
        return None
    tiles = [t for t in ps.named(prefilltime.TILE)
             if t.stats.get("rows") and t.stats.get("tokens")]
    secs = sum(s for name, s in ps.module_s.items()
               if progspans.PREFILL.match(name)) / len(ps.devices)
    n = prefilltime.launches(ps)
    if not tiles or not secs or not n:
        return None
    asked = sum(t.stats["rows"] * ref.prefill_flops(
        m["arch"], round(t.stats["tokens"] / t.stats["rows"]))
        for t in tiles) / len(tiles)
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    return 100.0 * asked * n / secs / peak["bf16_flops"]
