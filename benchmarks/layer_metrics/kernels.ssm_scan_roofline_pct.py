"""Kernels: how near streaming speed a tile's selective scan runs. What
the scan must move is the model's: dt, u and y a channel, the gate, B
and C a coordinate of every real prompt token a state-space layer, and a
float32 state out a row a layer; the configuration's reference counts it
(`ssm_scan_bytes(arch, tokens, rows)`: `linear_tokens` and `rows` a tile
of the stretch's `engine.prefill_tile` spans, the mean over them, times
the launches of the prefill programs that ran), at the peak bytes/s.
Over the device time of the events whose `kernel_metadata` reads
`ssm_scan` (`ops/selective_scan`). The recurrence is bound by the vector
unit (six operations and an exponential a state element a token, no
matrix form), for which `lib/peaks.py` holds no published peak: the
share's ceiling is under 100 and it reads the distance from streaming
speed, not from the kernel's own bound. Padding's positions run too and
lower it. Nothing in a rehearsal, from a program without the kernel or
the counter, or from a reference that counts no scan."""

from lib import peaks, prefilltime, progspans

KERNEL = "ssm_scan"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    ref = m["ctx"].spec.reference
    if not ps or not hasattr(ref, "ssm_scan_bytes"):
        return None
    spent_s = ps.kernel_s.get(KERNEL)
    launches = prefilltime.launches(ps)
    tiles = [t for t in ps.named(prefilltime.TILE)
             if t.stats.get("linear_tokens") and t.stats.get("rows")]
    if not spent_s or not launches or not tiles:
        return None
    tokens, rows = (sum(t.stats[key] for t in tiles) / len(tiles) * launches
                    for key in ("linear_tokens", "rows"))
    least_s = ref.ssm_scan_bytes(
        m["arch"], tokens, rows * ref.ssm_layers(m["arch"])) \
        / peaks.peaks_for(m["devices"][0].device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / (spent_s / len(ps.devices))
