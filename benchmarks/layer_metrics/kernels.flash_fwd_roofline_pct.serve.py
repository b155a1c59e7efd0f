"""Kernels: the forward flash kernel's share of its roofline where it
serves, a prefill tile's causal attention. The least time the chip could
take a launch is the larger of its operations at the peak FLOP/s and its
bytes at the peak bytes/s (`lib/stats.flash_flops_bytes`: QK^T and PV
over the pairs at or under the diagonal; q, k, v read and o written once,
K and V by their own heads) at the *tile's* shape: `tile_rows` x `bucket`
of the stretch's `engine.prefill_tile` spans (the mean over them: the work
as the engine asks for it, padding included) and the configuration's
heads. Times the launches that ran, over the device time of the events
whose `kernel_metadata` reads `flash_fwd` (`ops/flash_attention`), a layer
one launch. By shapes and never by the kernel's own grid, so a change to
the grid moves the time and not the yardstick. Right where every tile of
the stretch takes the kernel at one cost a launch (a dense stack with
every bucket at or over the kv crossover); nothing in a rehearsal, or
where the trace holds no such event or no tile."""

from lib import peaks, progspans, stats

KERNEL = "flash_fwd"


def least_s_launch(arch, peak, rows: int, bucket: int) -> float:
    head = arch.get("head_dim") or arch["d_model"] // arch["n_heads"]
    fb = stats.flash_flops_bytes(rows, arch["n_heads"], arch["n_kv_heads"],
                                 bucket, bucket, head, causal=True,
                                 backward=False)
    return max(fb["flops"] / peak["bf16_flops"],
               fb["bytes"] / peak["hbm_bytes_per_s"])


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    if not ps:
        return None
    spent_s = ps.kernel_s.get(KERNEL)
    launches = ps.kernel_launches.get(KERNEL)
    tiles = [(s.stats["tile_rows"], s.stats["bucket"])
             for s in ps.named("engine.prefill_tile")
             if isinstance(s.stats.get("tile_rows"), int)
             and isinstance(s.stats.get("bucket"), int)]
    if not spent_s or not launches or not tiles:
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least = sum(least_s_launch(m["arch"], peak, rows, bucket)
                for rows, bucket in tiles) / len(tiles)
    return 100.0 * least * launches / spent_s
