"""Trainer: model-FLOP utilisation. (6 per matmul parameter + 12·L·d·S)
operations a token, times tokens a second a chip, over the chip's bf16
peak. Recomputed operations (remat) do not count."""

from lib import peaks, stats


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    if "tok_s_chip" not in m:
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)["bf16_flops"]
    return stats.mfu_pct(
        m["tok_s_chip"], stats.train_flops_per_token(m["arch"], m["seq_len"]),
        peak)
