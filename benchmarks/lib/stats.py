"""Metric arithmetic: percentiles, per-token time, rates, model FLOPs."""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple


def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """The p-th percentile (0..100) by linear interpolation between
    closest ranks; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    if len(vals) == 1:
        return float(vals[0])
    k = (len(vals) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return float(vals[lo] + (vals[hi] - vals[lo]) * (k - lo))


def tpot_ms(first_s: float, last_s: float, tokens: int) -> Optional[float]:
    """Time per output token after the first, in ms; None for a
    one-token answer."""
    if tokens < 2:
        return None
    return (last_s - first_s) / (tokens - 1) * 1e3


def window_rate(stamps: Sequence[Tuple[float, int]], t_open: float,
                t_close: float) -> float:
    """Tokens a second over a window, from (time read, tokens read)
    stamps: every token read in [t_open, t_close) over the window's
    whole length. All the work, all the time: a stall at either edge
    lowers it."""
    return sum(n for t, n in stamps if t_open <= t < t_close) \
        / (t_close - t_open)


JOIN_S = 0.02   # stamps this close to a delivery's first are part of it


def delivery_rate(stamps: Sequence[Tuple[float, int]]) -> Optional[float]:
    """A steadier statistic beside `window_rate`, for a per-layer
    metric only: the engine hands tokens over in blocks of up to 64
    steps, so a window's edges cut through lumps of tokens. This rate
    runs from the first delivery to the last: the tokens of every
    delivery after the first over the time between the two (a step's
    first tokens and its block land a poll apart: one delivery). It
    leaves out less than a block at each end, and with it any stall
    there, which is why it is no end-to-end metric. None for fewer than
    two deliveries."""
    groups: List[List[float]] = []          # [time of first stamp, tokens]
    for t, n in sorted(stamps):
        if groups and t - groups[-1][0] <= JOIN_S:
            groups[-1][1] += n
        else:
            groups.append([t, n])
    if len(groups) < 2:
        return None
    return sum(g[1] for g in groups[1:]) / (groups[-1][0] - groups[0][0])


def spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median: the driver's measure of a metric's run-to-run noise."""
    import statistics

    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every
    token: all but the input embedding (a lookup) and the norm gains."""
    d, f, L = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    hd = d // cfg["n_heads"]
    attn = 2 * d * cfg["n_heads"] * hd + 2 * d * cfg["n_kv_heads"] * hd
    head = d * cfg["vocab_size"]
    return L * (attn + 3 * d * f) + head


def total_params(cfg: Dict[str, Any]) -> int:
    d, L, v = cfg["d_model"], cfg["n_layers"], cfg["vocab_size"]
    emb = v * d if cfg.get("tie_embeddings") else 2 * v * d
    return matmul_params(cfg) - d * v + emb + L * 2 * d + d


def train_flops_per_token(cfg: Dict[str, Any], seq: int) -> float:
    """Forward and backward operations a trained token requires:
    6 per matmul parameter plus 12·L·d·S of attention (causal masking
    and recomputation not counted, as model-FLOP utilisation is
    defined)."""
    return 6.0 * matmul_params(cfg) + 12.0 * cfg["n_layers"] \
        * cfg["d_model"] * seq


def mfu_pct(tokens_per_s_chip: float, flops_per_token: float,
            peak_flops: float) -> float:
    return 100.0 * tokens_per_s_chip * flops_per_token / peak_flops


def flash_flops_bytes(batch: int, heads: int, kv_heads: int, sq: int,
                      skv: int, head_dim: int, *, causal: bool,
                      backward: bool, itemsize: int = 2) -> Dict[str, float]:
    """Operations and HBM bytes one flash-attention call needs, from its
    shapes. Forward: QK^T and PV, 4·B·H·Sq·Skv·D, halved under a causal
    mask; it reads q, k, v and writes o. Backward (the dq and dkv
    kernels together): five such products, 2.5 times the forward's
    operations, as the algorithm requires (recomputing P is part of
    it); it reads q, k, v, o, do and writes dq, dk, dv."""
    fwd = 4.0 * batch * heads * sq * skv * head_dim
    if causal:
        fwd *= 0.5
    q_bytes = batch * heads * sq * head_dim * itemsize
    kv_bytes = batch * kv_heads * skv * head_dim * itemsize
    if not backward:
        return {"flops": fwd, "bytes": 2.0 * q_bytes + 2.0 * kv_bytes}
    return {"flops": 2.5 * fwd,
            "bytes": 4.0 * q_bytes + 2.0 * kv_bytes   # q, o, do in; dq out
            + 2.0 * kv_bytes}                          # dk, dv out
