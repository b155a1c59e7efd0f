"""Metric arithmetic on hand-made inputs."""

import math

import pytest

from lib import peaks, stats


@pytest.mark.parametrize("values,p,want", [
    ([5.0], 90, 5.0),
    ([1, 2, 3, 4, 5], 50, 3.0),
    ([1, 2, 3, 4, 5], 100, 5.0),
    ([1, 2, 3, 4, 5], 0, 1.0),
    (list(range(1, 12)), 90, 10.0),        # rank 9 of 0..10
    ([10, 20], 90, 19.0),                  # interpolated
    ([3, 1, 2], 50, 2.0),                  # order does not matter
])
def test_percentile(values, p, want):
    assert stats.percentile(values, p) == pytest.approx(want)


def test_percentile_of_nothing_is_nothing():
    assert stats.percentile([], 90) is None


def test_tpot_is_per_token_after_the_first():
    # First token at 1.0 s, the 11th at 1.5 s: ten gaps of 50 ms.
    assert stats.tpot_ms(1.0, 1.5, 11) == pytest.approx(50.0)
    assert stats.tpot_ms(1.0, 1.0, 1) is None


def test_tpot_does_not_depend_on_the_output_length():
    # Whole-request latency doubles with the length; TPOT does not.
    assert stats.tpot_ms(0.0, 0.05 * 99, 100) == pytest.approx(
        stats.tpot_ms(0.0, 0.05 * 199, 200))


def test_window_rate_is_every_token_over_the_whole_window():
    # Blocks of 100 tokens every 2 s from t = 11; the window is 10..20.
    stamps = [(9.0, 100), (11.0, 100), (13.0, 100), (15.0, 100),
              (20.0, 100)]
    assert stats.window_rate(stamps, 10.0, 20.0) == pytest.approx(30.0)
    # A stall after the last delivery lowers it; the steadier statistic
    # beside it (first delivery to last) cannot see that.
    assert stats.delivery_rate(stamps[1:4]) == pytest.approx(50.0)
    assert stats.window_rate([], 10.0, 20.0) == 0.0


def test_delivery_rate_runs_from_first_to_last_delivery():
    # Blocks of 100 tokens every 2 s; the first tokens of a new request
    # land 5 ms before the block of the same step: one delivery.
    stamps = [(10.0, 100), (11.995, 1), (12.0, 100), (14.0, 100)]
    assert stats.delivery_rate(stamps) == pytest.approx(201 / 4.0)
    # The same deliveries, wherever the window's edges cut: the same rate
    # (a plain count over the window would swing by a whole block).
    assert stats.delivery_rate(stamps[1:]) == pytest.approx(100 / 2.005)
    assert stats.delivery_rate([(1.0, 5)]) is None
    assert stats.delivery_rate([]) is None


def test_spread_is_interquartile_over_median():
    import statistics

    vals = [100, 101, 102, 103, 104, 105]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == pytest.approx(
        (q[2] - q[0]) / statistics.median(vals))


ARCH = {"d_model": 2048, "d_ff": 8192, "n_layers": 24, "n_heads": 16,
        "n_kv_heads": 8, "vocab_size": 92544, "tie_embeddings": False}


def test_parameter_counts_of_internlm2_1b8():
    # 24 x (attention 2*2048*2048 + 2*2048*1024 + ffn 3*2048*8192)
    attn = 2 * 2048 * 2048 + 2 * 2048 * 1024
    assert stats.matmul_params(ARCH) == 24 * (attn + 3 * 2048 * 8192) \
        + 2048 * 92544
    assert stats.total_params(ARCH) == pytest.approx(1.889e9, rel=1e-3)


def test_mfu_arithmetic():
    fpt = stats.train_flops_per_token(ARCH, 4096)
    assert fpt == 6 * stats.matmul_params(ARCH) + 12 * 24 * 2048 * 4096
    # A chip at its peak trains peak / fpt tokens a second: 100%.
    peak = peaks.peaks_for("TPU v5 lite")["bf16_flops"]
    assert stats.mfu_pct(peak / fpt, fpt, peak) == pytest.approx(100.0)
    assert stats.mfu_pct(0.4 * peak / fpt, fpt, peak) == pytest.approx(40.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(ValueError):
        peaks.peaks_for("cpu")


def test_flash_operations_and_bytes():
    f = stats.flash_flops_bytes(2, 16, 8, 4096, 4096, 128, causal=True,
                                backward=False)
    assert f["flops"] == 0.5 * 4 * 2 * 16 * 4096 * 4096 * 128
    q = 2 * 16 * 4096 * 128 * 2
    kv = 2 * 8 * 4096 * 128 * 2
    assert f["bytes"] == 2 * q + 2 * kv
    b = stats.flash_flops_bytes(2, 16, 8, 4096, 4096, 128, causal=True,
                                backward=True)
    assert b["flops"] == 2.5 * f["flops"]
    assert b["bytes"] == 4 * q + 4 * kv
    # At this size the kernel is bound by compute, not by bandwidth.
    pk = peaks.peaks_for("TPU v5 lite")
    assert f["flops"] / pk["bf16_flops"] > f["bytes"] / pk["hbm_bytes_per_s"]
    assert math.isfinite(b["flops"])
