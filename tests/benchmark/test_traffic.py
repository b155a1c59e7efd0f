"""The rule PR 23 lacked: the seed never changes the offered work."""

import os

import numpy as np
import pytest

from conftest import KEPT_CHAT, ROOT, TINY_FILES
from lib import modelcfg, traffic
from lib.spec import Spec

SEEDS = (7, 2**31 + 12345)        # the driver's seeds pass 32 signed bits


def _cells():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]] \
            + [KEPT_CHAT["name"]]


def _spec(cell, request):
    """A cell of BENCHMARK.json, or the one kept for later."""
    if cell == KEPT_CHAT["name"]:
        return request.getfixturevalue("kept_chat_spec")
    return Spec(ROOT, cell)


@pytest.mark.parametrize("cell", _cells())
def test_offered_work_is_the_same_for_every_seed(cell, request):
    spec = _spec(cell, request)
    if spec.traffic["driver"] == "train":
        # A step's shape is the whole of a training cell's offered work.
        assert {"batch_size", "seq_len"} <= set(spec.traffic)
        assert "trace_seed" not in spec.traffic
        return
    a = traffic.make_trace(spec.traffic)
    b = traffic.make_trace(spec.traffic)
    assert a == b and len(a) == spec.traffic["n_requests"]
    # make_trace takes no seed at all; what the seed makes differs.
    ids = [traffic.token_ids(s, a, spec.config["vocab_size"])
           for s in SEEDS]
    assert [len(p) for p in ids[0]] == [r.prompt_len for r in a]
    assert [len(p) for p in ids[0]] == [len(p) for p in ids[1]]
    assert ids[0] != ids[1]
    assert ids[0] == traffic.token_ids(SEEDS[0], a,
                                       spec.config["vocab_size"])


@pytest.mark.parametrize("cell", _cells())
def test_every_request_fits_its_cell(cell, request):
    spec = _spec(cell, request)
    if spec.traffic["driver"] == "train":
        return
    for r in traffic.make_trace(spec.traffic):
        assert r.prompt_len + r.output_len <= spec.traffic["max_total_len"]
        assert spec.traffic["max_total_len"] < spec.sizes["max_seq_len"]
        assert r.output_len >= 1


def test_weights_depend_on_the_seed_and_only_on_it():
    import jax

    cfg = modelcfg.transformer_config(
        TINY_FILES["configs/tiny.json"],
        {"model": {"dtype": "float32", "param_dtype": "float32"}})
    a, b, a2 = (modelcfg.make_params(cfg, s)
                for s in (SEEDS[0], SEEDS[1], SEEDS[0]))
    assert not np.allclose(a["embed"], b["embed"])
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(a), jax.tree.leaves(a2)))
    # Two seeds that agree in their low 31 bits still differ.
    c = modelcfg.make_params(cfg, SEEDS[0] + 2**31)
    assert not np.allclose(a["embed"], c["embed"])


def test_lengths_lie_on_the_quantile_grid():
    import random

    dist = {"dist": "lognormal", "median": 320, "sigma": 0.7, "min": 32,
            "max": 1536}
    vals = traffic.stratified(dist, 256, random.Random(1))
    assert min(vals) >= 32 and max(vals) <= 1536
    assert sorted(vals)[127] <= 320 <= sorted(vals)[128]
    # Another order, the same multiset.
    assert sorted(vals) == sorted(
        traffic.stratified(dist, 256, random.Random(2)))
    assert vals != sorted(vals)


def test_arrival_rate_is_exact_over_the_trace():
    import random

    n, rate = 256, 1.25
    times = traffic.arrival_times(n, rate, random.Random(3))
    assert times == sorted(times)
    # The mean of the exponential's quantile grid is within 1% of 1/rate.
    assert times[-1] / n == pytest.approx(1.0 / rate, rel=0.01)


def test_a_rate_only_rescales_the_schedule(kept_chat_spec):
    spec = kept_chat_spec
    a = traffic.make_trace(spec.traffic, rate=1.0)
    b = traffic.make_trace(spec.traffic, rate=2.0)
    assert [(r.prompt_len, r.output_len) for r in a] == \
        [(r.prompt_len, r.output_len) for r in b]
    assert [r.due_s for r in a] == pytest.approx(
        [2.0 * r.due_s for r in b])


def test_unknown_distribution_is_an_error():
    import random

    with pytest.raises(ValueError):
        traffic.stratified({"dist": "zipf"}, 4, random.Random(0))
