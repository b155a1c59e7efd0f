"""`trainer.remat_dev_pct`: device time under `rematted_computation` over
busy time, on a made-up profile of two devices; what a program without a
checkpoint reads; the entry and its reader."""

import importlib.util
import os

import pytest

from lib import progspans
from lib.spec import Spec

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MS = 1e6          # ns
NAME = "trainer.remat_dev_pct"
CELL = "internlm2-1b8-train-fsdp4"
LAYER = "jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/" \
    "checkpoint/"
HEAD = "jit(train_step)/transpose(jvp(loss_head))/while/body/checkpoint/"


def _reader():
    spec = importlib.util.spec_from_file_location(
        "_remat_dev_pct", os.path.join(ROOT, "benchmarks", "layer_metrics",
                                       NAME + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _raw(remat=True):
    """A step of 100 ms on each of two devices: forward 0-30, the layers'
    recomputation 30-50 (gate and up), their backward 50-80, the head's
    recomputation 80-85, the optimizer 85-95, idle to 100; a `while`
    holds the backward and is no operation of its own."""
    again = "rematted_computation/" if remat else ""
    step = [("%fusion.1 = bf16[8] fusion()", 0, 30 * MS,
             "jit(train_step)/jvp(fwd)/while/body/dot_general"),
            ("%fusion.2 = bf16[8] fusion()", 30 * MS, 12 * MS,
             LAYER + again + "dot_general"),
            ("%fusion.3 = bf16[8] fusion()", 42 * MS, 8 * MS,
             LAYER + again + "mul"),
            ("%fusion.4 = bf16[8] fusion()", 50 * MS, 30 * MS,
             LAYER + "transpose/dot_general"),
            ("%fusion.5 = f32[8] fusion()", 80 * MS, 5 * MS,
             HEAD + again + "dot_general"),
            ("%fusion.6 = f32[8] fusion()", 85 * MS, 10 * MS,
             "jit(train_step)/optimizer/add"),
            ("%while.7 = (s32[]) while()", 30 * MS, 50 * MS,
             LAYER + again + "while")]
    ops = [e[:3] for e in step]
    return {"spans": [], "window": (10 * MS, 110 * MS),
            "scopes": {e[0]: e[3] for e in step},
            "devices": {"/device:TPU:0": {"ops": ops, "modules": []},
                        "/device:TPU:1": {"ops": ops, "modules": []}}}


class _Ctx:
    def __init__(self):
        self.said = []

    def log(self, **kv):
        self.said.append(kv)


def _measure(raw):
    return {"ctx": _Ctx(), "raw_profile": raw,
            "program_spans": progspans.reduce_profile(raw)}


def test_recomputed_seconds_by_whose_checkpoint_cut_to_the_window():
    got = _reader().remat_seconds(_raw())
    assert got == pytest.approx({"fwd": 2 * 0.020, "loss_head": 2 * 0.005})
    # The window's edge cuts an operation, as it cuts busy time.
    raw = _raw()
    raw["window"] = (36 * MS, 110 * MS)
    assert _reader().remat_seconds(raw)["fwd"] == pytest.approx(2 * 0.014)
    # No window: the whole of what the profile holds.
    raw["window"] = None
    assert sum(_reader().remat_seconds(raw).values()) == pytest.approx(0.05)


def test_the_share_is_over_the_devices_busy_time_and_the_split_is_logged():
    m = _measure(_raw())
    assert m["program_spans"].busy_total_s == pytest.approx(2 * 0.085)
    got = _reader().read({"name": NAME}, m)
    assert got == pytest.approx(100 * 25 / 85)
    line, = m["ctx"].said
    assert line["phase"] == "remat"
    assert line["remat_s"] == pytest.approx({"fwd": 0.04, "loss_head": 0.01})
    # It is part of what the phases' reader calls backward.
    assert got < m["program_spans"].phase_pct("bwd") \
        == pytest.approx(100 * 55 / 85)


@pytest.mark.parametrize("case", ["no-checkpoint", "no-trace", "idle"])
def test_where_there_is_nothing_to_read_it_reads_nothing(case):
    """`remat: false`, a serving program, a run without a trace: no
    number, no line, no error."""
    if case == "no-checkpoint":
        m = _measure(_raw(remat=False))
    elif case == "no-trace":
        m = {"ctx": _Ctx(), "program_spans": None}
    else:
        m = _measure({"spans": [], "window": None, "devices": {},
                      "scopes": {}})
    assert _reader().read({"name": NAME}, m) is None
    assert m["ctx"].said == []


def test_a_scope_under_no_transposed_phase_still_counts():
    raw = _raw()
    name = raw["devices"]["/device:TPU:0"]["ops"][1][0]
    raw["scopes"][name] = "jit(step)/checkpoint/rematted_computation/dot"
    got = _reader().remat_seconds(raw)
    assert got["other"] == pytest.approx(2 * 0.012)
    assert got["fwd"] == pytest.approx(2 * 0.008)


def test_the_entry_and_its_reader():
    import json

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry, = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Trainer",
        "moves": "train_tok_s_chip", "workloads": [CELL]}
    spec = Spec(ROOT, CELL)
    assert NAME in [m["name"] for m in spec.metrics("per_layer")]
    assert entry["moves"] in {m["name"]
                              for m in spec.metrics("end_to_end")}
    assert spec.load_module("layer_metrics", NAME).__file__.endswith(
        NAME + ".py")
    other = Spec(ROOT, "internlm2-1b8-batch-closed")
    assert NAME not in [m["name"] for m in other.metrics("per_layer")]
