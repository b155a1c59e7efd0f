"""The trace reduction: interval arithmetic on hand-made events, and the
whole reduction on a small trace recorded on the chip (kept beside this
file as the plain lists `read_xplane` returns)."""

import gzip
import json
import os

import pytest

from lib import kernels, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6          # ns


def test_union_and_subtraction_of_intervals():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert xplane.union_ns([]) == 0
    assert xplane.merged([(5, 20), (0, 10), (30, 40)]) == [(0, 20), (30, 40)]
    # [0,10) and [20,30) minus [5,25): 5 + 5 left.
    assert xplane.subtract_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert xplane.subtract_ns([(0, 10)], []) == 10
    assert xplane.subtract_ns([(0, 10)], [(0, 10)]) == 0
    assert xplane.subtract_ns([(0, 10)], [(2, 3), (4, 6)]) == 7


def test_hlo_text_is_cut_to_a_name_and_a_shape():
    raw = ("%copy.80 = bf16[16,24,2048,8,128]{4,3,2,1,0:T(8,128)(2,1)} "
           "copy(%x)")
    assert xplane.op_name(raw) == "copy.80 bf16[16,24,2048,8,128]"
    assert xplane.op_name("%while.31 = (s32[]{:T(128)}, bf16[2]) while(") \
        == "while.31"
    assert xplane.op_name("_fwd_kernel") == "_fwd_kernel"
    assert xplane.CONTAINER.match("while.31")
    assert not xplane.CONTAINER.match("while_body_fusion.2")


def _planes():
    """Two devices over a 100 ms window. Device 0: a matmul 0-40, an
    all-gather 30-60 (exposed 40-60), a flash kernel 70-90. Idle 60-70
    and 90-100. Device 1: busy 0-100 with one fusion."""
    return {
        "/device:TPU:0": {
            "XLA Ops": [("fusion.1", 0, 40 * MS),
                        ("all-gather.3", 30 * MS, 30 * MS),
                        ("shard_map.420", 70 * MS, 20 * MS)],
            "XLA Modules": [("jit_train_step(1)", 0, 90 * MS)],
        },
        "/device:TPU:1": {
            "XLA Ops": [("fusion.1", 0, 100 * MS)],
            "XLA Modules": [("jit_train_step(1)", 0, 100 * MS)],
        },
        "/host:CPU": {
            "bench-tracer": [("bench:window", 0, 100 * MS)],
            "main": [("bench:block_until_ready", 55 * MS, 20 * MS),
                     ("bench:step_dispatch", 91 * MS, 5 * MS)],
        },
    }


def test_reduction_of_a_hand_made_trace():
    tr = xplane.reduce_trace(_planes())
    assert tr.window_s == pytest.approx(0.1)
    assert tr.busy_s == pytest.approx([0.08, 0.1])
    assert tr.busy_mean_s == pytest.approx(0.09)
    assert tr.op_s["fusion.1"] == pytest.approx(0.14)
    assert tr.module_s["jit_train_step(1)"] == pytest.approx(0.19)
    assert tr.module_n["jit_train_step(1)"] == 1
    # Collectives: 30 ms on device 0, 20 of them with no compute beside.
    assert tr.collective_s == pytest.approx(0.015)
    assert tr.collective_exposed_s == pytest.approx(0.010)
    assert tr.ops_matching(kernels.FLASH_EVENTS) == pytest.approx(0.02)
    assert tr.modules_matching("train_step") == (pytest.approx(0.19), 1)
    # The two gaps of device 0, longest first, named by the host's span.
    assert [g[0] for g in tr.idle_gaps] == ["block_until_ready",
                                            "step_dispatch"]
    assert [g[1] for g in tr.idle_gaps] == pytest.approx([0.01, 0.01])
    b = tr.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.14)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_events_are_cut_to_the_window_span():
    planes = _planes()
    planes["/host:CPU"]["bench-tracer"] = [("bench:window", 50 * MS,
                                            50 * MS)]
    tr = xplane.reduce_trace(planes)
    assert tr.window_s == pytest.approx(0.05)
    assert tr.busy_s == pytest.approx([0.03, 0.05])   # 50-60 and 70-90


def test_a_trace_without_device_events_reduces_to_nothing():
    tr = xplane.reduce_trace({"/host:CPU": {"t": [("bench:window", 0, 5)]}})
    assert tr.busy_s == [] and tr.op_s == {}
    assert tr.breakdown() == {"device_ops": [], "idle_gaps": []}


RECORDED = os.path.join(HERE, "recorded_trace.json.gz")


def test_reduction_of_a_trace_recorded_on_the_chip():
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    planes = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
              for p, lines in rec["planes"].items()}
    tr = xplane.reduce_trace(planes)
    want = rec["expect"]
    assert len(tr.devices) == want["devices"]
    assert tr.window_s == pytest.approx(want["window_s"], rel=1e-6)
    assert tr.busy_mean_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < tr.busy_mean_s <= tr.window_s
    assert sum(tr.op_s.values()) >= tr.busy_s[0] * 0.9
    top = tr.breakdown()["device_ops"][0][0]
    assert top == want["top_op"]
    assert tr.modules_matching("decode|prefill")[0] > 0
