"""The reduction of the program's own spans, names and scopes: on
hand-made events, on a small trace recorded on the chip with the new
spans in it (kept beside this file as the plain lists `read_profile`
returns), and through a traced rehearsal of the tiny cells."""

import gzip
import io
import json
import os

import pytest

import run
from lib import progspans
from lib.progspans import Span

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6          # ns
KERNEL = ('%shard_map.4{n} = bf16[2,16,4096,128]{{3,2,1,0}} custom-call('
          'f32[1,2] %x), custom_call_target="tpu_custom_call", '
          'frontend_attributes={{kernel_metadata={{\n"kernel":"{k}"\n}}}}')


def _raw():
    """One device over a 100 ms window, the engine's thread and a
    client's. Device: a decode block of 8 steps that began 10 ms before
    the window (40 ms long: three quarters inside), idle 30-40, a
    prefill 40-60, idle 60-64, a block of 4 steps from 64 on past the
    window's end to 112 (three quarters inside again)."""
    ops = [("%fusion.1 = bf16[8] fusion()", -10 * MS, 40 * MS),
           (KERNEL.format(n=0, k="flash_fwd"), 40 * MS, 10 * MS),
           ("%fusion.2 = bf16[8] fusion()", 50 * MS, 10 * MS),
           ("%fusion.3 = bf16[8] fusion()", 64 * MS, 48 * MS)]
    modules = [("jit_decode_k8(11)", -10 * MS, 40 * MS),
               ("jit_prefill_sample_batch(12)", 40 * MS, 20 * MS),
               ("jit_decode_k4(13)", 64 * MS, 48 * MS)]
    eng = "llm-engine"
    spans = [
        Span("engine.tick", 0, 45 * MS, eng, {"tick": 7, "waiting": 1}),
        Span("engine.prefill_tile", 1 * MS, 9 * MS, eng,
             {"side": "slot", "bucket": 256, "rows": 2, "tile_rows": 8,
              "tokens": 300, "req_ids": "4 5"}),
        Span("engine.dispatch_block", 10 * MS, 2 * MS, eng,
             {"block": 3, "k": 4, "active": 2, "slots": 4}),
        Span("engine.process_block", 12 * MS, 30 * MS, eng,
             {"block": 2, "k": 8, "slots": 4, "active": 2, "emitted": 13,
              "discarded": 3}),
        Span("engine.fetch", 13 * MS, 28 * MS, eng, {}),
        Span("engine.tick", 45 * MS, 50 * MS, eng, {"tick": 8, "waiting": 0}),
        Span("engine.process_block", 46 * MS, 40 * MS, eng,
             {"block": 3, "k": 4, "slots": 4, "active": 2, "emitted": 8,
              "discarded": 0}),
        Span("engine.fetch", 47 * MS, 38 * MS, eng, {}),
        Span("engine.idle_wait", 96 * MS, 30 * MS, eng, {}),
        Span("other.work", 20 * MS, 50 * MS, "client", {}),
    ]
    return {"spans": spans, "window": (0.0, 100 * MS),
            "devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "scopes": {}}


def test_nesting_self_time_and_attribute_sums():
    ps = progspans.reduce_profile(_raw())
    assert ps.window_s == pytest.approx(0.1)
    self_s = ps.self_s_by_name()
    # Tick 7: 45 ms less its tile (9), dispatch (2) and block (30).
    # Tick 8: 50 less its block (40). The idle wait is cut at the window.
    assert self_s["engine.tick"] == pytest.approx(0.004 + 0.010)
    assert self_s["engine.process_block"] == pytest.approx(0.002 + 0.002)
    assert self_s["engine.fetch"] == pytest.approx(0.066)
    assert self_s["engine.idle_wait"] == pytest.approx(0.004)
    tile, fetch = ps.named("engine.prefill_tile")[0], ps.named(
        "engine.fetch")[0]
    assert tile.parent.name == "engine.tick" and tile.depth == 1
    assert fetch.parent.name == "engine.process_block" and fetch.depth == 2
    assert ps.named("other.work")[0].parent is None      # another thread
    assert ps.attribute_sums("engine.process_block") == {
        "block": 5, "k": 12, "slots": 8, "active": 4, "emitted": 21,
        "discarded": 3}
    assert "req_ids" not in ps.attribute_sums("engine.prefill_tile")
    assert tile.stats["req_ids"] == "4 5"
    # Host time a tick: (45 + 50) ms less the two fetches, over two ticks.
    assert ps.tick_host_ms() == pytest.approx((95 - 28 - 38) / 2)


def test_partial_launches_at_the_edges_count_in_proportion():
    ps = progspans.reduce_profile(_raw())
    assert ps.launches == pytest.approx({
        "jit_decode_k8": 0.75, "jit_prefill_sample_batch": 1.0,
        "jit_decode_k4": 0.75})
    assert ps.decode_steps() == pytest.approx(8 * 0.75 + 4 * 0.75)
    # 30 + 36 ms of decode programs over 9 steps.
    assert ps.decode_ms_step() == pytest.approx(66 / 9)
    assert ps.kernel_s == pytest.approx({"flash_fwd": 0.010})
    assert ps.kernel_launches == pytest.approx({"flash_fwd": 1.0})


def test_a_launch_the_profiler_cut_is_measured_against_whole_launches():
    """The profiler starts and stops just outside the window, and a
    program running then is recorded from there, or up to there: its
    event is shorter than the launch was."""
    raw = _raw()
    raw["devices"]["/device:TPU:0"]["modules"] = [
        ("jit_decode_k8(11)", -1 * MS, 11 * MS),     # began long before
        ("jit_decode_k8(11)", 10 * MS, 40 * MS),     # whole: 5 ms a step
        ("jit_decode_k4(13)", 50 * MS, 20 * MS),     # whole
        ("jit_decode_k2(14)", 95 * MS, 6 * MS),      # cut by the stop
        ("jit_prefill_sample_batch(12)", 70 * MS, 25 * MS)]
    ps = progspans.reduce_profile(raw)
    # 10 ms of a 40 ms launch; 5 ms of a 2-step launch at 5 ms a step.
    assert ps.launches == pytest.approx({
        "jit_decode_k8": 1.25, "jit_decode_k4": 1.0, "jit_decode_k2": 0.5,
        "jit_prefill_sample_batch": 1.0})
    assert ps.decode_steps() == pytest.approx(10 + 4 + 1)
    assert ps.decode_ms_step() == pytest.approx((10 + 40 + 20 + 5) / 15)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    ps = progspans.reduce_profile(_raw())
    assert ps.busy_total_s == pytest.approx(0.086)
    assert ps.idle_s == pytest.approx(0.014)
    # 30-40 lies in tick 7 > process_block > fetch (and in the client's
    # span, which is no deeper); 60-64 in tick 8 > process_block > fetch.
    assert ps.idle_gaps == [("engine.fetch", pytest.approx(0.010)),
                            ("engine.fetch", pytest.approx(0.004))]
    assert ps.idle_named_pct() == pytest.approx(100.0)
    raw = _raw()
    raw["spans"] = [s for s in raw["spans"]
                    if s.name in ("engine.tick", "other.work")]
    ps = progspans.reduce_profile(raw)
    # The tick names them, and a gap that only the tick covers is not
    # explained by it.
    assert [g[0] for g in ps.idle_gaps] == ["engine.tick", "engine.tick"]
    assert ps.idle_named_pct() == pytest.approx(0.0)


def test_phases_and_kernel_classes_from_scope_paths_and_metadata():
    assert progspans.phase_of(
        "jit(train_step)/jvp(fwd)/while/body/closed_call/dot_general") \
        == "fwd"
    assert progspans.phase_of(
        "jit(train_step)/transpose(jvp(fwd))/while/body/closed_call/"
        "checkpoint/rematted_computation/shard_map/pallas_call") == "bwd"
    assert progspans.phase_of(
        "jit(train_step)/transpose(jvp(loss_head))/while/body/mul") == "bwd"
    assert progspans.phase_of("jit(train_step)/jvp(loss_head)/div") == "fwd"
    assert progspans.phase_of("jit(train_step)/optimizer/mul") == "opt"
    assert progspans.phase_of("jit(train_step)/jvp()/while") is None
    assert progspans.phase_of(None) is None
    step = [("%fusion.9 = f32[4] fusion()", 0, 20 * MS),
            (KERNEL.format(n=1, k="flash_dq"), 20 * MS, 30 * MS),
            (KERNEL.format(n=2, k="flash_dkv"), 50 * MS, 30 * MS),
            ("%fusion.7 = f32[4] fusion()", 80 * MS, 10 * MS),
            ("%while.3 = (s32[]) while()", 0, 90 * MS),
            ("%copy.1 = f32[4] copy()", 90 * MS, 5 * MS)]
    scopes = {step[0][0]: "jit(train_step)/jvp(fwd)/while/body/dot_general",
              step[1][0]: "jit(train_step)/transpose(jvp(fwd))/while/body/"
                          "checkpoint/shard_map/pallas_call",
              step[2][0]: "jit(train_step)/transpose(jvp(fwd))/while/body/"
                          "checkpoint/shard_map/pallas_call",
              step[3][0]: "jit(train_step)/optimizer/add"}
    ps = progspans.reduce_profile({
        "spans": [], "window": (0.0, 100 * MS), "scopes": scopes,
        "devices": {"/device:TPU:0": {"ops": step, "modules": []},
                    "/device:TPU:1": {"ops": step, "modules": []}}})
    assert len(ps.devices) == 2 and ps.busy_total_s == pytest.approx(0.19)
    assert ps.phase_pct("fwd") == pytest.approx(100 * 40 / 190)
    assert ps.phase_pct("bwd") == pytest.approx(100 * 120 / 190)
    assert ps.phase_pct("opt") == pytest.approx(100 * 20 / 190)
    assert ps.kernel_launches == pytest.approx({"flash_dq": 2.0,
                                                "flash_dkv": 2.0})
    # The container (`while`) is left out; the copy has no phase.
    assert ps.phase_s[None] == pytest.approx(0.01)
    # A program that marks no phase (the parent commit) still shows what
    # autodiff transposed: that alone reads as nothing.
    ps.phase_s = {"bwd": 0.12, None: 0.07}
    assert ps.phase_pct("bwd") is None and ps.phase_pct("fwd") is None


def test_a_program_without_spans_names_or_scopes_reads_as_nothing():
    """The parent commit: `bench:*` spans only, `jit_decode_multi`."""
    raw = _raw()
    raw["spans"] = []
    raw["devices"]["/device:TPU:0"]["modules"] = [
        ("jit_decode_multi(1)", 0, 30 * MS)]
    ps = progspans.reduce_profile(raw)
    assert ps.tick_host_ms() is None and ps.decode_ms_step() is None
    assert ps.idle_named_pct() is None and ps.phase_pct("fwd") is None
    assert ps.named("engine.tick") == []
    assert [g[0] for g in ps.idle_gaps] == ["no_program_span"] * 2
    empty = progspans.reduce_profile({"spans": [], "window": None,
                                      "devices": {}, "scopes": {}})
    assert empty.window_s == 0.0 and empty.summary()["idle_gaps"] == []


def test_scope_paths_are_read_from_the_files_wire_format(tmp_path):
    """A hand-made XSpace: one device plane whose event metadata holds a
    `tf_op` stat, one host plane that is skipped."""

    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)

    def field(num, payload):
        if isinstance(payload, int):
            return varint(num << 3) + varint(payload)
        return varint(num << 3 | 2) + varint(len(payload)) + payload

    def entry(key, msg):                      # one entry of a map field
        return field(1, key) + field(2, msg)

    stat_meta = field(1, 7) + field(2, b"tf_op")
    other_meta = field(1, 9) + field(2, b"hlo_category")
    ev_meta = (field(1, 3) + field(2, b"%fusion.1 = f32[4] fusion()")
               + field(5, field(1, 9) + field(5, b"loop fusion"))
               + field(5, field(1, 7) + field(5, b"jit(f)/jvp(fwd)/mul:")))
    line = field(2, b"XLA Ops") + field(4, field(1, 3) + field(2, 5))
    plane = (field(2, b"/device:TPU:0") + field(3, line)
             + field(4, entry(3, ev_meta)) + field(5, entry(7, stat_meta))
             + field(5, entry(9, other_meta)))
    host = field(2, b"/host:CPU") + field(4, entry(3, ev_meta)) \
        + field(5, entry(7, stat_meta))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, plane) + field(1, host))
    assert progspans.scope_paths(str(path)) == {
        "%fusion.1 = f32[4] fusion()": "jit(f)/jvp(fwd)/mul"}


RECORDED = os.path.join(HERE, "recorded_program_trace.json.gz")


def test_reduction_of_a_trace_recorded_on_the_chip_with_the_spans():
    assert os.path.getsize(RECORDED) < 100_000
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    raw = {"spans": [Span(*s) for s in rec["spans"]],
           "window": tuple(rec["window"]), "scopes": rec["scopes"],
           "devices": {p: {k: [tuple(e) for e in evs]
                           for k, evs in d.items()}
                       for p, d in rec["devices"].items()}}
    ps = progspans.reduce_profile(raw)
    want = rec["expect"]
    got = ps.summary()
    for key in ("window_s", "busy_total_s", "idle_s", "decode_steps",
                "decode_ms_step", "tick_host_ms"):
        assert got[key] == pytest.approx(want[key], rel=1e-6), key
    assert got["span_counts"] == want["span_counts"]
    assert got["module_launches"] == pytest.approx(want["module_launches"])
    assert got["kernel_s"] == pytest.approx(want["kernel_s"])
    assert [g[0] for g in got["idle_gaps"]] == [
        g[0] for g in want["idle_gaps"]]
    # What the chip's trace looked like, not only that the sums repeat.
    assert {"engine.tick", "engine.fetch", "engine.deliver_first",
            "engine.process_block"} <= set(got["span_counts"])
    assert any(progspans.DECODE_BLOCK.match(n) for n in ps.launches)
    assert "flash_fwd" in ps.kernel_s and ps.idle_s > 0
    assert any(g[0].startswith("engine.") for g in ps.idle_gaps)
    assert 0 < ps.busy_total_s <= ps.window_s
    blocks = ps.named("engine.process_block")
    assert sum(b.stats["k"] for b in blocks) == pytest.approx(
        ps.decode_steps(), abs=max(b.stats["k"] for b in blocks))


ENGINE_METRICS = ("engine.host_self_ms_tick", "engine.prefill_useful_pct",
                  "engine.decode_useful_pct")


@pytest.mark.parametrize("workload,suffix", [("tiny-closed", "batch"),
                                             ("tiny-open", "online")])
def test_traced_rehearsal_reads_the_engines_own_metrics(tiny_root, workload,
                                                        suffix):
    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(2**31 + 5),
                   "--seconds", "2", "--trace", "1"], root=tiny_root,
                  rehearse=True, out=out)
    assert rc == 0
    got = json.loads(out.getvalue().strip().splitlines()[-1])["rehearsal"]
    for name in ENGINE_METRICS:
        assert got[f"{name}.{suffix}"]["value"] > 0, name
    assert 0 < got[f"engine.prefill_useful_pct.{suffix}"]["value"] <= 100
    assert 0 < got[f"engine.decode_useful_pct.{suffix}"]["value"] <= 100
    if suffix == "batch":
        assert got["engine.admit_wait_steps_p90.batch"]["value"] >= 0
    # No device plane on a CPU: what needs one reads nothing.
    assert not [n for n in got if n.startswith(
        ("engine.idle_named_pct", "model.", "kernels.", "trainer.phase"))]
    with open(os.path.join(tiny_root, ".bench_out", workload,
                           "program_spans.json")) as f:
        summary = json.load(f)
    assert summary["span_counts"]["engine.tick"] > 0
    assert summary["devices"] == 0 and summary["idle_gaps"] == []
