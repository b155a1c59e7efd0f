"""The routed period-stack cell (`trinity-mini-reason-closed`) at a tiny
size on the CPU: its reference, its driver, its readers and its check
script, through `run.py`, with the real cell's metrics; and the readers
of the device's scopes on a small made-up profile.

`ENTRIES` is what the cell appended to BENCHMARK.json. The tiny copy of
the benchmark (`conftest.make_tiny_root`) maps the cell to
`tiny-afmoe-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `afmoe` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import progspans, scopetime
from lib.spec import Spec

REAL = "trinity-mini-reason-closed"
CELL = "tiny-afmoe-closed"
# (name, unit, better, source, layer): all move `serve_out_tok_s`.
NEW_METRICS = [
    ("model.moe_dev_ms_step.batch", "ms", "lower", "device_trace", "Model"),
    ("model.attn_dev_ms_step.window", "ms", "lower", "device_trace",
     "Model"),
    ("model.attn_dev_ms_step.global", "ms", "lower", "device_trace",
     "Model"),
    ("engine.moe_experts_hit_pct.batch", "%", "lower", "program_counter",
     "Engine"),
    ("engine.moe_load_max_over_mean.batch", "ratio", "lower",
     "program_counter", "Engine"),
    ("kernels.moe_experts_roofline_pct.batch", "%", "higher",
     "device_trace", "Kernels")]
NEW_READERS = [m[0] for m in NEW_METRICS]
ENTRIES = {
    "config": {
        "name": "trinity-mini-l5",
        "source": "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/"
                  "config.json",
        "file": "benchmarks/configs/trinity-mini-l5.json",
        "reduced": ["n_layers", "n_dense_layers"],
        "why": "afmoe 26B-A3B at published widths: 1 dense + one period of "
               "3 window + 1 global layers, 128 sigmoid-routed experts top "
               "8 + 1 shared, 200192-row vocabulary: one pipeline stage's "
               "five layers on one chip"},
    "workload": {
        "name": REAL, "config": "trinity-mini-l5",
        "traffic": "reason-closed", "chips": 1,
        "why": "closed loop, 32 callers on 32 slots x 4096, prompts ~512, "
               "answers ~2048: decode past the 2048 window through routed "
               "experts (87% hit a step), a two-kind cache, float32 "
               "activations; prefill ~17%"},
    # Accepted metrics whose `workloads` gain the cell: readers that fit
    # it unchanged.
    "listed_in": [
        "serve_out_tok_s", "engine.occupancy_pct.batch",
        "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
        "model.decode_dev_ms_step_exact.batch", "device.idle_pct.batch",
        "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
        "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
        "engine.decode_useful_pct.batch",
        "engine.admit_wait_steps_p90.batch", "engine.idle_named_pct.batch"],
    "per_layer": [
        {"name": n, "unit": u, "better": b, "source": src, "layer": layer,
         "moves": "serve_out_tok_s", "workloads": [REAL]}
        for n, u, b, src, layer in NEW_METRICS],
}


def _tiny_afmoe_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_afmoe_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="afmoe_decoder")


@pytest.fixture(scope="module")
def afmoe_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `afmoe` configuration: same driver, same reference, same metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("afmoe")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-afmoe.json"), "w") as f:
        json.dump(_tiny_afmoe_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # Prompts longer than the window of 8, decode across the ring's edge.
    sizes["check"] = {"prompt_lens": [30, 5, 12], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # The copy lists the stand-in under every metric the real cell is
    # listed under; the cell itself and its configuration are added here.
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(ENTRIES["listed_in"]) | set(NEW_READERS)
    bench["configs"].append({
        "name": "tiny-afmoe", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-afmoe.json", "why": "test only"})
    bench["workloads"].append(dict(
        ENTRIES["workload"], name=CELL, config="tiny-afmoe",
        traffic="tiny-closed"))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 281, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    assert bench["configs"][-1] == ENTRIES["config"]
    assert bench["workloads"][-1] == ENTRIES["workload"]
    assert bench["per_layer"][-len(NEW_METRICS):] == ENTRIES["per_layer"]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in ENTRIES["listed_in"]:
                assert m["workloads"][-1] == REAL
            elif m["name"] not in NEW_READERS:
                assert REAL not in m.get("workloads", [])


def test_the_real_cell_names_its_files_and_every_new_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith("references/afmoe_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    names = {m["name"] for m in spec.metrics("per_layer")}
    assert set(NEW_READERS) <= names
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    for fn in ("forward_logits", "loss", "train_flops_per_token",
               "chosen_experts", "moe_experts_min_bytes"):
        assert callable(getattr(spec.reference, fn)), fn
    # The entries keep to the contract's form.
    assert all(len(e["why"]) <= 200 for e in (ENTRIES["config"],
                                              ENTRIES["workload"]))
    # Every catalog key of the configuration is there as published.
    cfg = spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"] and cfg["assumed"]
    assert all(k in cfg for k in cfg["published"])
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_experts"],
            cfg["num_hidden_layers"], cfg["vocab_size"]) == (
        2048, 128, 128, 32, 200192)


def test_the_tiny_cell_is_correct_against_its_own_reference(afmoe_root,
                                                            capsys):
    line = _run(afmoe_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"serve_out_tok_s", "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4


def test_a_traced_rehearsal_reads_the_routing_counters(afmoe_root):
    line = _run(afmoe_root, trace=1)
    got = line["rehearsal"]
    # What the program counts comes through the spans on any backend.
    hit = got["engine.moe_experts_hit_pct.batch"]["value"]
    assert 0 < hit <= 100
    # 8 experts, top 2: the fullest expert holds at least the mean.
    assert got["engine.moe_load_max_over_mean.batch"]["value"] >= 1.0
    assert got["engine.decode_useful_pct.batch"]["value"] > 0
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        afmoe_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.process_block"]
    assert sums["moe_expert_steps"] == sums["k"] * 4 * 8
    assert sums["moe_rows"] == sums["k"] * 4 * 4 * 2      # slots x top 2
    assert sums["moe_experts_hit"] <= sums["moe_expert_steps"]


def test_the_check_script_reads_sound_control_and_flips(afmoe_root):
    from checks import serve_logits

    out = io.StringIO()
    assert serve_logits.main(
        ["--workload", CELL, "--seeds", "5,2147483653", "--control", "1",
         "--control-len", "40"], root=afmoe_root, rehearse=True,
        out=out) == 0
    rows = [json.loads(ln) for ln in out.getvalue().splitlines()]
    last = rows[-1]
    assert last["seeds"] == 2 and last["limit"] == 0.08
    assert last["sound_largest_rel_rms_err"] < 1e-4
    # float32 program against float32 reference: the same experts.
    assert last["routing_pairs"] == 2 * 4 * 40 and last["routing_flips"] == 0
    # Weights rounded to 8-bit floats are told apart by the limit.
    assert last["control_smallest_rel_rms_err"] > last["limit"]


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_scope_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the decode programs, per device step;
    the roofline share from the counters; nothing from a trace without
    the scopes."""
    ms = 1e6
    ops = [("%a = f32[] fusion(1)", 0.0, 2 * ms),
           ("%ragged-dot-none.2 = f32[] custom-call(2)", 2 * ms, 6 * ms),
           ("%c = f32[] fusion(3)", 8 * ms, 1 * ms),
           ("%d = f32[] fusion(4)", 9 * ms, 3 * ms),
           ("%e = f32[] fusion(5)", 12 * ms, 5 * ms),   # a prefill's
           ("%while.1 = () while(6)", 0.0, 12 * ms)]
    scopes = {
        ops[0][0]: "jit(decode_k4)/while/body/moe_router/dot_general",
        ops[1][0]: "ragged-dot-none",       # the kernel loses its scope
        ops[2][0]: "jit(decode_k4)/while/body/moe_shared/dot_general",
        ops[3][0]: "jit(decode_k4)/while/body/attn_window/dot_general",
        ops[4][0]: "jit(prefill_sample_batch)/moe_experts/ragged_dot",
        ops[5][0]: "jit(decode_k4)/while"}
    raw = {"spans": [], "window": (0.0, 20 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [("jit_decode_k4(7)", 0.0, 12 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    monkeypatch.setattr(scopetime.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.spans = [progspans.Span("engine.process_block", 0.0, 1.0, "t", {
        "k": 4, "moe_expert_steps": 4 * 2 * 128, "moe_experts_hit": 800,
        "moe_rows": 4 * 2 * 256, "moe_rows_max": 40})]
    spec = real_spec

    class Dev:
        device_kind = "TPU v5 lite"

    ctx = _Ctx()
    ctx.spec = spec
    m = {"ctx": ctx, "program_spans": ps, "arch": spec.config,
         "devices": [Dev()]}

    def read(name):
        metric = {"name": name}
        return spec.load_module("layer_metrics", name).read(metric, m)

    assert ps.decode_steps() == 4
    assert read("model.moe_dev_ms_step.batch") == pytest.approx(9 / 4)
    assert read("model.attn_dev_ms_step.window") == pytest.approx(3 / 4)
    assert read("model.attn_dev_ms_step.global") is None
    assert read("engine.moe_experts_hit_pct.batch") == pytest.approx(
        100 * 800 / 1024)
    assert read("engine.moe_load_max_over_mean.batch") == pytest.approx(
        40 * 128 / 2048)
    # 200 experts and 512 rows a step: their bytes at 819 GB/s over the
    # 1.5 ms a step under `moe_experts`.
    least_ms = 2 * (200 * 3 * 2048 * 1024 + 512 * 2 * 2048) / 819e9 * 1e3
    assert read("kernels.moe_experts_roofline_pct.batch") == pytest.approx(
        100 * least_ms / 1.5)
    # A trace of a program without the scopes: the kernel is still told
    # by its name, a scope's reader is silent; and with no such kernel
    # either, every reader is.
    raw["scopes"] = {k: "jit(decode_k4)/while/body/dot_general"
                     for k in scopes}
    m.pop("decode_scope_s")
    assert read("model.moe_dev_ms_step.batch") == pytest.approx(6 / 4)
    assert read("model.attn_dev_ms_step.window") is None
    raw["devices"]["/device:TPU:0"]["ops"] = [
        (name.replace("ragged-dot-none", "fusion"), s, d)
        for name, s, d in ops]
    m.pop("decode_scope_s")
    assert read("model.moe_dev_ms_step.batch") is None
    assert read("kernels.moe_experts_roofline_pct.batch") is None
