"""The looped cell (`ouro-2b6-mathqa-closed`) at a tiny size on the CPU:
its reference, its driver and its readers, through `run.py`, with the
real cell's metrics; the four new readers on a small made-up profile;
the real configuration's keys against the catalog row; and the
reference's operation and byte counts against a hand count at the
published sizes.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-ouro-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `ouro` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import progspans, scopetime
from lib.spec import Spec

REAL = "ouro-2b6-mathqa-closed"
CONFIG = "ouro-2.6b"
CELL = "tiny-ouro-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("engine.loop_passes_per_tok.batch", "passes/token", "lower",
     "program_counter", "Engine"),
    ("engine.loop_exit_pass_mean.batch", "pass", "lower", "program_counter",
     "Engine"),
    ("model.loop_pass_dev_ms_step.batch", "ms", "lower", "device_trace",
     "Model"),
    ("model.loop_decode_hbm_pct.batch", "%", "higher", "device_trace",
     "Model")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind jamba2's:
# readers that read true for it unchanged.
LISTED_IN = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch",
    "model.decode_launch_fixed_ms.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "engine.device_calls_per_launch.batch",
    "engine.cache_held_pct.batch", "model.prefill_mfu_pct.batch",
    "model.attn_dev_ms_step.global"]
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/ByteDance/Ouro-2.6B/blob/main/"
                  "config.json",
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": [],
        "why": "ouro 2.6B LoopLM whole at its widths: 48 sandwich-normed MHA "
               "layers (16 x 128, SwiGLU 5632) walked 4 times a token over "
               "one set of weights, an exit gate a pass, 192 cache slabs; "
               "5.34 GB bf16"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "mathqa-closed",
        "chips": 1,
        "why": "closed loop, 8 callers on 8 slots x 640, prompts 48-256, "
               "answers ~256 (128-384): a step streams 4 x 4.93 GB of layer "
               "weights + 1.5 MB a held row over 192 slabs; 5.34 GB weights "
               "+ 8.05 GB cache = 84%"}}


def _tiny_ouro_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_ouro_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="ouro_looped_decoder")


@pytest.fixture(scope="module")
def ouro_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `ouro` configuration: same driver, same reference, same metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("ouro")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-ouro.json"), "w") as f:
        json.dump(_tiny_ouro_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # One that fills a bucket, one inside one, one of three tokens, as the
    # real cell's.
    sizes["check"] = {"prompt_lens": [32, 12, 3], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-ouro", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-ouro.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-ouro", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 5501, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (jamba2's were its last cell,
    # configuration and metrics); a later PR's entries go behind these, so
    # nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("jamba2-3b") + 1
    assert cells.index(REAL) == cells.index("jamba2-reason-wide-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(names) >= 10 and len(cells) >= 11 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index(
        "model.decode_launch_fixed_ms.batch") + 1
    assert [order.index(n) for n in NEW_NAMES] == list(range(
        order.index(NEW_NAMES[0]), order.index(NEW_NAMES[0]) + len(NEW)))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                assert m["workloads"].index(REAL) == len(m["workloads"]) - 1 \
                    and m["workloads"][-2] in (
                        "jamba2-reason-wide-closed",
                        "solar-open2-rollout-closed"), m["name"]
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])
    # The decode kernel's share of its roofline counts K and V bytes over
    # `n_layers`, and this stack keeps `ut_steps x n_layers` slabs: the
    # reader would read four times too low, so the cell is not listed and
    # brings the whole step's share (`model.loop_decode_hbm_pct.batch`).
    kernel = next(m for m in bench["per_layer"]
                  if m["name"] == "kernels.decode_attn_roofline_pct.batch")
    assert REAL not in kernel["workloads"]


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith(
        "references/ouro_looped_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    for name in NEW_NAMES:
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            name.rsplit(".", 1)[0] + ".py")
    for fn in ("forward_logits", "exit_mass", "exit_pass", "pass_states",
               "prefill_flops", "decode_bytes", "loss",
               "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's, no
    # cache, no kernel, no scan: Python passes over Python layers.
    with open(spec.reference.__file__) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("`ray_tpu/models`", "")
    for word in ("pallas", "lax.scan", "fori_loop", "import ray"):
        assert word not in text, word
    assert "for _ in range(T):" in text and '"highest"' in text


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        sizes["slots"], "ended_in_window", 512)
    assert tr["prompt_len"] == {"dist": "lognormal", "median": 128,
                                "sigma": 0.5, "min": 48, "max": 256}
    assert tr["output_len"] == {"dist": "lognormal", "median": 256,
                                "sigma": 0.35, "min": 128, "max": 384}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        639, 12.0, 0.0)
    others = [json.load(open(os.path.join(ROOT, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))
              if f != "mathqa-closed.json"]
    assert tr["trace_seed"] not in [o.get("trace_seed") for o in others]
    # The issue's width, 8 x 640, or its fallback of 6 with 6 callers.
    assert sizes["slots"] in (8, 6) and sizes["max_seq_len"] == 640
    model = sizes["model"]
    assert model["param_dtype"] == "bfloat16" and model["max_seq_len"] == 640
    # The issue's precision rule: bf16 activations, or float32 over a
    # bf16 cache of the same bytes.
    assert (model["dtype"], model.get("cache_dtype")) in (
        ("bfloat16", None), ("float32", "bfloat16"))
    assert sizes["check"] == {"prompt_lens": [250, 100, 3],
                              "decode_steps": 16, "window_requests": 3}
    assert sizes["trace_seconds"] == 8.0 and len(sizes["slots_why"]) > 200
    # The largest fused block: 8 steps, not the harness's 64 (a finished
    # request's slot waits a block: `slots_why` has the sweep).
    assert sizes["decode_block"] == 8
    from lib import modelcfg, traffic
    from ray_tpu.models import periodic
    from ray_tpu.serve.llm import default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 48 <= min(lens) and max(lens) <= 256
    assert all(r.prompt_len + r.output_len <= 639 for r in trace)
    assert all(128 <= r.output_len <= 384 for r in trace)
    buckets = default_buckets(640)
    assert {next(b for b in buckets if b >= n) for n in lens} == {
        64, 128, 256}
    # Resident: 5.34 GB of weights and 192 slabs of K and V.
    cfg = modelcfg.transformer_config(real_spec.config, sizes)
    assert periodic.cache_layers(cfg) == {"window": 0, "global": 192}
    assert periodic.layer_plan(cfg) == [("periods", (48, 1), False)]
    rows = 192 * sizes["slots"] * 640 * 16 * 128 * 2 * 2
    if sizes["slots"] == 8:
        assert 8.05e9 < rows < 8.06e9
        assert 0.83 < (rows + 2 * cfg.num_params()) / 16e9 < 0.85
    # A token's rows over the 192 slabs: 1.5 MB.
    assert 192 * 16 * 128 * 2 * 2 == 1572864


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"] == []
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"] \
        and cfg["program_keys"] and cfg["not_served"]
    assert set(cfg["assumed"]) >= {
        "sandwich_norm", "attention", "cache_index", "between_passes",
        "exit_gate", "seeded_draws", "dtypes"}
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        assert not {k for k, v in row["config"].items() if cfg[k] != v}
    assert cfg["published_counts"] == {
        "num_hidden_layers": 48, "vocab_size": 49152, "total_ut_steps": 4}
    assert (cfg["d_model"], cfg["n_layers"], cfg["n_heads"],
            cfg["n_kv_heads"], cfg["head_dim"], cfg["d_ff"],
            cfg["global_attn_every"], cfg["ut_steps"],
            cfg["early_exit_threshold"], cfg["vocab_size"],
            cfg["tie_embeddings"], cfg["rope_theta"], cfg["norm_eps"]
            ) == (2048, 48, 16, 16, 128, 5632, 1, 4, 1, 49152, False,
                  1000000, 1e-6)
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "ouro"
    assert (program.ut_steps, program.early_exit_threshold,
            program.sliding_window) == (4, 1, 0)
    # 48 x 51.4 M + 2 x 100.7 M + the final norm and the gate.
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert program.num_params() == 48 * layer + 2 * 49152 * 2048 \
        + 2048 + 2049 == 2667974657


def test_the_references_counts_are_a_hand_count(real_spec):
    ref, cfg = real_spec.reference, real_spec.config
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632          # 51.4 M
    assert ref.layer_matmul_params(cfg) == per_layer == 51380224
    # A 128-token prompt: every product four times, the 8,256 causal pairs
    # of 192 attentions (2 x 16 heads x 2 x 128 a pair), the gate a pass,
    # the head once.
    n, pairs = 128, 128 * 129 // 2
    want = 4 * 48 * (2 * n * per_layer + 2 * pairs * 16 * 2 * 128) \
        + 4 * 2 * 2048 + 2 * 2048 * 49152
    assert ref.prefill_flops(cfg, n) == want
    assert 2.53e12 < want < 2.55e12
    # The products, not the attention, at these lengths: 99%.
    assert 4 * 48 * 2 * n * per_layer / want > 0.98
    # A decode step: 4 x 4.93 GB of layer weights, 0.2 GB of head, and
    # 1.5 MB a held row.
    weights = 4 * 48 * (per_layer + 4 * 2048) * 2
    assert 19.73e9 < weights < 19.74e9
    assert ref.decode_bytes(cfg, 0, 0) == weights + (
        2048 * 49152 + 2 * 2048 + 1) * 2
    step = ref.decode_bytes(cfg, 3200, 8)
    assert step == ref.decode_bytes(cfg, 0, 0) + 3200 * 1572864 \
        + 8 * 2048 * 2
    # 24.97 GB a step at 3,200 rows held: 30.5 ms at the peak.
    assert 24.9e9 < step < 25.0e9 and 30e-3 < step / 819e9 < 31e-3
    # A bf16 cache under float32 weights is counted apart.
    assert ref.decode_bytes(cfg, 100, 0, element=4, cache_element=2) \
        - ref.decode_bytes(cfg, 0, 0, element=4) == 100 * 1572864


def test_a_traced_rehearsal_is_correct_and_counts_every_pass(ouro_root,
                                                             capsys):
    line = _run(ouro_root, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4
    got = line["rehearsal"]
    # What the host counts comes through the spans on any backend: three
    # passes for every token delivered, all of them leaving at the third.
    assert got["engine.loop_passes_per_tok.batch"]["value"] == 3.0
    assert got["engine.loop_exit_pass_mean.batch"]["value"] == 3.0
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        ouro_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]
    blocks, first = sums["engine.process_block"], sums.get(
        "engine.deliver_first", {})
    assert blocks["loop_passes"] == 3 * blocks["emitted"] \
        == 3 * blocks["loop_exit_p3"]
    assert blocks["loop_exit_p1"] == blocks["loop_exit_p2"] == 0
    assert first.get("loop_passes", 0) == 3 * first.get("tokens", 0)


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """The two counters from the spans, a pass's device time by its
    scope, the step's share of the peak bandwidth from the reference's
    count; nothing from a trace without the scope or the counters."""
    ms = 1e6
    block = "jit_decode_k8(9)"
    ops = [("%a = f32[] fusion(1)", 200 * ms, 160 * ms),     # a pass: ffn
           ("%b = f32[] custom-call(2)", 360 * ms, 64 * ms),  # a pass: attn
           ("%c = f32[] fusion(3)", 424 * ms, 16 * ms)]      # head, sampler
    scopes = {
        ops[0][0]: "jit(decode_k8)/while/body/while/body/ut_pass/while/body/"
                   "ffn/dot_general",
        ops[1][0]: "jit(decode_k8)/while/body/while/body/ut_pass/while/body/"
                   "attn_global/pallas_call",
        ops[2][0]: "jit(decode_k8)/while/body/dot_general"}
    raw = {"spans": [], "window": (0.0, 500 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(block, 200 * ms, 240 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    monkeypatch.setattr(scopetime.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.spans = [
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "active": 8, "cache_rows": 8 * 8 * 640,
            "cache_rows_held": 8 * 3200}),
        progspans.Span("engine.process_block", 3.0, 1.0, "t", {
            "k": 8, "emitted": 60, "loop_passes": 240, "loop_exit_p1": 0,
            "loop_exit_p2": 0, "loop_exit_p3": 6, "loop_exit_p4": 54}),
        progspans.Span("engine.deliver_first", 4.0, 1.0, "t", {
            "tokens": 4, "loop_passes": 16, "loop_exit_p1": 0,
            "loop_exit_p2": 0, "loop_exit_p3": 0, "loop_exit_p4": 4})]
    spec = real_spec

    class Dev:
        device_kind = "TPU v5 lite"

    ctx = _Ctx()
    ctx.spec = spec
    m = {"ctx": ctx, "program_spans": ps, "arch": spec.config,
         "devices": [Dev()]}

    def read(name):
        return spec.load_module("layer_metrics", name).read(
            {"name": name}, m)

    assert ps.decode_steps() == 8
    assert read("engine.loop_passes_per_tok.batch") == 4.0
    assert read("engine.loop_exit_pass_mean.batch") == pytest.approx(
        (3 * 6 + 4 * 58) / 64)
    # 224 ms under `ut_pass` over 8 steps of 4 passes: 7 ms a pass.
    assert read("model.loop_pass_dev_ms_step.batch") == pytest.approx(7.0)
    assert read("model.attn_dev_ms_step.global") == pytest.approx(8.0)
    # 30 ms a step against the 24.97 GB of 3,200 held rows and 8 slots.
    want = 100 * spec.reference.decode_bytes(spec.config, 3200, 8) \
        / 819e9 / 0.030
    assert read("model.loop_decode_hbm_pct.batch") == pytest.approx(want)
    assert 100 < want < 105     # made up: a little over what a chip can
    # A trace of a program without the scope (the parent's, another
    # architecture's) and spans without the counters: every one of them is
    # silent, and nothing raises.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    m.pop("decode_scope_s")
    assert read("model.loop_pass_dev_ms_step.batch") is None
    ps.spans = [progspans.Span("engine.process_block", 3.0, 1.0, "t", {
        "k": 8, "emitted": 60})]
    for name in NEW_NAMES:
        assert read(name) is None, name
    # Nor from a configuration that walks its layers once.
    m["arch"] = dict(spec.config, ut_steps=1)
    assert read("model.loop_pass_dev_ms_step.batch") is None
