"""The hybrid cell (`kimi-linear-docgen-closed`) at a tiny size on the
CPU: its reference, its driver and its readers, through `run.py`, with
the real cell's metrics; the new readers on a small made-up profile; the
real configuration's keys against the catalog row; and the reference's
byte counts against a hand count at the published sizes, each over its
own layers of the kind (20 KDA, 7 MLA), never all 27.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-kimi-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `kimi_linear` configuration."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans, scopetime
from lib.spec import Spec

REAL = "kimi-linear-docgen-closed"
CONFIG = "kimi-linear-48b-ep16"
CELL = "tiny-kimi-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("model.hybrid_decode_hbm_pct.batch", "%", "higher", "device_trace",
     "Model"),
    ("engine.cache_state_share_pct.batch", "%", "lower", "program_counter",
     "Engine"),
    ("model.attn_dev_ms_req.latent", "ms", "lower", "device_trace", "Model")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell: readers that read
# true for it unchanged.
LISTED_IN = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch",
    "model.decode_launch_fixed_ms.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "engine.device_calls_per_launch.batch",
    "model.moe_dev_ms_step.batch", "engine.moe_experts_hit_pct.batch",
    "engine.moe_load_max_over_mean.batch",
    "kernels.moe_experts_roofline_pct.batch",
    "engine.moe_pairs_held_pct.batch", "engine.cache_held_pct.batch",
    "model.prefill_mfu_pct.batch", "model.attn_dev_ms_step.linear",
    "model.attn_dev_ms_req.linear", "kernels.linear_attn_roofline_pct.batch",
    "kernels.linear_prefill_attn_roofline_pct.batch",
    "engine.linear_state_live_pct.batch", "model.attn_dev_ms_step.latent",
    "model.mla_proj_dev_ms_step.batch",
    "kernels.latent_attn_roofline_pct.batch",
    "kernels.latent_prefill_attn_roofline_pct.batch"]
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/moonshotai/"
                  "Kimi-Linear-48B-A3B-Instruct/blob/main/config.json",
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": ["moe_experts", "vocab_size"],
        "why": "kimi linear 48B, all 27 layers at its widths: 20 KDA layers "
               "(32 x 128 x 128 f32 state) + 7 NoPE MLA layers (32 heads, "
               "rows of 576), 16 of 256 experts a layer (1 of 16 chips), 1/8 "
               "vocab; 8.59 GB"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "docgen-closed",
        "chips": 1,
        "why": "closed loop, 32 callers on 32 slots x 6144, prompts "
               "1024-4000 (one tile), answers ~1024 (512-2048): a step "
               "rewrites 20 x 2.1 MB of f32 state a slot and reads 7 "
               "layers' latent rows; 26 routed layers"}}


def _tiny_kimi_config():
    from ray_tpu.models import configs

    cfg = configs.tiny_kimi_test(periods=1)     # seven layers
    arch = dataclasses.asdict(cfg)
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del arch[key]
    return dict(arch, linear_attn_config={
        k: list(v) if isinstance(v, tuple) else v
        for k, v in cfg.linear_attn_config}, reference="kimi_linear_decoder")


@pytest.fixture(scope="module")
def kimi_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `kimi_linear` configuration: same driver, same reference, same
    metrics."""
    root = make_tiny_root(str(tmp_path_factory.mktemp("kimi")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-kimi.json"), "w") as f:
        json.dump(_tiny_kimi_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # Past a chunk of 64, under one, and shorter than the convolution.
    sizes["check"] = {"prompt_lens": [70, 12, 2], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-kimi", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-kimi.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-kimi", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 5701, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (ouro's were its last cell and
    # configuration); a later PR's entries go behind these.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("ouro-2.6b") + 1
    assert cells.index(REAL) == cells.index("ouro-2b6-mathqa-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(names) >= 11 and len(cells) >= 12 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index(
        "model.loop_decode_hbm_pct.batch") + 1
    assert [order.index(n) for n in NEW_NAMES] == list(range(
        order.index(NEW_NAMES[0]), order.index(NEW_NAMES[0]) + len(NEW)))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                assert m["workloads"].index(REAL) == len(m["workloads"]) - 1 \
                    and m["workloads"][-2] in (
                        "ouro-2b6-mathqa-closed", "jamba2-reason-wide-closed",
                        "solar-open2-rollout-closed",
                        "openpangu-longgen-closed",
                        "glm5-longctx-closed"), m["name"]
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])
    # The decode kernel's share of its roofline counts K and V a head over
    # `n_layers` and this stack keeps neither; it has no `attn_global`
    # scope either (its global layers run under `attn_latent`).
    for name in ("kernels.decode_attn_roofline_pct.batch",
                 "model.attn_dev_ms_step.global"):
        metric = next(m for m in bench["per_layer"] if m["name"] == name)
        assert REAL not in metric["workloads"]


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith(
        "references/kimi_linear_decoder.py")
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
    for fn in ("forward_logits", "chosen_experts", "loss",
               "routed_layer_output", "prefill_flops", "kda_state_bytes",
               "kda_flops_bytes", "latent_attn_min_bytes",
               "latent_attn_flops", "prefill_attn_flops_bytes",
               "moe_experts_min_bytes", "moe_experts_flops", "decode_bytes",
               "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's or of
    # another reference's, no cache, no kernel, no chunks; the recurrence
    # a position at a time.
    with open(spec.reference.__file__) as f:
        text = f.read()
    assert "import ray" not in text and "from ray_tpu" not in text \
        and "_decoder import" not in text and "import solar" not in text
    for word in ("pallas", "chunk_scan", "KVCache"):
        assert word not in text, word
    assert '"highest"' in text and "def _recurrence" in text


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"],
            tr["trace_seed"]) == (sizes["slots"], "ended_in_window", 192,
                                  5701)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 1024,
                                "max": 4000}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.3, "min": 512, "max": 2048}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        6143, 30.0, 0.0)
    others = [json.load(open(os.path.join(ROOT, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))
              if f != "docgen-closed.json"]
    assert tr["trace_seed"] not in [o.get("trace_seed") for o in others]
    # The issue's width, 32 x 6144, or its fallback of 24 with 24 callers.
    assert sizes["slots"] in (32, 24) and sizes["max_seq_len"] == 6144
    model = sizes["model"]
    assert model["param_dtype"] == "bfloat16" and model["max_seq_len"] == 6144
    # The issue's precision rule: bf16 activations, or float32 over bf16
    # latent rows; the state is float32 either way.
    assert (model["dtype"], model.get("cache_dtype")) in (
        ("bfloat16", None), ("float32", "bfloat16"))
    assert sizes["check"] == {"prompt_lens": [3800, 1500, 6],
                              "decode_steps": 16, "window_requests": 2}
    assert sizes["trace_seconds"] == 8.0 and len(sizes["slots_why"]) > 200
    from lib import modelcfg, traffic
    from ray_tpu.models import generate, periodic
    from ray_tpu.serve.llm import default_buckets
    import jax
    import jax.numpy as jnp

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 1024 <= min(lens) and max(lens) <= 4000
    assert all(r.prompt_len + r.output_len <= 6143 for r in trace)
    assert all(512 <= r.output_len <= 2048 for r in trace)
    buckets = default_buckets(6144)
    assert {next(b for b in buckets if b >= n) for n in lens} == {2048, 4096}
    # Resident: 8.59 GB of weights, twenty layers' states and tails, seven
    # layers' latent rows in whole lanes.
    cfg = modelcfg.transformer_config(real_spec.config, sizes)
    assert periodic.cache_layers(cfg) == {"window": 0, "global": 7,
                                          "linear": 20}
    cache = jax.eval_shape(lambda: generate.init_kv_cache(
        cfg, sizes["slots"], 6144))
    B = sizes["slots"]
    assert cache.k is None and cache.v is None and cache.kw is None
    assert (cache.c.shape, cache.c.dtype) == ((7, B, 6144, 640),
                                              jnp.bfloat16)
    assert (cache.s.shape, cache.s.dtype) == ((20, B, 32, 128, 128),
                                              jnp.float32)
    assert cache.tails.shape == (20, B, 3, 12288) \
        and cache.tails.dtype == cfg.dtype
    if B == 32:
        assert 1.34e9 < cache.s.size * 4 < 1.35e9
        assert 1.76e9 < cache.c.size * 2 < 1.77e9
        held = sum(a.size * a.dtype.itemsize for a in (
            cache.s, cache.tails, cache.c))
        assert 0.72 < (held + 2 * cfg.num_params()) / 16e9 < 0.75
    # A slot's state whatever its length, and a held token's rows.
    assert 20 * 32 * 128 * 128 * 4 == 41943040 and 7 * 576 * 2 == 8064
    state, row = periodic.cache_bytes(cfg)
    assert row == 8064 and state == 41943040 + 20 * 3 * 12288 * (
        2 if model["dtype"] == "bfloat16" else 4)


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"] == [
        "moe_experts", "vocab_size"]
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"] \
        and cfg["program_keys"] and cfg["use"]
    assert all(k in cfg for k in cfg["published"])
    assert "16 chips share each layer" in cfg["deployment"]
    assert any("(guess" in line for line in cfg["assumed"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        assert {k for k, v in row["config"].items() if cfg[k] != v} == {
            "vocab_size"}
    assert cfg["published_counts"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "first_k_dense_replace": 1, "num_nextn_predict_layers": 0}
    # Every published width, all 27 layers.
    assert (cfg["d_model"], cfg["n_layers"], cfg["n_dense_layers"],
            cfg["n_heads"], cfg["head_dim"], cfg["kv_lora_rank"],
            cfg["q_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["d_ff"],
            cfg["moe_d_ff"], cfg["moe_router_experts"], cfg["moe_top_k"],
            cfg["moe_shared_experts"], cfg["linear_n_heads"],
            cfg["linear_head_dim"], cfg["linear_conv_kernel"],
            cfg["route_scale"], cfg["vocab_size"], cfg["moe_experts"]
            ) == (2304, 27, 1, 32, 72, 512, None, 128, 64, 128, 9216, 1024,
                  256, 8, 1, 32, 128, 4, 2.446, 163840 // 8, 16)
    assert cfg["moe_first_expert"] % 16 == 0 and cfg["moe_first_expert"] > 0
    from lib import modelcfg
    from ray_tpu.models import periodic

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "kimi_linear"
    assert program.q_lora_rank == 0 and program.head_dim == 72
    assert periodic.layer_plan(program) == [
        ("dense_layers", (1,), False), ("periods", (6, 4), True),
        ("tail_layers", (1, 2), True)]
    assert periodic.step_kinds(program) == [
        ("linear",), ("linear", "linear", "global", "linear"),
        ("linear", "global")]
    # 20 x 39.52 + 7 x 29.11 + 26 x 120.91 + 63.70 + 94.37 M: 8.59 GB.
    assert program.num_params() == 4296139648 == sum(
        real_spec.reference.resident_params(cfg).values())


def test_the_references_counts_are_a_hand_count(real_spec):
    ref, cfg = real_spec.reference, real_spec.config
    table = ref.layer_table(cfg)
    assert [i + 1 for i, layer in enumerate(table) if layer.kind == "global"
            ] == cfg["linear_attn_config"]["full_attn_layers"]
    assert (ref.linear_layers(cfg), ref.latent_layers(cfg), len(table)) == (
        20, 7, 27)
    assert [layer.routed for layer in table] == [False] + [True] * 26
    # One owned slot's update in one KDA layer: 32 heads' 128 x 128 float32
    # state read and written; the engine's count spans the 20 layers.
    assert ref.kda_state_bytes(cfg, 1) == 2 * 4 * 32 * 128 * 128 == 4194304
    assert ref.kda_state_bytes(cfg, 32 * 20) == 32 * 2 * 41943040
    # A held token: 576 bf16 values in each of the 7 MLA layers.
    assert ref.latent_attn_min_bytes(cfg, 1) == 7 * 576 * 2 == 8064
    assert ref.latent_attn_flops(cfg, 1) == 7 * 32 * 2 * (2 * 512 + 64)
    # A tile's attention as its reader multiplies it: x 27 gives 7 layers'.
    one = 32 * 4096 * 4097 / 2 * 2.0 * (192 + 128)
    assert 27 * ref.prefill_attn_flops_bytes(cfg, 1, 4096)["flops"] \
        == pytest.approx(7 * one)
    # A decode step: the weights outside the experts, the experts hit, the
    # owned slots' states and tails twice, the held tokens' rows.
    parts = ref.resident_params(cfg)
    assert parts["kda"] == 20 * (
        4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
        + 2 * 2304 + 4 * 12288 + 32 + 2 * 4096 + 128)
    assert parts["mla"] == 7 * (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
        + 2 * 2304 + 512)
    assert parts["experts"] == 26 * 16 * 3 * 2304 * 1024
    assert parts["routers_shared"] == 26 * (
        2304 * 256 + 256 + 3 * 2304 * 1024)
    once = parts["kda"] + parts["mla"] + parts["routers_shared"] \
        + parts["dense_ffn"] + 2304 * 20480 + 2304
    assert ref.decode_bytes(cfg, 0, 0, 0) == 2 * once
    assert 2.60e9 < 2 * once < 2.62e9
    step = ref.decode_bytes(cfg, 32 * 2700, 32, 0.64 * 26 * 16)
    assert step == pytest.approx(
        2 * once + 32 * 2304 * 2 + 0.64 * 26 * 16 * 3 * 2304 * 1024 * 2
        + 32 * 2 * (41943040 + 20 * 3 * 12288 * 2) + 32 * 2700 * 8064)
    # 9.85 GB a step: 12 ms at the peak.
    assert 9.8e9 < step < 9.9e9 and 11.9e-3 < step / 819e9 < 12.1e-3
    # Float32 tails are counted apart.
    assert ref.decode_bytes(cfg, 0, 1, 0, tail_element=4) \
        - ref.decode_bytes(cfg, 0, 1, 0) == 2 * 20 * 3 * 12288 * 2


def test_a_traced_rehearsal_is_correct_and_counts_both_kinds_of_cache(
        kimi_root, capsys):
    line = _run(kimi_root, trace=1)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4
    got = line["rehearsal"]
    # What the host counts comes through the spans on any backend.
    assert 0 < got["engine.linear_state_live_pct.batch"]["value"] <= 100
    assert 0 < got["engine.cache_state_share_pct.batch"]["value"] < 100
    assert 10 < got["engine.moe_pairs_held_pct.batch"]["value"] < 50
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        kimi_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.dispatch_block"]
    # Five KDA layers and two MLA layers in this preset, 4 slots: a held
    # token is counted once, its bytes over the two layers.
    assert sums["linear_slot_steps"] == 5 * 4 * sums["k"]
    assert sums["cache_row_bytes_held"] == sums["cache_rows_held"] \
        * 2 * (32 + 8) * 4
    assert sums["cache_state_bytes_live"] == \
        sums["linear_slot_steps_live"] * (2 * 16 * 16 * 4 + 3 * 96 * 4)
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert tiles["linear_tokens"] == 5 * tiles["tokens"]


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """The whole step's share of the peak bandwidth from the reference's
    count, the share of a step's owned bytes that are state, a tile's
    latent attention by its scope; the accepted linear and latent readers
    on the same profile; nothing from a trace without the scopes or the
    counters."""
    ms = 1e6
    tile, block = "jit_prefill_sample_batch(7)", "jit_decode_k8(9)"
    ops = [("%a = f32[] fusion(1)", 0.0, 200 * ms),        # tile: the scan
           ("%b = f32[] custom-call(2)", 200 * ms, 40 * ms),  # tile: flash
           ("%c = f32[] fusion(3)", 240 * ms, 60 * ms),    # tile: other
           ("%d = f32[] fusion(4)", 400 * ms, 64 * ms),    # decode: linear
           ("%e = f32[] custom-call(5)", 464 * ms, 16 * ms),  # decode: latent
           ("%f = f32[] fusion(6)", 480 * ms, 8 * ms),     # decode: mla_proj
           ("%g = f32[] fusion(7)", 488 * ms, 72 * ms)]    # decode: experts
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/attn_linear/"
                   "kda_scan/while/body/dot_general",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/attn_latent/"
                   "pallas_call",
        ops[2][0]: "jit(prefill_sample_batch)/dot_general",
        ops[3][0]: "jit(decode_k8)/while/body/while/body/attn_linear/"
                   "pallas_call",
        ops[4][0]: "jit(decode_k8)/while/body/while/body/attn_latent/"
                   "pallas_call",
        ops[5][0]: "jit(decode_k8)/while/body/while/body/mla_proj/"
                   "dot_general",
        ops[6][0]: "jit(decode_k8)/while/body/moe_experts/while/body/"
                   "jit(gmm)/x"}
    raw = {"spans": [], "window": (0.0, 600 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(tile, 0.0, 300 * ms),
                                       (block, 400 * ms, 160 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    for lib in (prefilltime, scopetime):
        monkeypatch.setattr(lib.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    ps.kernel_s = {"decode_attn": 0.016}    # the event's `kernel_metadata`
    state, row = 32 * (41943040 + 20 * 3 * 12288 * 2), 8064
    ps.spans = [
        progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", {
            "side": "slot", "bucket": 4096, "rows": 1, "tile_rows": 1,
            "tokens": 3000, "req_ids": "41", "linear_tokens": 20 * 3000}),
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "active": 32, "cache_rows": 8 * 32 * 6144,
            "cache_rows_held": 8 * 32 * 2700,
            "linear_slot_steps": 8 * 32 * 20,
            "linear_slot_steps_live": 8 * 32 * 20,
            "cache_state_bytes_live": 8 * state,
            "cache_row_bytes_held": 8 * 32 * 2700 * row}),
        progspans.Span("engine.process_block", 3.0, 1.0, "t", {
            "k": 8, "moe_expert_steps": 8 * 26 * 16,
            "moe_experts_hit": 8 * 266, "moe_rows": 8 * 400})]
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

    ref = spec.reference
    acts = spec.sizes["model"]["dtype"]
    assert ps.decode_steps() == 8
    # 20 ms a step against the step's least bytes at 2,700 held tokens a
    # slot, 32 owned slots and 266 experts hit.
    want = 100 * ref.decode_bytes(
        spec.config, 32 * 2700, 32, 266,
        tail_element=2 if acts == "bfloat16" else 4) / 819e9 / 0.020
    assert read("model.hybrid_decode_hbm_pct.batch") == pytest.approx(want)
    assert 55 < want < 65
    # 1.37 GB of states and tails against 0.70 GB of rows.
    assert read("engine.cache_state_share_pct.batch") == pytest.approx(
        100 * state / (state + 32 * 2700 * row))
    assert 60 < read("engine.cache_state_share_pct.batch") < 70
    assert read("model.attn_dev_ms_req.latent") == pytest.approx(40.0)
    assert read("model.attn_dev_ms_req.linear") == pytest.approx(200.0)
    assert read("model.attn_dev_ms_step.linear") == pytest.approx(8.0)
    assert read("model.attn_dev_ms_step.latent") == pytest.approx(2.0)
    assert read("model.mla_proj_dev_ms_step.batch") == pytest.approx(1.0)
    assert read("engine.linear_state_live_pct.batch") == 100.0
    assert read("kernels.linear_attn_roofline_pct.batch") == pytest.approx(
        100 * ref.kda_state_bytes(spec.config, 32 * 20) / 819e9 / 0.008)
    # The latent kernel's share counts 7 layers' rows, the tile's the 7
    # layers' pairs: both under 100 here.
    assert read("kernels.latent_attn_roofline_pct.batch") == pytest.approx(
        100 * max(32 * 2700 * 8064 / 819e9,
                  ref.latent_attn_flops(spec.config, 32 * 2700) / 197e12)
        / 0.002)
    one = ref.prefill_attn_flops_bytes(spec.config, 1, 4096)
    assert read("kernels.latent_prefill_attn_roofline_pct.batch") == \
        pytest.approx(100 * 27 * max(one["flops"] / 197e12,
                                     one["bytes"] / 819e9) / 0.040)
    for name in ("kernels.linear_attn_roofline_pct.batch",
                 "kernels.latent_attn_roofline_pct.batch",
                 "kernels.latent_prefill_attn_roofline_pct.batch",
                 "kernels.linear_prefill_attn_roofline_pct.batch",
                 "kernels.moe_experts_roofline_pct.batch",
                 "model.prefill_mfu_pct.batch"):
        assert 0 < read(name) < 100, name
    # Spans without the counters this PR adds (the parent's): the two
    # readers of them are silent, and nothing raises.
    ps.spans = [progspans.Span(s.name, s.start, s.dur, "t", {
        k: v for k, v in s.stats.items() if k not in (
            "cache_state_bytes_live", "cache_row_bytes_held",
            "linear_slot_steps_live")}) for s in ps.spans]
    assert read("engine.cache_state_share_pct.batch") is None
    assert read("model.hybrid_decode_hbm_pct.batch") is None
    # A trace of a program without the scope: the tile's reader too.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    m.pop("prefill_scope_s")
    assert read("model.attn_dev_ms_req.latent") is None
    # A reference whose `decode_bytes` takes no experts (a looped
    # stack's): not this reader's.
    ctx.spec = Spec(ROOT, "ouro-2b6-mathqa-closed")
    assert read("model.hybrid_decode_hbm_pct.batch") is None
