"""The sparse-attention cell (`glm5-longctx-closed`) at a tiny size on the
CPU: its reference, its driver, its readers and its check script, through
`run.py`, with the real cell's metrics; the new readers on a small made-up
profile; and the real configuration's keys against the catalog row.

The tiny copy of the benchmark (`conftest.make_tiny_root`) maps the cell
to `tiny-glm-closed` (tests/conftest.py names the stand-in); the fixture
below adds that cell's files and its tiny `glm_moe_dsa` configuration, and
has tiles longer than 16 rows walk 16 at a time."""

import dataclasses
import io
import json
import os

import pytest

import run
from conftest import ROOT, make_tiny_root
from lib import prefilltime, progspans, scopetime
from lib.spec import Spec

REAL = "glm5-longctx-closed"
CONFIG = "glm-5-l5-ep16"
CELL = "tiny-glm-closed"
# (name, unit, better, source, layer): what the cell appended; all move
# `serve_out_tok_s` and list the cell alone.
NEW = [
    ("engine.sparse_rows_read_pct.batch", "%", "lower", "program_counter",
     "Engine"),
    ("model.attn_dev_ms_step.sparse", "ms", "lower", "device_trace", "Model"),
    ("model.indexer_dev_ms_step.batch", "ms", "lower", "device_trace",
     "Model"),
    ("model.attn_dev_ms_req.sparse", "ms", "lower", "device_trace", "Model"),
    ("model.indexer_dev_ms_req.prefill", "ms", "lower", "device_trace",
     "Model"),
    ("kernels.sparse_attn_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels"),
    ("kernels.indexer_roofline_pct.batch", "%", "higher", "device_trace",
     "Kernels"),
    ("kernels.sparse_prefill_attn_roofline_pct.batch", "%", "higher",
     "device_trace", "Kernels")]
NEW_NAMES = [m[0] for m in NEW]
# Accepted metrics whose `workloads` gain the cell, behind the last cell
# each listed: readers that read true for it unchanged.
LISTED_BEHIND_SDAR = [
    "serve_out_tok_s", "engine.occupancy_pct.batch",
    "engine.delivery_tok_s.batch", "model.decode_dev_ms_step.batch",
    "model.decode_dev_ms_step_exact.batch", "device.idle_pct.batch",
    "device.peak_mem_pct.batch", "device.compiles_in_window.batch",
    "engine.host_self_ms_tick.batch", "engine.prefill_useful_pct.batch",
    "engine.decode_useful_pct.batch", "engine.admit_wait_steps_p90.batch",
    "engine.idle_named_pct.batch", "model.moe_dev_ms_step.batch",
    "engine.moe_experts_hit_pct.batch",
    "engine.moe_load_max_over_mean.batch",
    "kernels.moe_experts_roofline_pct.batch", "engine.cache_held_pct.batch",
    "model.prefill_mfu_pct.batch"]
LISTED_BEHIND_PANGU = ["engine.moe_pairs_held_pct.batch",
                       "model.mla_proj_dev_ms_step.batch"]
LISTED_IN = LISTED_BEHIND_SDAR + LISTED_BEHIND_PANGU
ENTRIES = {
    "config": {
        "name": CONFIG,
        "source": "https://huggingface.co/zai-org/GLM-5/blob/main/"
                  "config.json",
        "file": f"benchmarks/configs/{CONFIG}.json",
        "reduced": ["n_layers", "n_dense_layers", "moe_experts",
                    "vocab_size"],
        "why": "glm_moe_dsa 744B at published widths: latent attention over "
               "the 2,048 rows a 32 x 128 indexer chooses, 1 dense + 4 "
               "routed layers, 16 of 256 sigmoid-routed experts held (top "
               "8) + 1 shared: 1 chip of 16"},
    "workload": {
        "name": REAL, "config": CONFIG, "traffic": "longctx-closed",
        "chips": 1,
        "why": "closed loop, 16 callers on 16 slots x 32768, prompts "
               "8192-28000 walked in chunks of 2048, answers ~1024: a step "
               "scores 9k-30k indexer keys a slot and attends the 2,048 "
               "chosen; 16 of 256 experts held"}}


def _tiny_glm_config():
    from ray_tpu.models import configs

    cfg = dataclasses.asdict(configs.tiny_glm_test())
    for key in ("dtype", "param_dtype", "max_seq_len", "remat"):
        del cfg[key]
    return dict(cfg, reference="glm_dsa_decoder")


@pytest.fixture(scope="module")
def glm_root(tmp_path_factory):
    """The tiny benchmark with the real cell's entries pointed at a tiny
    `glm_moe_dsa` configuration: same driver, same reference, same
    metrics."""
    from ray_tpu.models import latent

    root = make_tiny_root(str(tmp_path_factory.mktemp("glm")))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny-glm.json"), "w") as f:
        json.dump(_tiny_glm_config(), f)
    with open(os.path.join(bdir, "cells", "tiny-closed.json")) as f:
        sizes = json.load(f)
    # Either side of index_topk = 8, and past a chunk of 16.
    sizes["check"] = {"prompt_lens": [40, 12, 6], "decode_steps": 6,
                      "window_requests": 2}
    with open(os.path.join(bdir, "cells", CELL + ".json"), "w") as f:
        json.dump(sizes, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for kind in ("end_to_end", "per_layer")
              for m in bench[kind] if CELL in m.get("workloads", ())}
    assert listed == set(LISTED_IN) | set(NEW_NAMES)
    bench["configs"].append({
        "name": "tiny-glm", "source": "test only", "reduced": [],
        "file": "benchmarks/configs/tiny-glm.json", "why": "test only"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-glm", "traffic": "tiny-closed",
        "chips": 1, "why": "test only"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    patch = pytest.MonkeyPatch()
    patch.setattr(latent, "PREFILL_CHUNK", 16)
    yield root
    patch.undo()


@pytest.fixture(scope="module")
def real_spec():
    return Spec(ROOT, REAL)


def _run(root, trace, seed=2**31 + 4101, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_entries_are_appended_to_benchmark_json(bench):
    # Behind everything the benchmark had (sdar's were its last cell,
    # configuration and metrics); a later PR's entries go behind these, so
    # nothing is pinned to the end.
    names = [c["name"] for c in bench["configs"]]
    cells = [w["name"] for w in bench["workloads"]]
    assert names.index(CONFIG) == names.index("sdar-30b-a3b-l7") + 1
    assert cells.index(REAL) == cells.index("sdar-blockgen-closed") + 1
    assert bench["configs"][names.index(CONFIG)] == ENTRIES["config"]
    assert bench["workloads"][cells.index(REAL)] == ENTRIES["workload"]
    assert all(len(e["why"]) <= 200 for e in ENTRIES.values())
    assert len(cells) >= 8 and sum(
        w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m for m in bench["per_layer"] if m["name"] in NEW_NAMES]
    assert [(m["name"], m["unit"], m["better"], m["source"], m["layer"])
            for m in mine] == NEW
    assert all(m["workloads"] == [REAL] and m["moves"] == "serve_out_tok_s"
               for m in mine)
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(NEW_NAMES[0]) == order.index(
        "model.blockgen_mfu_pct.batch") + 1
    assert [order.index(n) for n in NEW_NAMES] == list(range(
        order.index(NEW_NAMES[0]), order.index(NEW_NAMES[0]) + len(NEW)))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if m["name"] in LISTED_IN:
                before = "openpangu-longgen-closed" \
                    if m["name"] in LISTED_BEHIND_PANGU \
                    else "sdar-blockgen-closed"
                assert m["workloads"].index(REAL) == m["workloads"].index(
                    before) + 1, m["name"]
            elif m["name"] not in NEW_NAMES:
                assert REAL not in m.get("workloads", [])


def test_the_real_cell_names_its_files_and_every_reader(real_spec):
    spec = real_spec
    assert spec.reference.__file__.endswith("references/glm_dsa_decoder.py")
    assert spec.traffic["driver"] == "serve_closed"
    assert {m["name"] for m in spec.metrics("end_to_end")} == {
        "serve_out_tok_s", "setup_s"}
    assert {m["name"] for m in spec.metrics("per_layer")} == (
        set(LISTED_IN) - {"serve_out_tok_s"}) | set(NEW_NAMES)
    for m in spec.metrics("per_layer"):
        reader = spec.load_module("layer_metrics", m["name"])
        assert reader is not None and callable(reader.read), m["name"]
    # Two reach accepted readers by the loader's longest-prefix rule (the
    # scope comes from the suffix); six are files of their own.
    for name, stem in (
            ("model.attn_dev_ms_step.sparse", "model.attn_dev_ms_step"),
            ("model.attn_dev_ms_req.sparse", "model.attn_dev_ms_req"),
            ("engine.sparse_rows_read_pct.batch",
             "engine.sparse_rows_read_pct"),
            ("model.indexer_dev_ms_step.batch", "model.indexer_dev_ms_step"),
            ("model.indexer_dev_ms_req.prefill", "model.indexer_dev_ms_req"),
            ("kernels.sparse_attn_roofline_pct.batch",
             "kernels.sparse_attn_roofline_pct"),
            ("kernels.indexer_roofline_pct.batch",
             "kernels.indexer_roofline_pct"),
            ("kernels.sparse_prefill_attn_roofline_pct.batch",
             "kernels.sparse_prefill_attn_roofline_pct")):
        assert spec.load_module("layer_metrics", name).__file__.endswith(
            stem + ".py")
    for fn in ("forward_logits", "chosen_experts", "chosen_rows",
               "prefill_flops", "moe_experts_min_bytes", "moe_experts_flops",
               "sparse_attn_min_bytes", "sparse_attn_flops",
               "indexer_min_bytes", "indexer_flops",
               "sparse_prefill_attn_flops", "loss", "train_flops_per_token"):
        assert callable(getattr(spec.reference, fn)), fn
    # The reference stands on its own: nothing of the program's, no
    # cache, no kernel, no gather, and an exact choice.
    with open(spec.reference.__file__) as f:
        text = f.read()
    assert "ray_tpu" not in text.replace("`ray_tpu/models`", "")
    for word in ("pallas", "approx_max_k", "take_along_axis(c",
                 "jnp.take("):
        assert word not in text, word
    assert "lax.top_k" in text and '"highest"' in text


def test_the_traffic_and_the_sizes_are_the_issues(real_spec):
    tr, sizes = real_spec.traffic, real_spec.sizes
    assert (tr["clients"], tr["measure"], tr["n_requests"]) == (
        16, "ended_in_window", 96)
    assert tr["prompt_len"] == {"dist": "loguniform", "min": 8192,
                                "max": 28000}
    assert tr["output_len"] == {"dist": "lognormal", "median": 1024,
                                "sigma": 0.3, "min": 512, "max": 2048}
    assert (tr["max_total_len"], tr["lead_in_s"], tr["drain_limit_s"]) == (
        32767, 40.0, 0.0)
    others = [json.load(open(os.path.join(ROOT, "benchmarks", "traffic", f)))
              for f in os.listdir(os.path.join(ROOT, "benchmarks", "traffic"))
              if f != "longctx-closed.json"]
    assert tr["trace_seed"] not in [o.get("trace_seed") for o in others]
    assert (sizes["slots"], sizes["max_seq_len"]) == (16, 32768)
    assert sizes["model"] == {"dtype": "bfloat16", "param_dtype": "bfloat16",
                              "max_seq_len": 32768, "index_dtype": "float32"}
    assert sizes["check"] == {"prompt_lens": [20000, 9000, 1500],
                              "decode_steps": 16, "window_requests": 2}
    assert sizes["trace_seconds"] == 8.0 and sizes["slots_why"]
    from lib import modelcfg, traffic
    from ray_tpu.models import latent
    from ray_tpu.serve.llm import LLMEngine, default_buckets

    trace = traffic.make_trace(tr)
    lens = [r.prompt_len for r in trace]
    assert 8192 <= min(lens) and max(lens) <= 28000
    assert all(r.prompt_len + r.output_len <= 32767 for r in trace)
    assert all(512 <= r.output_len <= 2048 for r in trace)
    buckets = default_buckets(32768)
    assert buckets[-3:] == [8192, 16384, 32768] and buckets[0] == 16
    # Every prompt is past a chunk and fills 5 to 14 of them; a tile is
    # one row; the check's 1,500 tokens choose every row (one chunk, the
    # tile over itself), 9,000 and 20,000 are walked.
    cfg = modelcfg.transformer_config(real_spec.config, sizes)
    assert latent.PREFILL_CHUNK == 2048 <= 8192
    chunks = [latent.prefill_chunks(cfg, next(
        b for b in buckets if b >= n), n)[0] for n in lens]
    assert (min(chunks), max(chunks)) == (5, 14)
    assert all(LLMEngine._tile_rows(b) == 1 for b in buckets[-3:])
    assert latent.chunk_rows(cfg, 2048) == 2048 == cfg.index_topk
    assert latent.prefill_chunks(cfg, 16384, 9000) == (5, 8)
    assert latent.prefill_chunks(cfg, 32768, 20000) == (10, 16)
    # Resident: 7.82 GB of weights, 3.36 GB of latent rows, 0.67 GB of
    # indexer keys: 74% of the chip before a chunk's temporaries.
    rows = cfg.n_layers * 16 * 32768 * 2
    held = rows * (latent.cache_lanes(cfg) + cfg.index_head_dim)
    assert 4.02e9 < held < 4.03e9
    assert 0.73 < (held + 2 * cfg.num_params()) / 16e9 < 0.75


def test_the_configuration_is_the_catalog_row(real_spec):
    cfg = real_spec.config
    assert cfg["source"] == ENTRIES["config"]["source"]
    assert cfg["reduced"] == ENTRIES["config"]["reduced"]
    assert cfg["assumed"] and cfg["deployment"] and cfg["left_out"] \
        and cfg["use"] and cfg["program_keys"]
    assert "16 chips share each layer" in cfg["deployment"]
    assert any("multi-token-prediction" in s for s in cfg["left_out"])
    assert all(k in cfg for k in cfg["published"])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "GLM-5")
        assert cfg["source"] == row["source_url"]
        assert sorted(row["config"]) == cfg["published"]
        differ = {k for k, v in row["config"].items() if cfg[k] != v}
        assert differ == {"vocab_size"}             # listed in `reduced`
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["q_lora_rank"], cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["index_n_heads"], cfg["index_head_dim"], cfg["index_topk"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["n_routed_experts"], cfg["num_experts_per_tok"],
            cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["routed_scaling_factor"], cfg["topk_method"]) == (
        6144, 64, 2048, 512, 192, 64, 256, 32, 128, 2048, 12288, 2048, 256,
        8, 78, 3, 2.5, "noaux_tc")
    assert cfg["published_counts"] == {
        "num_hidden_layers": 78, "first_k_dense_replace": 3,
        "n_routed_experts": 256, "vocab_size": 154880,
        "num_nextn_predict_layers": 1}
    # The program's keys: the published widths under its own names, the
    # router's published width beside the 16 experts held.
    assert (cfg["d_model"], cfg["n_heads"], cfg["d_ff"], cfg["moe_d_ff"],
            cfg["moe_router_experts"], cfg["moe_experts"], cfg["moe_top_k"],
            cfg["moe_shared_experts"], cfg["route_scale"], cfg["n_layers"],
            cfg["n_dense_layers"], cfg["vocab_size"], cfg["rope_theta"]) == (
        6144, 64, 12288, 2048, 256, 16, 8, 1, 2.5, 5, 1, 19360, 1000000)
    assert cfg["rope_theta"] == cfg["rope_parameters"]["rope_theta"]
    assert 0 <= cfg["moe_first_expert"] <= 256 - 16 \
        and cfg["moe_first_expert"] % 16 == 0
    assert cfg["vocab_size"] * 8 == 154880
    from lib import modelcfg

    program = modelcfg.transformer_config(cfg, real_spec.sizes)
    assert program.arch == cfg["model_type"] == "glm_moe_dsa"
    assert (program.index_n_heads, program.index_head_dim,
            program.index_topk) == (32, 128, 2048)
    assert 3909e6 < program.num_params() < 3911e6
    ref = real_spec.reference
    assert [r for _, _, r in ref.layer_table(cfg)] == [False] + [True] * 4
    # A 16k prompt a token: 2.7 GFLOP of products, 0.63 of the chosen pairs
    # (1,920 a token x 65,536 x 5 layers; ISSUE 41's 1.4 counted them
    # twice), 0.34 of the indexer's scores; a decode step over
    # 16 slots x 19,000 held rows scores 0.39 GB of keys and reads 0.19 GB
    # of chosen rows.
    n = 16384
    pairs = ref.sparse_prefill_attn_flops(cfg, n) / n
    scores = ref.indexer_prefill_flops(cfg, n) / n
    products = (ref.prefill_flops(cfg, n) - pairs * n - scores * n) / n
    assert 2.6e9 < products < 2.8e9 and 0.62e9 < pairs < 0.64e9 \
        and 0.33e9 < scores < 0.34e9
    assert ref.chosen_pairs(cfg, 100) == 5050 \
        and ref.chosen_pairs(cfg, 4096) == 2048 * 2049 / 2 + 2048 * 2048
    assert 0.38e9 < ref.indexer_min_bytes(cfg, 16 * 19000) < 0.40e9
    assert 0.18e9 < ref.sparse_attn_min_bytes(cfg, 16 * 2048) < 0.20e9
    # A chosen row is 1,152 B and 64 heads x 2,176 operations: bound by
    # the operations by a little; an indexer key is 256 B and 8,192
    # operations: bound by its bytes.
    assert ref.sparse_attn_flops(cfg, 1) == 5 * 64 * 2176
    assert ref.sparse_attn_min_bytes(cfg, 1) == 5 * 1152
    assert ref.indexer_flops(cfg, 1) == 5 * 32 * 128 * 2
    assert ref.indexer_min_bytes(cfg, 1) == 5 * 256


def test_the_tiny_cell_is_correct_against_its_own_reference(glm_root,
                                                            capsys):
    line = _run(glm_root, trace=0)
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["rehearsal"]) == {"serve_out_tok_s", "setup_s"}
    logged = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
              if ln.startswith('{"phase"')]
    check = next(d for d in logged if d["phase"] == "serve_setup")["check"]
    assert check["positions"] == 3 * 7 and check["logit_rel_rms_err"] < 1e-4


def test_a_traced_rehearsal_reads_the_rows_scored_and_read(glm_root):
    line = _run(glm_root, trace=1)
    got = line["rehearsal"]
    # What the host counts comes through the spans on any backend: 8 rows
    # read a slot a step of the 10-80 it holds.
    assert 5 < got["engine.sparse_rows_read_pct.batch"]["value"] < 80
    assert 10 < got["engine.moe_pairs_held_pct.batch"]["value"] < 50
    assert 0 < got["engine.cache_held_pct.batch"]["value"] <= 100
    # No device on a CPU: the device-trace readers return nothing.
    assert not [n for n in got if n.startswith(("model.", "kernels."))]
    spans = json.load(open(os.path.join(
        glm_root, ".bench_out", CELL, "program_spans.json")))
    sums = spans["span_attribute_sums"]["engine.dispatch_block"]
    assert sums["cache_rows_held"] > sums["sparse_rows_read"]
    assert 0 < sums["sparse_rows_read"] <= 8 * 4 * sums["k"]
    tiles = spans["span_attribute_sums"]["engine.prefill_tile"]
    assert 0 < tiles["chunks"] <= tiles["chunks_of"]


def test_the_check_script_reads_both_dtypes_control_and_flips(glm_root):
    from checks import sparse_logits

    def read(*extra):
        out = io.StringIO()
        assert sparse_logits.main(
            ["--workload", CELL, "--seeds", "5,2147483653", "--control",
             "1", "--control-len", "64", *extra], root=glm_root,
            rehearse=True, out=out) == 0
        return json.loads(out.getvalue().splitlines()[-1])

    last = read()
    assert last["seeds"] == 2 and last["limit"] == 0.08
    assert last["dtype"] == "float32" and last["over_limit"] == 0
    assert last["sound_largest_rel_rms_err"] < 1e-4
    # float32 program against float32 reference: the same experts and the
    # same rows, every query of every layer.
    assert last["routing_pairs"] == 2 * 2 * 64 and last["routing_flips"] == 0
    assert last["row_choice_pairs"] == 2 * 3 * 64
    assert last["row_choice_flips"] == 0 == last["rows_differing_share"]
    assert last["control_smallest_rel_rms_err"] > 0.03 \
        > 100 * last["sound_largest_rel_rms_err"]
    rounded = read("--dtype", "bfloat16")
    assert rounded["dtype"] == "bfloat16"
    assert rounded["sound_largest_rel_rms_err"] \
        > 10 * last["sound_largest_rel_rms_err"]
    # The indexer is float32 under bf16 activations too: what moves a row
    # across the eighth place is what the layers before it rounded.
    assert 0 <= rounded["row_choice_flip_share"] < 0.5


class _Ctx:
    trace, rehearse, out_dir = True, False, "/nonexistent"


def test_the_new_readers_on_a_made_up_profile(monkeypatch, real_spec):
    """Device time by scope inside the decode and the prefill programs;
    the three roofline shares and the share of rows read from the
    counters and the reference's counts; nothing from a trace without the
    scopes or the counters."""
    ms = 1e6
    tile, block = "jit_prefill_sample_batch(7)", "jit_decode_k8(9)"
    ops = [("%a = f32[] fusion(1)", 0.0, 100 * ms),          # tile: index
           ("%b = f32[] custom-call(2)", 100 * ms, 400 * ms),  # tile: sparse
           ("%c = f32[] fusion(3)", 500 * ms, 100 * ms),     # tile: other
           ("%d = f32[] fusion(4)", 700 * ms, 16 * ms),      # decode: index
           ("%e = f32[] custom-call(5)", 716 * ms, 8 * ms),  # decode: sparse
           ("%f = f32[] fusion(6)", 724 * ms, 56 * ms)]      # decode: rest
    scopes = {
        ops[0][0]: "jit(prefill_sample_batch)/while/body/while/body/"
                   "attn_index/pallas_call",
        ops[1][0]: "jit(prefill_sample_batch)/while/body/while/body/while/"
                   "body/attn_sparse/pallas_call",
        ops[2][0]: "jit(prefill_sample_batch)/dot_general",
        ops[3][0]: "jit(decode_k8)/while/body/attn_index/top_k",
        ops[4][0]: "jit(decode_k8)/while/body/attn_sparse/pallas_call",
        ops[5][0]: "jit(decode_k8)/while/body/moe_experts/while/body/"
                   "jit(gmm)/x"}
    raw = {"spans": [], "window": (0.0, 800 * ms), "scopes": scopes,
           "devices": {"/device:TPU:0": {
               "ops": ops, "modules": [(tile, 0.0, 600 * ms),
                                       (block, 700 * ms, 80 * ms)]}}}
    monkeypatch.setattr(progspans, "read_profile", lambda path: raw)
    for lib in (prefilltime, scopetime):
        monkeypatch.setattr(lib.xplane, "find_xplane", lambda d: "x.pb")
    ps = progspans.reduce_profile(raw)
    held = 8 * 16 * 19000                   # rows the 8 steps' slots hold
    read_rows = 8 * 16 * 2048               # rows they chose
    ps.spans = [
        progspans.Span("engine.prefill_tile", 0.0, 1.0, "t", {
            "side": "slot", "bucket": 16384, "rows": 1, "tile_rows": 1,
            "tokens": 12000, "req_ids": "41", "chunks": 6, "chunks_of": 8}),
        progspans.Span("engine.dispatch_block", 2.0, 1.0, "t", {
            "k": 8, "cache_rows": 8 * 16 * 32768, "cache_rows_held": held,
            "sparse_rows_read": read_rows})]
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
    assert read("engine.sparse_rows_read_pct.batch") == pytest.approx(
        100 * 2048 / 19000)
    assert read("model.indexer_dev_ms_step.batch") == pytest.approx(2.0)
    assert read("model.attn_dev_ms_step.sparse") == pytest.approx(1.0)
    assert read("model.indexer_dev_ms_req.prefill") == pytest.approx(100.0)
    assert read("model.attn_dev_ms_req.sparse") == pytest.approx(400.0)
    ref = spec.reference
    least = max(ref.sparse_attn_min_bytes(spec.config, 16 * 2048) / 819e9,
                ref.sparse_attn_flops(spec.config, 16 * 2048) / 197e12)
    assert read("kernels.sparse_attn_roofline_pct.batch") == pytest.approx(
        100 * least / 0.001)
    least = max(ref.indexer_min_bytes(spec.config, 16 * 19000) / 819e9,
                ref.indexer_flops(spec.config, 16 * 19000) / 197e12)
    assert read("kernels.indexer_roofline_pct.batch") == pytest.approx(
        100 * least / 0.002)
    assert read("kernels.sparse_prefill_attn_roofline_pct.batch") == \
        pytest.approx(100 * ref.sparse_prefill_attn_flops(
            spec.config, 12000) / 0.4 / 197e12)
    assert read("model.prefill_mfu_pct.batch") == pytest.approx(
        100 * ref.prefill_flops(spec.config, 12000) / 0.6 / 197e12)
    for name in ("kernels.sparse_attn_roofline_pct.batch",
                 "kernels.indexer_roofline_pct.batch",
                 "kernels.sparse_prefill_attn_roofline_pct.batch",
                 "model.prefill_mfu_pct.batch"):
        assert 0 < read(name) < 100, name
    # A trace of a program without the scopes (the parent's, another
    # architecture's): every one of them is silent, and nothing raises.
    raw["scopes"] = {k: "jit(x)/dot_general" for k in scopes}
    m.pop("prefill_scope_s")
    m.pop("decode_scope_s")
    for name in NEW_NAMES[1:]:
        assert read(name) is None, name
    # And one whose spans carry no counters: the counter reader too.
    ps.spans = ps.spans[:1]
    assert read("engine.sparse_rows_read_pct.batch") is None
