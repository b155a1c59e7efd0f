"""BENCHMARK.json against its own rules, and every cell's files."""

import json
import os
import re

import pytest

from conftest import ROOT
from lib.spec import Spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"hidden|intermediate|latent|state|proj|_dim$|_rank$"
                    r"|head_dim|d_model|d_ff|expansion|per_tok")


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) < 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert any(bench["command"][1].startswith(p + "/")
               for p in bench["paths"])


def test_run_seconds_fits_a_full_check_of_24_cells(bench):
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units(bench):
    names = []
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[kind]:
            assert NAME.match(e["name"]), e["name"]
            names.append((kind in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)


def test_end_to_end_metrics(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert set(e2e) == {"ttft_p90_ms", "tpot_p90_ms", "serve_out_tok_s",
                        "train_tok_s_chip", "setup_s"}
    assert "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    # No serving metric is a mean or percentile of whole-request latency.
    assert not [n for n in e2e if "latency" in n]


def _cells_of(metric, bench):
    return set(metric.get("workloads",
                          [w["name"] for w in bench["workloads"]]))


def test_every_moves_target_is_reported_where_the_metric_is(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and m["moves"] != "setup_s"
        assert _cells_of(m, bench) <= _cells_of(e2e[m["moves"]], bench), m
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200


def test_every_cell_reports_enough(bench):
    known = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert _cells_of(m, bench) <= known
    for w in bench["workloads"]:
        spec = Spec(ROOT, w["name"])
        e2e = [m["name"] for m in spec.metrics("end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert spec.metrics("per_layer")


def test_at_most_one_cell_in_four_takes_four_chips(bench):
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configurations(bench):
    used = {w["config"] for w in bench["workloads"]}
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for c in bench["configs"]:
        assert c["name"] in used
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://")
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"] and "assumed" in cfg
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key


# The published widths, from each model's own config.json: no cell may
# run narrower.
PUBLISHED = {
    "mistral-7b-v0.3-l16": {"d_model": 4096, "n_heads": 32, "n_kv_heads": 8,
                            "d_ff": 14336, "vocab_size": 32768,
                            "rope_theta": 1e6, "norm_eps": 1e-5,
                            "tie_embeddings": False, "n_layers": 16},
    "internlm2-1.8b": {"d_model": 2048, "n_heads": 16, "n_kv_heads": 8,
                       "d_ff": 8192, "vocab_size": 92544, "rope_theta": 1e6,
                       "norm_eps": 1e-5, "tie_embeddings": False,
                       "n_layers": 24},
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_widths_are_unchanged(bench, name):
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    for key, want in PUBLISHED[name].items():
        assert cfg[key] == want, key
    assert cfg["d_model"] // cfg["n_heads"] == 128
    pub = cfg["published"]
    assert pub["hidden_size"] == cfg["d_model"]
    assert pub["intermediate_size"] == cfg["d_ff"]
    changed = {"n_layers"} if pub["num_hidden_layers"] != cfg["n_layers"] \
        else set()
    assert changed == set(cfg["reduced"])


def test_every_cell_has_its_files_driver_and_readers(bench):
    for w in bench["workloads"]:
        spec = Spec(ROOT, w["name"])
        assert spec.load_module("drivers", spec.traffic["driver"]) is not None
        for m in spec.metrics("per_layer"):
            reader = spec.load_module("layer_metrics", m["name"])
            assert reader is not None and callable(reader.read), m["name"]


def test_files_under_paths_are_named_from_name_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel), rel


def test_sweep_file_gives_the_chat_cells_rate(kept_chat_spec):
    spec = kept_chat_spec
    with open(spec.path("sweeps", "mistral7b-chat-steady.json")) as f:
        sweep = json.load(f)
    assert sweep["device"]["platform"] == "tpu"
    assert spec.traffic["rate_req_s"] == pytest.approx(
        sweep["chosen_rate_req_s"])
    assert sweep["chosen_rate_req_s"] == pytest.approx(
        0.8 * sweep["knee_req_s"], abs=0.006)
