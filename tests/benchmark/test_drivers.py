"""Each driver end to end on the tiny test-only configuration, on the
CPU (four virtual devices for `train`): the last line's keys, no device
metric printed from a CPU, nothing compiled inside the window; and the
harness taking a new configuration, traffic mix and reader as files."""

import hashlib
import io
import json
import os
import shutil
import subprocess
import sys

import pytest

import run
from conftest import BENCH, ROOT, TINY_CELLS, make_tiny_root

LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(root, workload, trace, seed=2**31 + 77, seconds=2):
    out = io.StringIO()
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  root=root, rehearse=True, out=out)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def _e2e_names(root, workload):
    from lib.spec import Spec

    return {m["name"] for m in Spec(root, workload).metrics("end_to_end")}


@pytest.mark.parametrize("workload", TINY_CELLS)
def test_driver_end_to_end(tiny_root, workload):
    line = _run(tiny_root, workload, trace=0)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    # A CPU run proves control flow: nothing is printed as a metric.
    assert line["metrics"] == {}
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"]
    assert set(line["rehearsal"]) == _e2e_names(tiny_root, workload)
    for m in line["rehearsal"].values():
        assert m["value"] > 0 and m["unit"]


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed",
                                      "tiny-train"])
def test_traced_run_reports_per_layer_and_compiles_nothing(tiny_root,
                                                           workload):
    line = _run(tiny_root, workload, trace=1)
    assert line["metrics"] == {}
    got = line["rehearsal"]
    compiles = [n for n in got if n.startswith("device.compiles_in_window")]
    assert len(compiles) == 1 and got[compiles[0]]["value"] == 0
    # No device on a CPU: the readers of the device trace return nothing
    # and the harness leaves them out.
    assert not [n for n in got if n.startswith(
        ("device.idle_pct", "kernels.", "model.", "trainer.mfu_pct"))]
    assert not (set(got) & _e2e_names(tiny_root, workload))
    assert {"device_ops", "idle_gaps"} == set(line["breakdown"])


def test_no_accelerator_is_an_error_not_a_fallback(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", "mistral7b-docqa-lone", "--seed", "1",
                  "--seconds", "1", "--trace", "0"], out=io.StringIO())
    assert e.value.code not in (0, None)
    assert "no accelerator" in str(e.value.code)


def test_unknown_workload_is_an_error():
    with pytest.raises(SystemExit):
        run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"],
                 rehearse=True, out=io.StringIO())


def test_benchmark_alone_exits_non_zero(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    `paths` there is no system to measure."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks")
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "mistral7b-docqa-lone", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert '"correct"' not in p.stdout


def _digests(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("__pycache__", ".bench_out")]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_config_traffic_and_reader_are_only_files(tmp_path):
    """What a later PR does: it drops in a configuration, a traffic mix,
    a cell's sizes and a per-layer reader, appends entries to
    BENCHMARK.json, and edits no file that was there."""
    root = make_tiny_root(str(tmp_path / "b"))
    before = _digests(os.path.join(root, "benchmarks"))
    bdir = os.path.join(root, "benchmarks")
    with open(os.path.join(bdir, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(n_layers=3, d_ff=192)
    with open(os.path.join(bdir, "configs", "tiny-deep.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "tiny-lone.json")) as f:
        mix = json.load(f)
    mix.update(trace_seed=99, clients=2,
               output_len={"dist": "loguniform", "min": 3, "max": 9})
    with open(os.path.join(bdir, "traffic", "tiny-pair.json"), "w") as f:
        json.dump(mix, f)
    shutil.copy(os.path.join(bdir, "cells", "tiny-lone.json"),
                os.path.join(bdir, "cells", "tiny-deep-pair.json"))
    with open(os.path.join(bdir, "layer_metrics", "engine.answers.py"),
              "w") as f:
        f.write("def read(metric, m):\n"
                "    return float(sum(r.tokens for r in m['rows']))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "tiny-deep", "source": "test only",
                             "file": "benchmarks/configs/tiny-deep.json",
                             "reduced": [], "why": "test only"})
    bench["workloads"].append({"name": "tiny-deep-pair",
                               "config": "tiny-deep", "traffic": "tiny-pair",
                               "chips": 1, "why": "test only"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "tpot_p90_ms"):
            m["workloads"].append("tiny-deep-pair")
    bench["per_layer"].append({
        "name": "engine.answers.pair", "unit": "tokens", "better": "higher",
        "source": "program_counter", "layer": "Engine",
        "moves": "ttft_p90_ms", "workloads": ["tiny-deep-pair"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    line = _run(root, "tiny-deep-pair", trace=0)
    assert line["correct"] and line["failed"] == 0
    assert set(line["rehearsal"]) == {"ttft_p90_ms", "tpot_p90_ms",
                                      "setup_s"}
    traced = _run(root, "tiny-deep-pair", trace=1)
    assert traced["rehearsal"]["engine.answers.pair"]["value"] > 0
    after = _digests(os.path.join(root, "benchmarks"))
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "configs/tiny-deep.json", "traffic/tiny-pair.json",
        "cells/tiny-deep-pair.json", "layer_metrics/engine.answers.py"}
