"""Fixtures of the benchmark's own tests: the import paths, and a
temporary copy of the benchmark with a tiny test-only configuration and
four tiny cells, one per driver, that a CPU can run in seconds."""

import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

# Real cell -> its tiny stand-in (same driver, same metrics).
TINY = {"mistral7b-docqa-lone": "tiny-lone",
        "internlm2-1b8-batch-closed": "tiny-closed",
        "internlm2-1b8-train-fsdp4": "tiny-train"}
# The open loop has no cell of its own in BENCHMARK.json since the chat
# cell was taken out (PERF.md, section 2): its tiny stand-in reports what
# the lone caller's does, and the cell kept for later is added to a copy
# of BENCHMARK.json the way a later PR would add it.
TINY_OPEN = "tiny-open"
TINY_CELLS = sorted(TINY.values()) + [TINY_OPEN]
KEPT_CHAT = {"name": "mistral7b-chat-steady",
             "config": "mistral-7b-v0.3-l16", "traffic": "chat-steady",
             "chips": 1, "why": "kept for later: PERF.md, Open questions"}

_LEN = {"dist": "lognormal", "median": 20, "sigma": 0.6, "min": 4, "max": 60}
_OUT = {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2, "max": 24}
TINY_FILES = {
    "configs/tiny.json": {
        "vocab_size": 256, "d_model": 64, "n_layers": 2, "n_heads": 4,
        "n_kv_heads": 2, "d_ff": 128, "rope_theta": 10000.0,
        "norm_eps": 1e-5, "tie_embeddings": False},
    "traffic/tiny-open.json": {
        "driver": "serve_open", "trace_seed": 1, "n_requests": 64,
        "prompt_len": _LEN, "output_len": _OUT, "max_total_len": 127,
        "rate_req_s": 6.0, "lead_in_s": 0.5, "drain_limit_s": 5.0},
    "traffic/tiny-closed.json": {
        "driver": "serve_closed", "trace_seed": 2, "n_requests": 64,
        "clients": 4, "measure": "ended_in_window", "prompt_len": _LEN,
        "output_len": _OUT, "max_total_len": 127, "lead_in_s": 0.5,
        "drain_limit_s": 0.0},
    "traffic/tiny-lone.json": {
        "driver": "serve_closed", "trace_seed": 3, "n_requests": 32,
        "clients": 1, "measure": "sent_in_window",
        "prompt_len": {"dist": "loguniform", "min": 30, "max": 100},
        "output_len": {"dist": "fixed", "value": 4}, "max_total_len": 127,
        "lead_in_s": 0.3, "drain_limit_s": 5.0},
    "traffic/tiny-train.json": {
        "driver": "train", "batch_size": 4, "seq_len": 32,
        "distinct_batches": 2, "warm_steps": 1, "lr": 3e-4},
    "cells/tiny-train.json": {
        "plan": {"fsdp": 4},
        "model": {"dtype": "float32", "param_dtype": "float32",
                  "max_seq_len": 32, "remat": True, "ce_chunk": 16},
        "trace_seconds": 0.5},
}
_SERVE_CELL = {
    "slots": 4, "max_seq_len": 128, "decode_block": 8,
    "model": {"dtype": "float32", "param_dtype": "float32",
              "max_seq_len": 128, "remat": False},
    "check": {"prompt_lens": [12, 20], "decode_steps": 3},
    "trace_seconds": 0.5}
for _n in ("tiny-open", "tiny-closed", "tiny-lone"):
    TINY_FILES[f"cells/{_n}.json"] = _SERVE_CELL


def make_tiny_root(dst: str) -> str:
    """A copy of the benchmark under `dst` with the tiny files added and
    a BENCHMARK.json that names them. Edits no file it copies."""
    shutil.copytree(BENCH, os.path.join(dst, "benchmarks"))
    for rel, content in TINY_FILES.items():
        with open(os.path.join(dst, "benchmarks", rel), "w") as f:
            json.dump(content, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny", "source": "test only",
                         "file": "benchmarks/configs/tiny.json",
                         "reduced": [], "why": "test only"}]
    bench["workloads"] = [
        {"name": n, "config": "tiny", "traffic": n,
         "chips": 4 if n == "tiny-train" else 1, "why": "test only"}
        for n in TINY_CELLS]
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m:
                m["workloads"] = [TINY[w] for w in m["workloads"]]
                if TINY["mistral7b-docqa-lone"] in m["workloads"]:
                    m["workloads"].append(TINY_OPEN)
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dst


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_tiny_root(str(tmp_path_factory.mktemp("bench")))


@pytest.fixture(scope="session")
def kept_chat_spec(tmp_path_factory):
    """The chat cell's files as a cell: BENCHMARK.json with its entry
    appended, beside the benchmark's own directory."""
    from lib.spec import Spec

    root = tmp_path_factory.mktemp("kept")
    os.symlink(BENCH, os.path.join(root, "benchmarks"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(KEPT_CHAT)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return Spec(str(root), KEPT_CHAT["name"])


@pytest.fixture(scope="session")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
