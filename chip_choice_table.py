#!/usr/bin/env python3
"""A tile's choice of rows on the chip, at `glm5-longctx-closed`'s shapes:
`ops/sparse_attention.topk_bias` told where its block of queries stands,
held bit for bit to its definition in XLA over whole rows
(`_topk_bias_xla`) and, with `--parent`, to that checkout's `topk_bias`.
Run by no cell and by no test but its own rehearsal:

    chiprun -- python chip_choice_table.py [--parent .archive/parent]

Two parts (`--only table|programs` runs one), one JSON line a row, each
written to `--out` (`chiprun_out/choice_table.jsonl`) as it is printed;
the last line says whether every bias was equal, and the exit code is 1
where one was not, where a call did not take the kernel, or where there
is no TPU.

- `table`: the choice alone. For each of the cell's two long buckets
  (32,768 and 16,384 columns) and every block of 1,024 queries of the
  longest prompt the bucket takes (`--prompt`: 28,000 tokens), float32
  scores from `index_scores_tile` on seeded normal queries, weights and
  keys at the configuration's 32 heads of 128: the columns counted,
  whether the bias equals the definition's and the parent's in every bit,
  and `ms` a call of each (the least mean over `--reps` batches of
  `--calls` back-to-back calls, host clock around `block_until_ready`).
  Then, a bucket, the same scores rounded to quarters, so that rows tie
  at the threshold and the `cond` takes the exact path, and the float32
  bias.
- `programs`: the choice inside the cell's own programs. With the cell's
  configuration and weights from `--seeds`, `latent.chosen_rows` (4,096
  and 8,192 tokens) and `latent.prefill` (28,000 tokens in the 32,768
  bucket, 15,000 in the 16,384 one) run with `topk_bias` wrapped for the
  length of the run: every call `_attend_chunk` makes also computes the
  definition's and the parent's bias on the same scores, and a host
  callback counts the elements whose bits differ and the calls whose
  `cond` took the tie path. What decides a cell's `correct` is the
  benchmark's own comparison; this says that the bias the programs attend
  under is the parent's.

`--tiny` rehearses both in the Pallas interpreter on a CPU (small
buckets, a test-size stack): its times mean nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib.util
import json
import math
import os
import sys
import time
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.models import configs, latent
from ray_tpu.models.generate import init_kv_cache
from ray_tpu.models.transformer import init_params
from ray_tpu.ops import sparse_attention as sa

ROOT = os.path.dirname(os.path.abspath(__file__))
CELL = "glm5-longctx-closed"


def _load_parent(root: str):
    """The parent checkout's ops/sparse_attention.py as a module of this
    tree's package (it imports `.flash_attention`'s constants alone)."""
    path = os.path.join(root, "ray_tpu", "ops", "sparse_attention.py")
    spec = importlib.util.spec_from_file_location(
        "ray_tpu.ops._parent_sparse_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _timed(fn, args, calls: int, reps: int) -> float:
    jax.block_until_ready(fn(*args))               # compile, warm
    best = math.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / calls)
    return best * 1e3


def _bits(x) -> jax.Array:
    """As whole numbers: XLA cannot compare before it rounds."""
    return lax.bitcast_convert_type(
        x, {2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])


def _differing(a, b) -> jax.Array:
    return jnp.sum(_bits(a) != _bits(b))


def _takes_the_kernel(fn, *args) -> bool:
    shapes = [jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))
              for x in args]
    return "pallas_call" in str(jax.make_jaxpr(fn)(*shapes))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout of the parent commit")
    ap.add_argument("--out", default="chiprun_out/choice_table.jsonl")
    ap.add_argument("--only", choices=["table", "programs"])
    ap.add_argument("--prompt", type=int, default=28000)
    ap.add_argument("--calls", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=54)
    ap.add_argument("--seeds", default="5400000011,3000000019",
                    help="the weights of `programs`")
    ap.add_argument("--tiny", action="store_true")
    a = ap.parse_args(argv)

    interpret = None
    if a.tiny:
        interpret, a.calls, a.reps, a.seeds = True, 1, 1, "54"
    elif jax.default_backend() != "tpu":
        print("chip_choice_table: no TPU here (--tiny rehearses on a CPU)",
              file=sys.stderr)
        return 1
    parent = _load_parent(a.parent) if a.parent else None
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as out_f:
        ok, rows = True, 0

        def say(row):
            nonlocal ok, rows
            ok &= all(v for key, v in row.items()
                      if key.startswith("equal") or key == "kernel")
            rows += 1
            line = json.dumps(row)
            print(line, flush=True)
            out_f.write(line + "\n")
            out_f.flush()

        if a.only != "programs":
            _table(a, say, parent, interpret)
        if a.only != "table":
            _programs(a, say, parent, interpret)
        device = jax.devices()[0]
        say({"ok": ok, "rows": rows, "device": {
            "platform": device.platform, "kind": device.device_kind}})
    return 0 if ok else 1


# -- the choice alone --------------------------------------------------------

def _table(a, say, parent, interpret) -> None:
    buckets, block, heads, k = (32768, 16384), latent._CHOICE_ROWS, 32, 2048
    if a.tiny:
        buckets, block, heads, k, a.prompt = (4096, 2048), 512, 2, 24, 3000
    scores_of = jax.jit(functools.partial(sa.index_scores_tile,
                                          interpret=interpret))
    for S in buckets:
        key = jax.random.fold_in(jax.random.key(a.seed), S)
        keys = jax.random.normal(key, (1, S, 128), jnp.float32)
        fns = {}

        def paths(dtype):
            if dtype not in fns:
                fns[dtype] = (
                    jax.jit(functools.partial(sa.topk_bias, k=k, dtype=dtype,
                                              interpret=interpret)),
                    jax.jit(functools.partial(sa._topk_bias_xla, k=k,
                                              dtype=dtype)),
                    parent and jax.jit(functools.partial(
                        parent.topk_bias, k=k, dtype=dtype,
                        interpret=interpret)))
            return fns[dtype]

        def compare(first, scores, dtype, what):
            ours, xla, theirs = paths(dtype)
            first = jnp.int32(first)
            got = ours(scores, q_offset=first)
            chosen = np.sum(np.asarray(got) == 0, axis=-1)
            row = {"bucket": S, "first": int(first), "scores": what,
                   "dtype": jnp.dtype(dtype).name,
                   "kernel": _takes_the_kernel(
                       lambda s, f: ours(s, q_offset=f), scores, first),
                   "columns_counted": int(min(
                       sa.columns_counted(int(first), block, S), S)),
                   "chosen_a_row": [int(chosen.min()), int(chosen.max())],
                   "equal_definition": not int(_differing(got, xla(scores))),
                   "ms": _timed(lambda s: ours(s, q_offset=first), (scores,),
                                a.calls, a.reps)}
            if theirs:
                row["equal_parent"] = not int(_differing(got, theirs(scores)))
                row["ms_parent"] = _timed(theirs, (scores,), a.calls, a.reps)
            say(row)

        last = None
        for first in range(0, min(-(-a.prompt // block) * block, S), block):
            kq, kw = jax.random.split(jax.random.fold_in(key, first))
            q = jax.random.normal(kq, (1, block, heads, 128), jnp.float32)
            w = jax.random.normal(kw, (1, block, heads), jnp.float32) \
                * heads ** -0.5
            last = first, scores_of(q, w, keys, jnp.int32(first))
            compare(*last, jnp.bfloat16, "index_scores_tile")
        first, scores = last
        compare(first, scores, jnp.float32, "index_scores_tile")
        compare(first, jnp.round(scores * 4) / 4, jnp.bfloat16,
                "rounded to quarters")


# -- the choice inside the cell's programs -------------------------------------

def _cell_config(seed: int):
    """The cell's configuration at its sizes, and weights from `seed`, as
    the benchmark makes them."""
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        from lib import modelcfg
        from lib.spec import Spec
    finally:
        sys.path.pop(0)
    spec = Spec(ROOT, CELL)
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    return cfg, modelcfg.make_params(cfg, seed)


def _tiny_config(seed: int):
    cfg = dataclasses.replace(configs.tiny_glm_test(index_topk=64),
                              max_seq_len=4096)
    return cfg, jax.jit(functools.partial(init_params, cfg))(
        jax.random.key(seed & 0x7FFFFFFF))


def _programs(a, say, parent, interpret) -> None:
    seen = {}

    def record(first, tie, *differ):
        seen["calls"] += 1
        seen["tie_path"] += int(tie)
        seen["offsets"].add(int(first))
        for name, n in zip(("definition", "parent"), differ):
            seen[name] += int(n)

    ours = sa.topk_bias

    def both(scores, k, q_offset=None, *, dtype=jnp.bfloat16, **_):
        got = ours(scores, k, q_offset, dtype=dtype, interpret=interpret)
        seen["kernel"] &= _takes_the_kernel(
            lambda s, f: ours(s, k, f, dtype=dtype, interpret=interpret),
            scores, q_offset)
        k = min(int(k), scores.shape[-1])
        _, thr, cnt = sa._threshold_bias(scores, k, q_offset, dtype,
                                         interpret)
        sides = [sa._topk_bias_xla(scores, k, dtype)]
        if parent:
            sides.append(parent.topk_bias(scores, k, dtype=dtype,
                                          interpret=interpret))
        jax.debug.callback(record, q_offset, sa._ties(thr, cnt, k),
                           *[_differing(got, x) for x in sides])
        return got

    walks = (("chosen_rows", 4096), ("chosen_rows", 8192),
             ("prefill", 28000), ("prefill", 15000))
    if a.tiny:
        walks = (("chosen_rows", 2048), ("prefill", 3000))
    for seed in [int(x) for x in a.seeds.split(",")]:
        cfg, params = (_tiny_config if a.tiny else _cell_config)(seed)
        rng = np.random.default_rng([seed, 54])
        for what, n in walks:
            seen.update(calls=0, tie_path=0, offsets=set(), definition=0,
                        parent=0, kernel=True)
            tokens = rng.integers(0, cfg.vocab_size, size=n)
            with mock.patch.object(sa, "topk_bias", both):
                if what == "chosen_rows":
                    out = latent.chosen_rows(cfg, params, tokens.tolist())
                else:
                    S = next(s for s in (4096, 16384, 32768) if s >= n)
                    buf = np.zeros((1, S), np.int32)
                    buf[0, :n] = tokens
                    out = jax.jit(functools.partial(latent.prefill, cfg))(
                        params, init_kv_cache(cfg, 2, S), jnp.asarray(buf),
                        jnp.asarray([n], jnp.int32),
                        jnp.asarray([1], jnp.int32))[1]
                jax.block_until_ready(out)
                jax.effects_barrier()
            del out
            row = {"seed": seed, "program": what, "tokens": n,
                   "kernel": seen["kernel"] and seen["calls"] > 0,
                   "topk_bias_calls": seen["calls"],
                   "offsets": len(seen["offsets"]),
                   "last_offset": max(seen["offsets"], default=None),
                   "calls_on_the_tie_path": seen["tie_path"],
                   "equal_definition": not seen["definition"]}
            if parent:
                row["equal_parent"] = not seen["parent"]
            say(row)
        del params


if __name__ == "__main__":
    sys.exit(main())
