#!/usr/bin/env python3
"""The flash kernels alone on the chip: the tables PERF.md section 6 (PR
33 the forward, PR 35 dq and dkv) gives and `ops/flash_attention`'s
`_FWD_MEASURED_BLOCKS` / `_BWD_MEASURED_BLOCKS` were chosen from. Run by
no cell and by no test but its own rehearsal:

    chiprun -- python chip_flash_table.py [--parent .archive/parent]
                                          [--only fwd|bwd]

One JSON line a reading (`ms`: the least mean over `--reps` batches of
`--calls` back-to-back calls, host clock around `block_until_ready`), all
of them also in `chiprun_out/flash_table.jsonl`. At each of the cells'
shapes: the block pairs tried; at the pair the kernel chooses, the mask
built on every live block against the edge blocks only, the table of live
pairs against the grid that walks runs of kv blocks (what a traced offset
gets), K and V expanded before the call against kv head `h // group`;
with `--parent`, that checkout's kernel on the same inputs. Then the
reference against the kernel under the kv crossover. Then the backward
at the shapes that train or could (`BWD_SHAPES`): dq and dkv apart (each
jitted alone: the other kernel is dead code) at every block pair, on the
run grid, with K and V expanded before the call and dk, dv summed behind
it, and the parent's two kernels. `--tiny` rehearses
the control flow in the Pallas interpreter on a CPU: its times mean
nothing.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import importlib.util
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp

fa = importlib.import_module("ray_tpu.ops.flash_attention")

# (what runs it, B, S, H, KVH, D, window)
SHAPES = [
    ("mistral7b-docqa-lone tile", 1, 4096, 32, 8, 128, None),
    ("internlm2-1b8-train-fsdp4 step", 2, 4096, 16, 8, 128, None),
    ("mellum2-repoctx-lone global layer", 1, 8192, 32, 4, 128, None),
    ("mellum2-repoctx-lone window layer", 1, 8192, 32, 4, 128, 1024),
]
BLOCKS = [(256, 512), (512, 256), (512, 512), (512, 1024), (1024, 256),
          (1024, 512), (1024, 1024), (2048, 512), (512, 2048), (2048, 2048)]
# The backward's rows: the train cell's launch and 8,192 positions at 32 / 4.
BWD_SHAPES = SHAPES[1:3]
BWD_BLOCKS = [(256, 256), (256, 512), (512, 256), (512, 512), (256, 1024),
              (512, 1024), (1024, 256), (1024, 512), (1024, 1024),
              (2048, 512), (512, 2048)]
# (B, S, H, KVH, D): a one-row tile and batch's eight rows under the
# crossover, where `flash_attention` takes the reference today.
SHORT = [(1, 512, 16, 8, 128), (1, 1024, 16, 8, 128), (8, 512, 16, 8, 128),
         (4, 1024, 16, 8, 128), (1, 1024, 32, 8, 128)]


def _load_parent(root: str):
    """The parent checkout's ops/flash_attention.py as a module of its
    own (it imports nothing of its package)."""
    path = os.path.join(root, "ray_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location("_parent_flash", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _timed(fn, args, calls: int, reps: int):
    """ms a call, or what the compiler said where it refused `fn`."""
    try:
        jax.block_until_ready(fn(*args))            # compile, warm
    except Exception as e:  # noqa: BLE001 - a row of the table, not a stop
        return f"{type(e).__name__}: {str(e)[:160]}"
    best = math.inf
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / calls)
    return best * 1e3


def _inputs(seed, B, S, H, KVH, D, dtype):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (B, H, S, D), dtype),
            jax.random.normal(ks[1], (B, KVH, S, D), dtype),
            jax.random.normal(ks[2], (B, KVH, S, D), dtype))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--parent", help="a checkout of the parent commit")
    ap.add_argument("--out", default="chiprun_out/flash_table.jsonl")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--seed", type=int, default=33)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--only", choices=["fwd", "bwd"])
    a = ap.parse_args(argv)

    shapes, blocks, short = SHAPES, BLOCKS, SHORT
    bwd_shapes, bwd_blocks = BWD_SHAPES, BWD_BLOCKS
    interpret = False
    if a.tiny:
        shapes = [("tiny", 1, 256, 4, 2, 32, None),
                  ("tiny window", 1, 256, 4, 1, 32, 64)]
        blocks, short = [(32, 64), (64, 64)], [(1, 64, 4, 2, 32)]
        bwd_shapes = [("tiny", 1, 1024, 4, 2, 32, None)]
        bwd_blocks = [(256, 512), (512, 512)]
        interpret, a.calls, a.reps = True, 1, 1
    elif jax.default_backend() != "tpu":
        print("chip_flash_table: no TPU here (--tiny rehearses on a CPU)",
              file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as out_f:
        def say(**row):
            line = json.dumps(row)
            print(line, flush=True)
            out_f.write(line + "\n")
            out_f.flush()

        parent = _load_parent(a.parent) if a.parent else None
        dev = jax.devices()[0]
        say(what="device", platform=dev.platform, kind=dev.device_kind,
            calls=a.calls, reps=a.reps, tiny=a.tiny)
        if a.only != "bwd":
            _table(a, say, shapes, blocks, short, interpret, parent)
        if a.only != "fwd":
            _bwd_table(a, say, bwd_shapes, bwd_blocks, interpret, parent)
    return 0


def _table(a, say, shapes, blocks, short, interpret, parent) -> None:
    dtype = jnp.bfloat16
    offs = jnp.zeros((1, 2), jnp.float32)

    def kernel(bq, bk, window, D, *, static=True, mod=fa):
        kw = dict(sm_scale=1.0 / math.sqrt(D), block_q=bq, block_k=bk,
                  causal=True, interpret=interpret, window=window)
        if mod is fa:
            kw["static_offs"] = (0, 0) if static else None
        return jax.jit(lambda q, k, v: mod._fwd_impl(q, k, v, offs, **kw))

    for name, B, S, H, KVH, D, window in shapes:
        q, k, v = _inputs(a.seed, B, S, H, KVH, D, dtype)
        shape = dict(shape=name, B=B, S=S, H=H, KVH=KVH, D=D, window=window)
        expand = jax.jit(lambda x: fa._expand_kv(x, H))
        ke, ve = expand(k), expand(v)
        # Checked on the first and the last head (all of them would be
        # 8.6 GB of scores at 8,192).
        ends = jnp.asarray([0, H - 1])
        want, want_lse = jax.jit(lambda q, k, v: fa._reference(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), offs, sm_scale=1.0 / math.sqrt(D),
            causal=True, window=window))(q[:, ends], ke[:, ends],
                                         ve[:, ends])
        chosen = fa.tileable(S, S, D, *fa._fwd_blocks(S, S, D, window))
        for bq, bk in blocks:
            if S % bq or S % bk:
                continue
            fn = kernel(bq, bk, window, D)
            ms = _timed(fn, (q, k, v), a.calls, a.reps)
            if isinstance(ms, str):
                say(what="table_grid", **shape, blocks=[bq, bk], ms=ms)
                continue
            got, lse = fn(q, k, v)
            say(what="table_grid", **shape, blocks=[bq, bk],
                chosen=(bq, bk) == chosen, ms=ms,
                out_max_err=float(jnp.max(jnp.abs(
                    got[:, ends].astype(jnp.float32) - want))),
                lse_max_err=float(jnp.max(jnp.abs(lse[:, ends]
                                                  - want_lse))),
                **fa.grid_steps(S, S, bq, bk, causal=True, window=window))
        bq, bk = chosen
        say(what="run_grid", **shape, blocks=[bq, bk],
            ms=_timed(kernel(bq, bk, window, D, static=False), (q, k, v),
                      a.calls, a.reps),
            **fa.grid_steps(S, S, bq, bk, causal=True, window=window,
                            q_offset=None))
        say(what="expanded_before_the_call", **shape, blocks=[bq, bk],
            ms=_timed(jax.jit(lambda q, k, v, f=kernel(bq, bk, window, D):
                              f(q, fa._expand_kv(k, H),
                                fa._expand_kv(v, H))),
                      (q, k, v), a.calls, a.reps))
        # The mask on every live block: the table's _INSIDE read as _EDGE.
        pairs = fa._live_pairs
        fa._live_pairs = lambda *x: (lambda qi, ki, kind: (
            qi, ki, (kind != fa._DEAD) * fa._EDGE))(*pairs(*x))
        try:
            say(what="mask_on_every_live_block", **shape, blocks=[bq, bk],
                ms=_timed(kernel(bq, bk, window, D), (q, k, v), a.calls,
                          a.reps))
        finally:
            fa._live_pairs = pairs
        if parent is not None:
            for pbq, pbk in {(256, 512), chosen,
                             (512, 512) if window else (256, 512)}:
                pbq, pbk = fa.tileable(S, S, D, pbq, pbk)
                pfn = kernel(pbq, pbk, window, D, mod=parent)
                say(what="parent_kernel", **shape, blocks=[pbq, pbk],
                    ms=_timed(pfn, (q, ke, ve), a.calls, a.reps),
                    ms_with_expansion=_timed(
                        jax.jit(lambda q, k, v, f=pfn: f(
                            q, fa._expand_kv(k, H), fa._expand_kv(v, H))),
                        (q, k, v), a.calls, a.reps))

    # Under the crossover: what `_XLA_CROSSOVER_SKV` decides, in the
    # layout the models call with, transposes and all.
    for B, S, H, KVH, D in short:
        q, k, v = (jnp.swapaxes(x, 1, 2)
                   for x in _inputs(a.seed, B, S, H, KVH, D, dtype))
        row = dict(what="under_the_crossover", B=B, S=S, H=H, KVH=KVH, D=D)
        for path, kw in (("reference", dict(force_reference=True)),
                         ("kernel", dict(force_pallas=True,
                                         interpret=interpret))):
            fn = jax.jit(lambda q, k, v, kw=kw: fa.flash_attention(
                q, k, v, causal=True, **kw))
            row[path + "_ms"] = _timed(fn, (q, k, v), a.calls, a.reps)
        say(**row)


def _bwd_table(a, say, shapes, blocks, interpret, parent) -> None:
    dtype = jnp.bfloat16
    offs = jnp.zeros((1, 2), jnp.float32)

    def err(got, want):
        return float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                     / jnp.max(jnp.abs(want)))

    for name, B, S, H, KVH, D, _ in shapes:
        q, k, v = _inputs(a.seed, B, S, H, KVH, D, dtype)
        do = _inputs(a.seed + 1, B, S, H, KVH, D, dtype)[0]
        shape = dict(shape=name, B=B, S=S, H=H, KVH=KVH, D=D)
        group = H // KVH
        kw = dict(sm_scale=1.0 / math.sqrt(D), causal=True,
                  interpret=interpret)

        def kernels(bq, bk, *, static=True, mod=fa):
            """dq alone, dkv alone, both: each a jitted call of
            `mod._bwd_impl` on the residuals the forward left."""
            more = {"static_offs": (0, 0) if static else None} \
                if mod is fa else {}
            bwd = functools.partial(mod._bwd_impl, block_q=bq, block_k=bk,
                                    **kw, **more)
            return (jax.jit(lambda *x: bwd(*x, offs)[0]),
                    jax.jit(lambda *x: bwd(*x, offs)[1:]),
                    jax.jit(lambda *x: bwd(*x, offs)))

        def expanded(bwd):
            """What `_flash_bwd_rule` did before dkv read the group: K
            and V expanded in front, dk and dv summed over it behind."""
            def f(q, k, v, do, out, lse):
                dq, dk, dv = bwd(q, fa._expand_kv(k, H),
                                 fa._expand_kv(v, H), do, out, lse)
                return (dq,) + tuple(
                    x.reshape(B, KVH, group, S, D).sum(axis=2)
                    for x in (dk, dv))
            return jax.jit(f)

        out, lse = jax.jit(lambda q, k, v: fa._fwd_impl(
            q, k, v, offs, block_q=min(S, 512), block_k=min(S, 512),
            static_offs=(0, 0), **kw))(q, k, v)
        res = (q, k, v, do, out, lse)
        # Checked on the first kv head and its group of query heads.
        want = jax.jit(lambda q, k, v, do: jax.vjp(
            lambda q, k, v: fa._reference(
                q, fa._expand_kv(k, group), fa._expand_kv(v, group), offs,
                sm_scale=kw["sm_scale"], causal=True)[0], q, k, v)[1](do))(
                    *(x[:, :n].astype(jnp.float32) for x, n in (
                        (q, group), (k, 1), (v, 1), (do, group))))
        chosen = fa.tileable(S, S, D, *fa._bwd_blocks(S, S, D))
        for bq, bk in blocks:
            if S % bq or S % bk:
                continue
            dq_fn, dkv_fn, both = kernels(bq, bk)
            row = dict(what="bwd_table_grid", **shape, blocks=[bq, bk],
                       chosen=(bq, bk) == chosen,
                       dq_ms=_timed(dq_fn, res, a.calls, a.reps),
                       dkv_ms=_timed(dkv_fn, res, a.calls, a.reps))
            if not isinstance(row["dq_ms"], str) \
                    and not isinstance(row["dkv_ms"], str):
                got = both(*res)
                row.update(
                    ms=_timed(both, res, a.calls, a.reps),
                    dq_err=err(got[0][:, :group], want[0]),
                    dk_err=err(got[1][:, :1], want[1]),
                    dv_err=err(got[2][:, :1], want[2]),
                    dq=fa.grid_steps(S, S, bq, bk, causal=True),
                    dkv=fa.grid_steps(S, S, bq, bk, causal=True,
                                      by_kv=True, group=group))
            say(**row)
        bq, bk = chosen
        dq_fn, dkv_fn, both = kernels(bq, bk, static=False)
        say(what="bwd_run_grid", **shape, blocks=[bq, bk],
            dq_ms=_timed(dq_fn, res, a.calls, a.reps),
            dkv_ms=_timed(dkv_fn, res, a.calls, a.reps),
            **fa.grid_steps(S, S, bq, bk, causal=True, q_offset=None))
        # The mask on every live block: `_block_kind` never says inside.
        kind = fa._block_kind
        fa._block_kind = lambda *x: (lambda live, inside: (
            live, inside & False))(*kind(*x))
        try:
            dq_fn, dkv_fn, both = kernels(bq, bk)
            say(what="bwd_mask_on_every_live_block", **shape,
                blocks=[bq, bk],
                dq_ms=_timed(dq_fn, res, a.calls, a.reps),
                dkv_ms=_timed(dkv_fn, res, a.calls, a.reps))
        finally:
            fa._block_kind = kind
        say(what="bwd_expanded_before_the_call", **shape, blocks=[bq, bk],
            ms=_timed(expanded(functools.partial(
                fa._bwd_impl, offs=offs, block_q=bq, block_k=bk,
                static_offs=(0, 0), **kw)), res, a.calls, a.reps))
        if parent is not None:
            for pbq, pbk in {fa.tileable(S, S, D, 256, 512), chosen}:
                dq_fn, dkv_fn, both = kernels(pbq, pbk, mod=parent)
                pres = (q, fa._expand_kv(k, H), fa._expand_kv(v, H), do,
                        out, lse)
                say(what="bwd_parent_kernel", **shape, blocks=[pbq, pbk],
                    dq_ms=_timed(dq_fn, pres, a.calls, a.reps),
                    dkv_ms=_timed(dkv_fn, pres, a.calls, a.reps),
                    ms=_timed(both, pres, a.calls, a.reps),
                    ms_with_expansion=_timed(expanded(functools.partial(
                        parent._bwd_impl, offs=offs, block_q=pbq,
                        block_k=pbk, **kw)), res, a.calls, a.reps))


if __name__ == "__main__":
    sys.exit(main())
