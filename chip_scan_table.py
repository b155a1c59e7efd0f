#!/usr/bin/env python3
"""A tile's delta-rule recurrence on the chip, alone, at the shapes of the
two cells that run it: `ops/delta_rule.chunk_scan`'s kernel (`kda_scan`)
beside the XLA walk it replaces there (`_chunk_scan_xla`). Run by no
cell and by no test but its own rehearsal:

    chiprun -- python chip_scan_table.py

One JSON line a row, each written to `--out`
(`chiprun_out/scan_table.jsonl`) as it is printed: the case, `xla_ms` and
`kernel_ms` a call (the least mean over `--reps` batches of `--calls`
back-to-back calls, host clock around `block_until_ready`), and the
largest difference between the two walks' `o` at the real positions and
between their states. The cases are one layer's scan of
`kimi-linear-docgen-closed` (32 heads; the 4,096 bucket with 2,900 real
tokens and with every token real, the 2,048 bucket with 1,500) and of
`solar-open2-rollout-closed` (64 heads; the 2,048 bucket with 1,300 real
tokens, in float32 and in bf16, and the queue-side tile of 4 x 2,048
with no lengths). The last line says whether every difference stayed
under `--atol` (`--bf16-atol` where the operands are bf16: the two
walks round in other places); the exit code is 1 where one did not or
where there is no TPU. What decides a cell's `correct` is the
benchmark's own comparison.

`--tiny` rehearses it in the Pallas interpreter on a CPU (one chunk pair
of eight heads): its times mean nothing.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

CASES = [  # name, rows, positions, heads, real tokens a row, dtype
    ("kimi-4096", 1, 4096, 32, 2900, "float32"),
    ("kimi-4096-full", 1, 4096, 32, None, "float32"),
    ("kimi-2048", 1, 2048, 32, 1500, "float32"),
    ("solar-2048", 1, 2048, 64, 1300, "float32"),
    ("solar-2048-bf16", 1, 2048, 64, 1300, "bfloat16"),
    ("solar-queue-4x2048", 4, 2048, 64, None, "float32"),
]
TINY = [("tiny", 2, 128, 8, 70, "float32")]


def operands(rows, n, heads, dtype, seed=3, d=128):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(jax.random.key(seed), 5)

    def unit(a):
        return a / jnp.linalg.norm(a, axis=-1, keepdims=True)

    shape = (rows, n, heads)
    q = unit(jax.random.normal(ks[0], shape + (d,))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape + (d,)))
    v = jax.random.normal(ks[2], shape + (d,))
    g = -jnp.exp(jax.random.uniform(ks[3], shape + (d,), minval=-6.0,
                                    maxval=0.5))
    beta = 2 * jax.nn.sigmoid(2 * jax.random.normal(ks[4], shape))
    return tuple(a.astype(dtype) for a in (q, k, v)) + (g, beta)


def timed(f, args, calls, reps):
    import jax

    out = jax.block_until_ready(f(*args))
    best = float("inf")
    for _ in range(reps):
        t = time.perf_counter()
        for _ in range(calls):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t) / calls)
    return best * 1e3, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--calls", type=int, default=5)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--atol", type=float, default=1e-4)
    ap.add_argument("--bf16-atol", type=float, default=0.05)
    ap.add_argument("--out", default="chiprun_out/scan_table.jsonl")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import delta_rule

    if not args.tiny and jax.default_backend() != "tpu":
        print(json.dumps({"ok": False, "error": "no TPU"}))
        return 1
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    xla = jax.jit(delta_rule._chunk_scan_xla)
    kernel = functools.partial(delta_rule._scan_pallas, interpret=args.tiny)
    ok = True
    with open(args.out, "w") as out:
        def line(**row):
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")

        for name, rows, n, heads, real, dtype in TINY if args.tiny else CASES:
            ops = operands(rows, n, heads, dtype)
            assert args.tiny or delta_rule.scan_usable(*ops[:3]), name
            upto = n if real is None else real
            lengths = jnp.full((rows,), upto, jnp.int32)
            zeros = jnp.zeros((rows, heads, 128, 128), jnp.float32)
            calls, reps = (1, 1) if args.tiny else (args.calls, args.reps)
            xla_ms, (want, want_last) = timed(
                xla, ops + (None if real is None else lengths,), calls, reps)
            kernel_ms, (o, last) = timed(kernel, ops + (lengths, zeros),
                                         calls, reps)
            at = (jnp.arange(n) < upto)[None, :, None, None]
            o_err = float(jnp.abs(jnp.where(at, o - want, 0)).max())
            s_err = float(jnp.abs(last - want_last).max())
            finite = bool(jnp.isfinite(o).all())
            # bf16 operands: the two walks round in other places.
            ok = ok and finite and max(o_err, s_err) < (
                args.bf16_atol if dtype == "bfloat16" else args.atol)
            line(case=name, rows=rows, positions=n, heads=heads, real=real,
                 dtype=dtype, xla_ms=xla_ms, kernel_ms=kernel_ms,
                 o_max_abs_diff=o_err, state_max_abs_diff=s_err,
                 finite=finite)
        line(ok=ok, device=jax.devices()[0].device_kind)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
