#!/usr/bin/env python3
"""The two readings a serving cell's `correct` stands on, many seeds in
one process (they share the compiled programs).

    python3 benchmarks/checks/serve_logits.py --workload <serving cell> \\
        --seeds 11,2147483648,... [--control 3] [--set key=value ...]

For every seed, as the serving drivers do it (`lib/serving.
check_against_reference`, the cell's own `check` sizes): weights from the
seed, the program's prefill and decode through the cache against the
configuration's float32 reference, `logit_rel_rms_err` beside the
harness's limit. For the first `--control` seeds also the control: the
reference on weights rounded to 8-bit floats against the reference on
the weights as they are, over one sequence of `--control-len` tokens;
held to the limit it should come out as not correct. Where the program
and the reference can both say which experts a routed layer chose
(`ray_tpu.models.periodic.chosen_experts`, the reference's
`chosen_experts`), the token-layer pairs whose chosen sets differ are
counted over that sequence: routing flips, told from arithmetic.
`--set` overrides a key of the configuration file for this reading
(`sliding_window=1024`). One JSON line a seed, then one with the sound
runs' largest reading, the control's smallest and the limit. No timed
window; it prints no result line and is no cell.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def fp8_in_place(params):
    """Every weight rounded to an 8-bit float (4 exponent and 3 mantissa
    bits, scaled per tensor so that its largest magnitude lands on the
    format's largest, 240) and back, by `reduce_precision` (the chip's
    compiler drops a cast down and up again). The weights are donated:
    a serving cell's fill most of the chip, so there is no room for a
    copy, and the caller makes them anew from the seed."""
    import jax
    import jax.numpy as jnp

    def q(w):
        f = w.astype(jnp.float32)
        scale = 240.0 / jnp.maximum(jnp.max(jnp.abs(f)), 1e-30)
        return (jax.lax.reduce_precision(f * scale, 4, 3) / scale) \
            .astype(w.dtype)

    return jax.jit(lambda p: jax.tree.map(q, p), donate_argnums=0)(params)


def _rel_rms(got, ref) -> float:
    import numpy as np

    err = np.asarray(got, np.float32) - np.asarray(ref, np.float32)
    return float(np.sqrt(np.mean(err * err) / np.mean(ref * ref)))


def routing_flips(spec, cfg, params, tokens):
    """(token-layer pairs whose chosen experts differ between program and
    reference, pairs compared), or None where either cannot say."""
    import numpy as np

    if getattr(cfg, "arch", "") != "afmoe" \
            or not hasattr(spec.reference, "chosen_experts"):
        return None
    from ray_tpu.models.periodic import chosen_experts

    ours = chosen_experts(cfg, params, tokens)
    theirs = spec.reference.chosen_experts(spec.config, params, tokens)
    flips = sum(int(np.sum(np.any(
        np.sort(np.asarray(a), -1) != np.sort(np.asarray(b), -1), axis=-1)))
        for a, b in zip(ours, theirs))
    return flips, len(ours) * len(tokens)


def read_seed(spec, cfg, seed: int, control: bool, control_len: int,
              rehearse: bool):
    import jax
    import numpy as np

    from lib import harness, modelcfg, serving

    ctx = harness.Context(spec, seed, 0.0, False, time.monotonic(), rehearse)
    slots, max_seq = int(spec.sizes["slots"]), int(spec.sizes["max_seq_len"])
    params = modelcfg.make_params(cfg, seed)
    jax.block_until_ready(params)
    out = {"seed": seed}
    out.update(serving.check_against_reference(ctx, cfg, params, slots,
                                               max_seq))
    rng = np.random.default_rng([seed, 0x636F6E74])
    tokens = rng.integers(0, cfg.vocab_size, size=control_len).tolist()
    flips = routing_flips(spec, cfg, params, tokens)
    if flips is not None:
        out.update(routing_flips=flips[0], routing_pairs=flips[1])
    if control:
        ref = np.asarray(spec.reference.forward_logits(
            spec.config, params, tokens), np.float32)
        params = fp8_in_place(params)
        out["control_rel_rms_err"] = _rel_rms(
            spec.reference.forward_logits(spec.config, params, tokens), ref)
    del params
    gc.collect()
    return out


def main(argv=None, *, root: str = ROOT, rehearse: bool = False,
         out=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--control", type=int, default=3,
                    help="how many of the seeds also read the control")
    ap.add_argument("--control-len", type=int, default=512)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="a key of the configuration file, for this reading")
    args = ap.parse_args(argv)
    out = out or sys.stdout

    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from lib import harness, modelcfg, serving
    from lib.spec import Spec

    spec = Spec(root, args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        try:
            spec.config[key] = json.loads(value)
        except ValueError:
            spec.config[key] = value
    harness.Context(spec, 0, 0.0, False, time.monotonic(),
                    rehearse).devices()     # a TPU with the cell's chips
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)

    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        row = read_seed(spec, cfg, seed, n < args.control, args.control_len,
                        rehearse)
        row["seconds"] = time.monotonic() - t0
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)
    controls = [r["control_rel_rms_err"] for r in rows
                if "control_rel_rms_err" in r]
    print(json.dumps({
        "workload": args.workload, "set": args.set, "seeds": len(rows),
        "sound_largest_rel_rms_err": max(r["logit_rel_rms_err"]
                                         for r in rows),
        "sound_readings": [r["logit_rel_rms_err"] for r in rows],
        "control_smallest_rel_rms_err": min(controls) if controls else None,
        "routing_flips": sum(r.get("routing_flips", 0) for r in rows),
        "routing_pairs": sum(r.get("routing_pairs", 0) for r in rows),
        "limit": serving.LOGIT_REL_TOL}), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
