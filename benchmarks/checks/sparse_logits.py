#!/usr/bin/env python3
"""`checks/routed_logits.py`'s readings for a configuration whose
attention reads the rows an indexer chooses, with the choice's own flips:

    python3 benchmarks/checks/sparse_logits.py --workload <serving cell> \\
        --seeds 11,2147483648,... [--dtype float32] [--control 2] \\
        [--control-len 4096] [--prompt-lens 1500]

The same seeds' loop and the same readings (the program's prefill and
decode through both caches against the configuration's float32 reference
at the cell's `check` sizes; the control on weights rounded to 8-bit
floats; the token-layer pairs whose chosen experts differ), and one
more: over the control's sequence, which has to be longer than
`index_topk` for the choice to choose, the (query, layer) pairs whose
chosen *rows* differ between the program (`chosen_rows` of the
configuration's stack module, under the activation dtype) and the
reference (`chosen_rows`, float32), beside the rows that differ as a
share of the rows chosen. A score rounded to bf16 moves a row across the
2,048th place where two scores lie close; whether that moves a logit is
what the first reading says. One JSON line a seed, then one with the
sound runs' readings, the shares flipped, the control's smallest reading
and the limit. No timed window; it prints no result line and is no cell.
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


def row_flips(spec, cfg, params, tokens):
    """{queries whose chosen rows differ, (query, layer) pairs compared,
    rows chosen by one side alone, rows the reference chose}, or None
    rows differing over rows chosen a layer}, or None where either side
    cannot say."""
    import numpy as np

    from ray_tpu.models.transformer import stack

    ours_fn = getattr(stack(cfg), "chosen_rows", None)
    if ours_fn is None or not hasattr(spec.reference, "chosen_rows"):
        return None
    ours = np.asarray(ours_fn(cfg, params, tokens))
    differ = pairs = rows = chosen = 0
    by_layer = []
    for mine, theirs in zip(ours, spec.reference.chosen_rows(
            spec.config, params, tokens)):
        theirs = np.asarray(theirs)
        off = mine != theirs
        differ += int(np.sum(np.any(off, axis=-1)))
        pairs += len(tokens)
        rows += int(np.sum(off))
        chosen += int(np.sum(theirs))
        by_layer.append(float(np.sum(off)) / max(int(np.sum(theirs)), 1))
    return {"row_choice_flips": differ, "row_choice_pairs": pairs,
            "rows_differing": rows, "rows_chosen": chosen,
            "rows_differing_share_by_layer": by_layer}


def main(argv=None, *, root: str = ROOT, rehearse: bool = False,
         out=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--dtype", default=None,
                    help="activation dtype in place of the cell's")
    ap.add_argument("--control", type=int, default=2,
                    help="how many of the seeds also read the control")
    ap.add_argument("--control-len", type=int, default=4096)
    ap.add_argument("--prompt-lens", default=None,
                    help="comma-separated prompt lengths in place of the "
                         "cell's `check.prompt_lens`: a reading a length "
                         "tells the lengths that choose from those that "
                         "do not")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="a key of the configuration file, or model.<key> "
                         "of the cell's own, for this reading "
                         "(index_topk=32768: every row chosen, so what is "
                         "left is arithmetic; model.index_dtype=null: the "
                         "choice made in the activation dtype)")
    args = ap.parse_args(argv)
    out = out or sys.stdout

    for p in (ROOT, BENCH, HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    if not rehearse:
        os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                              os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import numpy as np

    import routed_logits
    import serve_logits
    from lib import harness, modelcfg, serving
    from lib.spec import Spec

    spec = Spec(root, args.workload)
    for item in args.set:
        key, _, value = item.partition("=")
        if key.startswith("model."):
            spec.sizes.setdefault("model", {})[key[6:]] = json.loads(value)
        else:
            spec.config[key] = json.loads(value)
    if args.dtype:
        spec.sizes.setdefault("model", {})["dtype"] = args.dtype
    if args.prompt_lens:
        spec.sizes.setdefault("check", {})["prompt_lens"] = [
            int(n) for n in args.prompt_lens.split(",")]
    harness.Context(spec, 0, 0.0, False, time.monotonic(),
                    rehearse).devices()     # a TPU with the cell's chips
    cfg = modelcfg.transformer_config(spec.config, spec.sizes)
    slots, max_seq = int(spec.sizes["slots"]), int(spec.sizes["max_seq_len"])

    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        ctx = harness.Context(spec, seed, 0.0, False, t0, rehearse)
        params = modelcfg.make_params(cfg, seed)
        jax.block_until_ready(params)
        row = {"seed": seed}
        row.update(serving.check_against_reference(ctx, cfg, params, slots,
                                                   max_seq))
        rng = np.random.default_rng([seed, 0x636F6E74])
        tokens = rng.integers(0, cfg.vocab_size,
                              size=args.control_len).tolist()
        flips = routed_logits.routing_flips(spec, cfg, params, tokens)
        if flips is not None:
            row.update(routing_flips=flips[0], routing_pairs=flips[1])
        row.update(row_flips(spec, cfg, params, tokens) or {})
        if n < args.control:
            ref = np.asarray(spec.reference.forward_logits(
                spec.config, params, tokens), np.float32)
            params = serve_logits.fp8_in_place(params)
            row["control_rel_rms_err"] = serve_logits._rel_rms(
                spec.reference.forward_logits(spec.config, params, tokens),
                ref)
        del params
        gc.collect()
        row["seconds"] = time.monotonic() - t0
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)

    def total(key):
        return sum(r.get(key, 0) for r in rows)

    def share(part, whole):
        return total(part) / total(whole) if total(whole) else None

    controls = [r["control_rel_rms_err"] for r in rows
                if "control_rel_rms_err" in r]
    readings = [r["logit_rel_rms_err"] for r in rows]
    print(json.dumps({
        "workload": args.workload, "dtype": str(cfg.dtype.__name__),
        "index_dtype": getattr(cfg, "index_dtype", None),
        "seeds": len(rows), "sound_readings": readings,
        "sound_largest_rel_rms_err": max(readings),
        "over_limit": sum(r > serving.LOGIT_REL_TOL for r in readings),
        "control_smallest_rel_rms_err": min(controls) if controls else None,
        "routing_flips": total("routing_flips"),
        "routing_pairs": total("routing_pairs"),
        "routing_flip_share": share("routing_flips", "routing_pairs"),
        "row_choice_flips": total("row_choice_flips"),
        "row_choice_pairs": total("row_choice_pairs"),
        "row_choice_flip_share": share("row_choice_flips",
                                       "row_choice_pairs"),
        "rows_differing_share": share("rows_differing", "rows_chosen"),
        "limit": serving.LOGIT_REL_TOL}), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
