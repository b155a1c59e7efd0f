"""Kernels: the share of its roofline that a decode step's indexer
reaches. The least time the chip could take a step is the larger of the
held rows' indexer keys, each read once, at the peak bytes/s, and the
scores' operations (every indexer head's query against the key) at the
peak FLOP/s; the configuration's reference counts both
(`indexer_min_bytes`, `indexer_flops`) from the rows the steps had to
score, every row held: `cache_rows_held` over `k` of the stretch's
`engine.dispatch_block` spans (a scorer that also reads rows nobody
holds reads lower for it). Over the device time a step under the scope `attn_index` inside
the decode programs (`models/latent.py`): the scorer, and with it the
indexer's projections, the key's write and the exact top-k, whose work
the least time counts as nothing: a choice that costs as much as the
scan halves this share. Nothing in a rehearsal, from a program without
the scope or the counter, or from a reference that counts no indexer."""

import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "_bench_sparse_attn_roofline", os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "kernels.sparse_attn_roofline_pct.py"))
_rows = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_rows)


def read(metric, m):
    return _rows.read(metric, m, scope="attn_index",
                      count="cache_rows_held",
                      least=("indexer_min_bytes", "indexer_flops"))
