"""Model: device time of the sparse-attention indexer inside the prefill
programs (`jit_prefill*` / `jit_first_token*`), per request whose prefill
ran in the traced stretch: the operations under the scope `attn_index`
(`models/latent.py`: the indexer's projections, a chunk's keys into the
indexer's cache, the chunk's scores against every row of its bucket it
may see (`ops/sparse_attention.index_scores_tile`) and the exact choice
of each query's rows (`topk_bias`)), all layers and chunks of a tile
together. Nothing from a program without the scope."""

from lib import prefilltime


def read(metric, m):
    return prefilltime.scope_ms_req(m, ("attn_index",))
