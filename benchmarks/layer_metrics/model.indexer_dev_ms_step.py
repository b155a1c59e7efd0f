"""Model: device time of the sparse-attention indexer inside the decode
programs (`jit_decode*`), per decode step the device ran in the traced
stretch: the operations under the scope `attn_index` (`models/latent.py`:
the indexer's three projections with the key's norm and rotation, the
step's key into the indexer's cache, the scores of every key a slot
holds (`ops/sparse_attention.index_scores_rows`) and the exact top-k
behind them), all layers of a step together. Nothing from a program
without the scope."""

from lib import scopetime


def read(metric, m):
    return scopetime.decode_ms_step(m, ("attn_index",))
