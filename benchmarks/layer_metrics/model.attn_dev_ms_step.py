"""Model: device time of decode attention by kind of layer (`.window`:
the layers that read a ring of `sliding_window` rows a slot; `.global`:
those that read `S_max`), per decode step the device ran in the traced
stretch: the operations under the scope `attn_window` or `attn_global`
(`models/periodic.py`) inside `jit_decode*`: the cache write, the scores,
the softmax and the product with V, all layers of the kind together."""

from lib import scopetime


def read(metric, m):
    return scopetime.decode_ms_step(
        m, ("attn_" + metric["name"].rsplit(".", 1)[1],))
