"""Model: device time of prefill attention by kind of layer (`.window`:
the layers that see `sliding_window` keys; `.global`: those that see
every earlier position), per request whose prefill ran in the traced
stretch: the operations under the scope `attn_window` or `attn_global`
(`models/periodic.py`) inside `jit_prefill*` / `jit_first_token*`: scores,
mask, softmax, the product with V and the tile's rows into the cache, all
layers of the kind together. A window layer whose work follows its window
reads (layers of the kind) x window / (S / 2) of a global one's; one that
meets all S keys and masks reads twice a global layer's."""

from lib import prefilltime


def read(metric, m):
    return prefilltime.scope_ms_req(
        m, ("attn_" + metric["name"].rsplit(".", 1)[1],))
