"""Model: device time of latent attention's projections inside the decode
programs (`jit_decode*`), per decode step the device ran in the traced
stretch: the operations under the scope `mla_proj` (`models/latent.py`:
the down-projections of the query and of the keys-and-values with their
norms and rotary, `W_UK` folded into the query, `W_UV` onto the weighted
rows, and `W_o`), all layers of a step together. Bound by the weights'
bytes at any batch this engine runs. Nothing from a program without the
scope."""

from lib import scopetime


def read(metric, m):
    return scopetime.decode_ms_step(m, ("mla_proj",))
