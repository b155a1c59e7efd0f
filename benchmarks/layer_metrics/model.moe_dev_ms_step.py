"""Model: device time of the routed feed-forward layers inside the decode
programs (`jit_decode*`), per decode step the device ran in the traced
stretch: the operations under the scopes `moe_router`, `moe_experts` and
`moe_shared` (`models/moe.py`, `models/periodic.py`), all routed layers of
a step together."""

from lib import scopetime


def read(metric, m):
    return scopetime.decode_ms_step(
        m, ("moe_router", "moe_experts", "moe_shared"))
