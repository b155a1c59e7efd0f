"""Model: device time of the routed feed-forward layers inside the
prefill programs (`jit_prefill*`, `jit_first_token*`), per request whose
prefill ran in the traced stretch: the operations under the scopes
`moe_router`, `moe_experts` and `moe_shared` (`models/moe.py`,
`models/periodic.py`), all routed layers of a tile together: the router,
the pairs' sort and gather, the three grouped products, the weighted sum."""

from lib import prefilltime


def read(metric, m):
    return prefilltime.scope_ms_req(
        m, ("moe_router", "moe_experts", "moe_shared"))
