"""Model: device time of one pass of a looped stack over its layers, per
decode step the device ran in the traced stretch: the operations under
the scope `ut_pass` (`models/periodic._walk`: a scan step a pass, the
layers' `attn_global` and `ffn` scopes inside it and the final norm
behind them) inside `jit_decode*`, over the configuration's `ut_steps`.
What lies outside it a step is the embedding, the exit gate, the head and
the sampler. Nothing where the trace has no such scope."""

from lib import scopetime


def read(metric, m):
    passes = int(m["arch"].get("ut_steps", 0) or 0)
    ms = scopetime.decode_ms_step(m, ("ut_pass",)) if passes > 1 else None
    return None if ms is None else ms / passes
