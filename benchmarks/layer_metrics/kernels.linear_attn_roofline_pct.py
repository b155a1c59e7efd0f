"""Kernels: the share of its roofline that a decode step's linear
attention reaches. The least time the chip could take a step is the
recurrent states of the slots a request owns, each read once and written
once in float32, at the peak bytes/s: the configuration's reference
counts them (`kda_state_bytes`) from the updates the steps had to make,
`linear_slot_steps_live` (an owned slot, a step, a linear layer) over `k`
of the stretch's `engine.dispatch_block` spans; a state nobody owns
counts for nothing, so a program that moves those too reads lower. Bound
by bytes: an update is 7 operations a state element of 8 bytes moved.
Over the device time a step under the scope `attn_linear` inside the
decode programs (`models/periodic.py`): the update, and with it the
layer's projections (their weights are bytes the least time counts as
nothing, as PR 41's gather was), the convolution, the gates and the
gated norm. Nothing in a rehearsal, from a program without the scope or
the counter, or from a reference that counts no state."""

from lib import peaks, progspans, scopetime

SCOPE, COUNT = "attn_linear", "linear_slot_steps_live"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_s = (scopetime.decode_scope_seconds(m) or {}).get(SCOPE) \
        if ps else None
    steps = ps.decode_steps() if ps else 0.0
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    ref = m["ctx"].spec.reference
    if not spent_s or not steps or not sums.get("k") \
            or not sums.get(COUNT) or not hasattr(ref, "kda_state_bytes"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least_s = ref.kda_state_bytes(m["arch"], sums[COUNT] / sums["k"]) \
        / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (spent_s / steps)
