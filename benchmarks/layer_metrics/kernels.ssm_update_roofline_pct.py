"""Kernels: the share of its roofline that a decode step's state-space
update reaches. The least time the chip could take a step is the
recurrent states of the slots a request owns, each read once and written
once in float32, at the peak bytes/s: the configuration's reference
counts them (`ssm_state_bytes`) from the updates the steps had to make,
`linear_slot_steps_live` (an owned slot, a step, a state-space layer)
over `k` of the stretch's `engine.dispatch_block` spans; a state nobody
owns counts for nothing, so a kernel that moves those too reads lower.
Bound by bytes: an update is 6 operations and an exponential a state
element of 8 bytes moved. Over the device time a step of the events
whose `kernel_metadata` reads `ssm_update` (`ops/selective_scan`): the
kernel alone, not the scope `attn_ssm` around it (the layer's four
projections read weights, which are not the update's bytes). Nothing in
a rehearsal, from a program without the kernel or the counter, or from a
reference that counts no state."""

from lib import peaks, progspans

KERNEL, COUNT = "ssm_update", "linear_slot_steps_live"


def read(metric, m):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_s = ps.kernel_s.get(KERNEL) if ps else None
    steps = ps.decode_steps() if ps else 0.0
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    ref = m["ctx"].spec.reference
    if not spent_s or not steps or not sums.get("k") \
            or not sums.get(COUNT) or not hasattr(ref, "ssm_state_bytes"):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    least_s = ref.ssm_state_bytes(m["arch"], sums[COUNT] / sums["k"]) \
        / peak["hbm_bytes_per_s"]
    return 100.0 * least_s / (spent_s / len(ps.devices) / steps)
