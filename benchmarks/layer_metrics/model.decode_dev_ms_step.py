"""Model: device time of the decode programs in the traced stretch, per
decode step. A block of k fused steps counts k: the count is the
engine's `decode_ticks`, read at both ends of the stretch. The engine
counts a block when it dispatches it, one block ahead of the device, so
the count is off by up to a block at each end."""

PROGRAMS = r"decode"


def read(metric, m):
    tr, ctx = m.get("trace"), m["ctx"]
    ticks = ctx.probe1.get("decode_ticks", 0) - ctx.probe0.get(
        "decode_ticks", 0)
    if tr is None or not tr.module_s or ticks <= 0:
        return None
    secs, _ = tr.modules_matching(PROGRAMS)
    return secs / max(1, len(tr.devices)) * 1e3 / ticks
