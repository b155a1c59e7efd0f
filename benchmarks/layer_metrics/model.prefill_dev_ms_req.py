"""Model: device time of the prefill programs (slot prefill and the
queue-side first-token forward) in the traced stretch, per request that
got its first token in it. The programs are found by the names jit gives
them; the `tracing` issue is to make those names stable."""

PROGRAMS = r"prefill|first_token"


def read(metric, m):
    tr, ctx = m.get("trace"), m["ctx"]
    if tr is None or not tr.module_s:
        return None
    secs, _launches = tr.modules_matching(PROGRAMS)
    served = sum(1 for r in m.get("all_rows", [])
                 if ctx.trace_t0 <= r.req.first_token_ts < ctx.trace_t1)
    if not served:
        return None
    return secs / max(1, len(tr.devices)) * 1e3 / served
