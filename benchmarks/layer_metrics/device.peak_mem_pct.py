"""Device: `peak_bytes_in_use` over `bytes_limit` of the fullest chip,
over the whole process (set-up and the reference check included)."""


def read(metric, m):
    best = None
    for d in m["devices"]:
        st = d.memory_stats() or {}
        if st.get("bytes_limit"):
            v = 100.0 * st.get("peak_bytes_in_use", 0) / st["bytes_limit"]
            best = v if best is None else max(best, v)
    return best
