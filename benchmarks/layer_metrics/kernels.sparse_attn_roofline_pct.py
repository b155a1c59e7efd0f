"""Kernels: the share of its roofline that a decode step's attention over
the chosen rows reaches. The least time the chip could take a step is
the larger of the chosen latent rows' bytes, each read once for keys and
values together, at the peak bytes/s, and their operations (every head's
query against the row, its probability times the row's latent part) at
the peak FLOP/s; the configuration's reference counts both
(`sparse_attn_min_bytes`, `sparse_attn_flops`) from the rows the steps
*chose*: `sparse_rows_read` over `k` of the stretch's
`engine.dispatch_block` spans, never the lanes a row is padded to nor a
kernel's grid. Over the device time a step under the scope `attn_sparse`
inside the decode programs (`models/latent.py`: the step's row into the
cache, the gather of the chosen rows and the attention over them), so a
gather that writes the rows out and a kernel that reads them again count
against the share. Nothing in a rehearsal, from a program without the
scope or the counter, or from a reference that counts no chosen rows."""

from lib import peaks, progspans, scopetime

SCOPE, COUNT = "attn_sparse", "sparse_rows_read"
LEAST = ("sparse_attn_min_bytes", "sparse_attn_flops")


def read(metric, m, scope=SCOPE, count=COUNT, least=LEAST):
    if m["ctx"].rehearse:       # no peaks for a CPU: no number
        return None
    ps = progspans.for_run(m)
    spent_s = (scopetime.decode_scope_seconds(m) or {}).get(scope) \
        if ps else None
    steps = ps.decode_steps() if ps else 0.0
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    ref = m["ctx"].spec.reference
    if not spent_s or not steps or not sums.get("k") \
            or not sums.get(count) or not hasattr(ref, least[0]):
        return None
    peak = peaks.peaks_for(m["devices"][0].device_kind)
    rows = sums[count] / sums["k"]
    least_s = max(
        getattr(ref, least[0])(m["arch"], rows) / peak["hbm_bytes_per_s"],
        getattr(ref, least[1])(m["arch"], rows) / peak["bf16_flops"])
    return 100.0 * least_s / (spent_s / steps)
