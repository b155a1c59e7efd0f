"""Engine: tokens handed to a caller, as a share of the slot-steps the
decode programs computed: sum of `emitted` over sum of `k` x `slots` of
the stretch's `ray_tpu:engine.process_block` spans. The rest is slots
that hold no request and steps past a request's end."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    blocks = ps.named("engine.process_block") if ps else []
    computed = sum(b.stats.get("k", 0) * b.stats.get("slots", 0)
                   for b in blocks)
    if not computed:
        return None
    return 100.0 * sum(b.stats.get("emitted", 0) for b in blocks) / computed
