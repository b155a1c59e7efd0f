"""Engine: the cache rows the owned slots hold, as a share of the rows
the decode blocks' steps span: sum of `cache_rows_held` over sum of
`cache_rows` (`k` x slots x `max_seq_len`) of the stretch's
`ray_tpu:engine.dispatch_block` spans. What a decode step that reads
only the rows held (`ops/decode_attention`) has left to read; nothing
from a program whose spans do not carry the counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    if not sums.get("cache_rows"):
        return None
    return 100.0 * sums.get("cache_rows_held", 0) / sums["cache_rows"]
