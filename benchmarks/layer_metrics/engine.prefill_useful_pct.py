"""Engine: prompt tokens a prefill tile was asked for, as a share of the
positions it computed: sum of `tokens` over sum of `tile_rows` x `bucket`
of the stretch's `ray_tpu:engine.prefill_tile` spans. The rest is
padding: rows of the 8-row tile that hold no request, and positions
between a prompt's end and its bucket's."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    tiles = ps.named("engine.prefill_tile") if ps else []
    computed = sum(t.stats.get("tile_rows", 0) * t.stats.get("bucket", 0)
                   for t in tiles)
    if not computed:
        return None
    return 100.0 * sum(t.stats.get("tokens", 0) for t in tiles) / computed
