"""Engine: the latent rows the decode steps are asked to read, as a share
of the rows the owned slots hold: sum of `sparse_rows_read` over sum of
`cache_rows_held` of the stretch's `ray_tpu:engine.dispatch_block` spans.
A stack whose indexer chooses `index_topk` rows a slot a step
(`models/latent.py`) is to read min(rows held, index_topk) of a slot's
rows: 2,048 of 9,000-30,000 is 7-23%; 100% is every slot still under
`index_topk` rows. Both counts are the host's arithmetic where it
dispatches a block, from each slot's position and the configuration's
`index_topk` (`stats()["counts"]` holds the same sums): rows asked for,
not rows observed. The number says what the traffic leaves the mechanism
to save; that the program reads no other row is what the tests hold
(`tests/test_glm_dsa.py`: rows outside the chosen sets poisoned) and what
the device time under `attn_sparse` would show. Nothing from a program
whose spans do not carry the counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    if not sums.get("cache_rows_held") or "sparse_rows_read" not in sums:
        return None
    return 100.0 * sums["sparse_rows_read"] / sums["cache_rows_held"]
