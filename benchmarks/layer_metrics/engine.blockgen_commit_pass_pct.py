"""Engine: commit passes (the pass over a block's final tokens that
makes its cache rows final) as a share of all the passes the owned slots
ran: sum of `commit_passes` over `commit_passes` + `denoise_passes` of the
stretch's `ray_tpu:engine.process_block` spans. What a commit fused into
the next block's first denoising pass would take out. Nothing from a
program whose spans do not carry the counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.process_block") if ps else {}
    passes = sums.get("commit_passes", 0) + sums.get("denoise_passes", 0)
    if not passes:
        return None
    return 100.0 * sums.get("commit_passes", 0) / passes
