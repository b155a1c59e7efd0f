"""Engine: tokens handed to a caller a pass a slot, where the model
generates a block of positions a pass (diffusion over blocks): sum of
`emitted` over sum of `k` x `active` of the stretch's
`ray_tpu:engine.process_block` spans that carry `block_length`. What an
acceptance rate is to speculation: `block_length` / (denoising passes a
block + its commit pass) while every slot generates, less what is cut off
a request's end. Nothing from a program whose spans do not carry the
counter (one token a step)."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    blocks = [b for b in (ps.named("engine.process_block") if ps else [])
              if b.stats.get("block_length")]
    passes = sum(b.stats.get("k", 0) * b.stats.get("active", 0)
                 for b in blocks)
    if not passes:
        return None
    return sum(b.stats.get("emitted", 0) for b in blocks) / passes
