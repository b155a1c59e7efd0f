"""Engine: what the host adds to a tick. Time of the engine's thread
inside `ray_tpu:engine.tick` spans of the traced stretch, less the
`engine.fetch` and `engine.idle_wait` spans inside them (there it only
waits for the device or for work), per tick, ms."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return ps.tick_host_ms() if ps else None
