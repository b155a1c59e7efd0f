"""Engine: CPU time of admitting a tile: the `cpu_us` of the
`engine.admit` spans inside the stretch's ticks (taking the requests,
routing them, building and launching their tiles) over the
`engine.prefill_tile` spans inside them, ms a tile (`lib/reqpath.py`)."""

from lib import reqpath


def read(metric, m):
    rp = reqpath.for_run(m)
    return rp.admit_host_ms_tile() if rp else None
