"""Engine: CPU time of the engine's thread per decode step: the `cpu_us`
of the `engine.tick` spans that begin in the traced stretch over the `k`
of the `engine.dispatch_block` spans inside them (`lib/reqpath.py`), ms.
CPU time, not length: a call that stands blocked behind the block in
flight, and a wait for the interpreter lock, are not in it."""

from lib import reqpath


def read(metric, m):
    rp = reqpath.for_run(m)
    return rp.host_cpu_ms_step() if rp else None
