"""Engine: from a request's `engine.submit` (the caller's thread) to the
start of the `engine.launch` of its prefill tile: the engine thread's wake
from `engine.idle_wait`, `_admit`, the tile's build. Median over the
requests submitted in the traced stretch whose chain of spans is whole
(`lib/reqpath.py`), ms."""

from lib import reqpath


def read(metric, m):
    rp = reqpath.for_run(m)
    return rp.median("submit_to_launch") if rp else None
