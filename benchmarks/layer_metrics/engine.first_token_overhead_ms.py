"""Engine: what the host adds around a request's prefill tile: from the
start of the tile's `engine.launch` to the end of the `engine.emit` that
hands the request its first token, less the length of that launch's
module event on device 0 (the transfers, the call, the launch's wait
behind work in flight, the copy back, the thread's wake, the emit).
Median over the stretch's requests (`lib/reqpath.py`), ms. Both ends are
the engine thread's spans and the module event gives only its length, so
the distance between the profile's host and device timelines is not in
it."""

from lib import reqpath


def read(metric, m):
    rp = reqpath.for_run(m)
    return rp.median("first_token_overhead") if rp else None
