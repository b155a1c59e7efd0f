"""Engine: what the thread asks of the device a launch. The
`engine.device_call` spans that begin in the traced stretch (one a call:
the key's split, a tile's transfers, the program, each eager scatter,
slice, pad, stack and concatenation, the start of each host copy; the
`to_host` reads left out, they are the fetch) over the `engine.launch`
spans that begin there (`lib/turn.py`). Host spans only. One jitted
admission and one fixed-shape first-token buffer take it towards 1."""

from lib import turn


def read(metric, m):
    tn = turn.for_run(m)
    return tn.device_calls_per_launch() if tn else None
