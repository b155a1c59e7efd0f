"""Model: device time of the decode programs in the traced stretch, per
decode step the device ran there. Both from the device's own module
events: a `jit_decode_k<k>` launch is k steps, a launch cut by an edge
of the stretch counts in proportion. Nothing where the programs do not
carry their block size."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return ps.decode_ms_step() if ps else None
