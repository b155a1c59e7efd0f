"""Engine: the share of device 0's idle time in the traced stretch that
falls inside a `ray_tpu:engine.*` span other than `engine.tick` itself:
how much of the idle time the engine's own spans explain. The idle time
it is a share of is on the `program_spans` output line (`idle_s`) and
in `.bench_out/<cell>/program_spans.json`."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return ps.idle_named_pct() if ps else None
