"""Kernels: the forward flash kernel's share of its roofline in
training (`lib/progspans.flash_roofline_pct`): the events whose
`kernel_metadata` reads `flash_fwd`, remat recomputation included."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return progspans.flash_roofline_pct(m, ps, backward=False) if ps else None
