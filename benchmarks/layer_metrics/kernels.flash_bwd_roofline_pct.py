"""Kernels: the backward flash kernels' share of their roofline in
training (`lib/progspans.flash_roofline_pct`): `flash_dq` and
`flash_dkv` together."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    return progspans.flash_roofline_pct(m, ps, backward=True) if ps else None
