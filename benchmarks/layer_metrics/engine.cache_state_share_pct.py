"""Engine: which of its two kinds of cache a decode step's owned bytes
are, where a stack keeps recurrent states beside rows that grow with the
tokens held: sum of `cache_state_bytes_live` (the owned slots' states and
convolution tails, every layer that keeps them, a step) over that plus
`cache_row_bytes_held` (the rows the owned slots' held tokens come to, at
the values a row keeps and not the lanes it is padded to), of the
stretch's `ray_tpu:engine.dispatch_block` spans. A state costs the same
at any length and the rows grow with it, so the share falls as contexts
grow: what a step pays for state against what it pays for context. Both
counts are the host's arithmetic where it dispatches a block
(`stats()["counts"]` holds the same sums). Nothing from a program whose
spans do not carry the counters."""

from lib import progspans


def read(metric, m):
    ps = progspans.for_run(m)
    sums = ps.attribute_sums("engine.dispatch_block") if ps else {}
    state = sums.get("cache_state_bytes_live")
    if not state:
        return None
    return 100.0 * state / (state + sums.get("cache_row_bytes_held", 0))
