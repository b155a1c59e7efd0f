"""Engine: idle time of device 0 from the end of the last decode block
before a prefill tile to the start of that tile's module event, median
over the tiles that begin in the traced stretch (`lib/reqpath.py`), ms:
what a lone caller's turn from one request to its next leaves the device
waiting (the last tokens' way back, the emit, the caller's poll and
submit, the admission, the tile's build and launch; the `request_path`
line gives it by program span). Device events only."""

from lib import reqpath


def read(metric, m):
    rp = reqpath.for_run(m)
    return rp.request_gap_idle() if rp else None
