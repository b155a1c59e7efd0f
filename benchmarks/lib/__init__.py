"""The benchmark's own library: the yardstick later PRs may not change.

Traffic generation, metric arithmetic, the table of peaks, the trace
reduction and the plain reference live here, not in `ray_tpu/`.
"""
