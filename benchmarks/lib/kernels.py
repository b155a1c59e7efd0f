"""Names by which the reduction finds the program's kernels in a trace."""

# The pallas flash-attention kernels of ray_tpu/ops/flash_attention.py.
# Their `pallas_call`s give no `name`, so on the chip their events are
# called `closed_call.<n>` on one device (one per layer in a prefill at kv
# 4096, none in a program below the kv crossover: the chat cell's trace
# holds none) and `shard_map.<n>` under a mesh (four in the fsdp=4 train
# step, which is also its count of `tpu_custom_call`s: forward, its remat
# recomputation, dq, dkv) (my chip runs, PR 24). Forward, dq and dkv
# cannot be told apart, and any other operation the program wrapped in a
# `closed_call` or `shard_map` would be counted with them: none of the
# four cells' programs has one. A name of their own is on PERF.md's list
# for the `tracing` issue, which then adds its pattern here.
FLASH_EVENTS = r"^closed_call|^shard_map"
