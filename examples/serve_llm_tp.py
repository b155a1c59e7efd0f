"""Serve an LLM across multiple chips (tensor + fsdp parallel replica).

The engine lays weights out by their logical axes (heads/mlp/vocab
ride tp, embed rides fsdp) and shards the KV cache across kv-heads;
the compiled prefill/decode steps then run SPMD over the mesh with XLA
collectives over ICI. This is how an 8B-class model that cannot fit
one 16 GiB chip serves (tp=4/fsdp=2 over 8 chips); the demo runs the
same code path with a tiny model on a virtual 4-device CPU mesh and
checks the sharded engine's greedy tokens equal the single-chip
engine's.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=4 \
     JAX_PLATFORMS=cpu python examples/serve_llm_tp.py
"""

import os

# Hard-set (not setdefault): this demo runs a tiny random-weight model
# on a virtual CPU mesh — it must not grab a real TPU chip.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# Robust 4-device provisioning (handles a pre-set smaller XLA_FLAGS
# count and an already-initialized backend alike).
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from __graft_entry__ import _provision_virtual_devices  # noqa: E402

if len(jax.devices()) < 4:
    _provision_virtual_devices(4)
import numpy as np  # noqa: E402

from ray_tpu.models import configs  # noqa: E402
from ray_tpu.models.transformer import init_params  # noqa: E402
from ray_tpu.parallel import ParallelPlan, make_mesh  # noqa: E402
from ray_tpu.serve.llm import LLMEngine  # noqa: E402


def run(mesh, params, cfg, prompts):
    eng = LLMEngine(cfg, params, num_slots=4, max_seq_len=128,
                    mesh=mesh)
    reqs = [eng.submit(p, max_new_tokens=16) for p in prompts]
    while eng.step():
        pass
    outs = [r.result(timeout=120) for r in reqs]
    eng._stop = True
    return outs


def main():
    cfg = configs.tiny_test()
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=n).tolist()
               for n in (9, 17, 30, 12)]

    devices = jax.devices()
    print(f"{len(devices)} devices: {[d.platform for d in devices]}")

    single = run(None, params, cfg, prompts)
    plan = ParallelPlan(tp=2, fsdp=2)
    mesh = make_mesh(plan, devices=devices[:4])
    sharded = run(mesh, params, cfg, prompts)
    assert sharded == single, "sharded tokens diverged!"
    print(f"tp=2/fsdp=2 over {plan.num_devices} devices reproduces "
          f"single-chip tokens exactly:")
    for p, o in zip(prompts, sharded):
        print(f"  prompt[{len(p):2d} tok] -> {o[:8]}...")
    # The real 8B shape is the same call:
    #   LLMServer(configs.llama3_8b(), plan=ParallelPlan(tp=4, fsdp=2))


if __name__ == "__main__":
    main()
