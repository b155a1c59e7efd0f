"""A configuration file -> the program's `TransformerConfig`, and its
weights made on the device from `--seed` in one jitted call, directly in
the dtype they are used in."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict


def transformer_config(config: Dict[str, Any], sizes: Dict[str, Any]):
    """Architecture from the configuration file; run-time choices (dtypes,
    remat, chunked loss, cache length) from the cell's sizes."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import TransformerConfig

    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    kw = {k: v for k, v in config.items() if k in fields}
    kw.update({k: v for k, v in sizes.get("model", {}).items()
               if k in fields})
    for k in ("dtype", "param_dtype"):
        if k in kw:
            kw[k] = jnp.dtype(kw[k]).type
    return TransformerConfig(**kw)


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    import jax

    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def make_params(cfg, seed: int):
    """Weights on the default device, in `cfg.param_dtype`."""
    import jax
    from functools import partial

    from ray_tpu.models.transformer import init_params

    return jax.jit(partial(init_params, cfg))(seed_key(seed))
