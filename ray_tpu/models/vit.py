"""Vision Transformer + CLIP dual-tower, TPU-first.

BASELINE config 4 (ViT-L / CLIP — image pipeline streaming into TPU
HBM). Same design stance as models/transformer.py (the reference trains
vision models only through integrated torch frameworks; this is new
TPU-native code): functional params + logical-axis metadata, lax.scan
over stacked layers, flash attention (non-causal), bf16 activations.

Patch embedding is a reshape + matmul — the XLA-friendly formulation of
the non-overlapping conv (keeps the FLOPs on the MXU, no conv window
lowering).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..parallel.sharding import with_sharding_constraint as wsc
from .transformer import TransformerConfig, rms_norm


@dataclass(frozen=True)
class ViTConfig:
    image_size: int = 224
    patch_size: int = 16
    channels: int = 3
    d_model: int = 1024
    n_layers: int = 24
    n_heads: int = 16
    d_ff: int = 4096
    n_classes: int = 1000          # 0 = no classifier head (feature tower)
    proj_dim: int = 0              # >0 = CLIP projection head
    pool: str = "mean"             # "mean" | "cls"
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = True

    @property
    def num_patches(self) -> int:
        n = (self.image_size // self.patch_size) ** 2
        return n + (1 if self.pool == "cls" else 0)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def patch_dim(self) -> int:
        return self.patch_size * self.patch_size * self.channels


def vit_l_16(n_classes: int = 1000) -> ViTConfig:
    """ViT-L/16 (BASELINE config 4 shapes)."""
    return ViTConfig(d_model=1024, n_layers=24, n_heads=16, d_ff=4096,
                     patch_size=16, n_classes=n_classes)


def vit_tiny_test() -> ViTConfig:
    return ViTConfig(image_size=32, patch_size=8, d_model=64, n_layers=2,
                     n_heads=4, d_ff=128, n_classes=10, dtype=jnp.float32,
                     param_dtype=jnp.float32, remat=False)


def param_logical_axes(cfg: ViTConfig) -> Dict[str, Any]:
    axes: Dict[str, Any] = {
        "patch_embed": ("patch", "embed"),
        "pos_embed": (None, "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "heads"),
            "wv": ("layers", "embed", "heads"),
            "wo": ("layers", "heads", "embed"),
            "ffn_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
    }
    if cfg.pool == "cls":
        axes["cls_token"] = (None, "embed")
    if cfg.n_classes > 0:
        axes["head"] = ("embed", None)
    if cfg.proj_dim > 0:
        axes["proj"] = ("embed", None)
    return axes


def init_params(cfg: ViTConfig, key: jax.Array) -> Dict[str, Any]:
    pd = cfg.param_dtype
    keys = jax.random.split(key, 12)

    def normal(k, shape, scale=0.02):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(pd)

    d = cfg.d_model
    L = cfg.n_layers
    layers = {
        "attn_norm": jnp.ones((L, d), pd),
        "wq": normal(keys[0], (L, d, d)),
        "wk": normal(keys[1], (L, d, d)),
        "wv": normal(keys[2], (L, d, d)),
        "wo": normal(keys[3], (L, d, d), 0.02 / math.sqrt(2 * L)),
        "ffn_norm": jnp.ones((L, d), pd),
        "w_gate": normal(keys[4], (L, d, cfg.d_ff)),
        "w_up": normal(keys[5], (L, d, cfg.d_ff)),
        "w_down": normal(keys[6], (L, cfg.d_ff, d), 0.02 / math.sqrt(2 * L)),
    }
    params: Dict[str, Any] = {
        "patch_embed": normal(keys[7], (cfg.patch_dim, d)),
        "pos_embed": normal(keys[8], (cfg.num_patches, d)),
        "layers": layers,
        "final_norm": jnp.ones((d,), pd),
    }
    if cfg.pool == "cls":
        params["cls_token"] = normal(keys[9], (1, d))
    if cfg.n_classes > 0:
        params["head"] = normal(keys[10], (d, cfg.n_classes))
    if cfg.proj_dim > 0:
        params["proj"] = normal(keys[11], (d, cfg.proj_dim))
    return params


def patchify(cfg: ViTConfig, images: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, N, p*p*C); pure reshape/transpose."""
    B, H, W, C = images.shape
    p = cfg.patch_size
    x = images.reshape(B, H // p, p, W // p, p, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)            # (B, Hp, Wp, p, p, C)
    return x.reshape(B, (H // p) * (W // p), p * p * C)


def _encoder_layer(cfg: ViTConfig, carry, lp):
    from ..ops import flash_attention

    x = carry
    B, N, D = x.shape
    H, Dh = cfg.n_heads, cfg.head_dim
    h = rms_norm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"].astype(h.dtype)).reshape(B, N, H, Dh)
    k = (h @ lp["wk"].astype(h.dtype)).reshape(B, N, H, Dh)
    v = (h @ lp["wv"].astype(h.dtype)).reshape(B, N, H, Dh)
    q = wsc(q, ("batch", "seq", "act_heads", None))
    k = wsc(k, ("batch", "seq", "act_heads", None))
    v = wsc(v, ("batch", "seq", "act_heads", None))
    a = flash_attention(q, k, v, causal=False)
    x = x + (a.reshape(B, N, H * Dh) @ lp["wo"].astype(x.dtype))

    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    f = jax.nn.silu(h @ lp["w_gate"].astype(h.dtype)) \
        * (h @ lp["w_up"].astype(h.dtype))
    f = wsc(f, ("batch", "seq", "act_mlp"))
    x = x + (f @ lp["w_down"].astype(x.dtype))
    x = wsc(x, ("batch", "seq", "act_embed"))
    return x, None


def encode(cfg: ViTConfig, params: Dict[str, Any], images: jax.Array
           ) -> jax.Array:
    """(B, H, W, C) images -> (B, D) pooled features."""
    x = patchify(cfg, images).astype(cfg.dtype)
    x = x @ params["patch_embed"].astype(cfg.dtype)
    if cfg.pool == "cls":
        cls = jnp.broadcast_to(
            params["cls_token"].astype(cfg.dtype)[None],
            (x.shape[0], 1, cfg.d_model))
        x = jnp.concatenate([cls, x], axis=1)
    x = x + params["pos_embed"].astype(cfg.dtype)[None]
    x = wsc(x, ("batch", "seq", "act_embed"))

    layer = partial(_encoder_layer, cfg)
    if cfg.remat:
        layer = jax.checkpoint(layer)
    x, _ = lax.scan(layer, x, params["layers"])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.pool == "cls":
        feat = x[:, 0]
    else:
        feat = jnp.mean(x, axis=1)
    return wsc(feat, ("batch", "act_embed"))


def classify(cfg: ViTConfig, params: Dict[str, Any], images: jax.Array
             ) -> jax.Array:
    """(B, H, W, C) -> (B, n_classes) float32 logits."""
    feat = encode(cfg, params, images)
    return (feat @ params["head"].astype(cfg.dtype)).astype(jnp.float32)


def classification_loss(cfg: ViTConfig, params, images, labels
                        ) -> Tuple[jax.Array, Dict]:
    logits = classify(cfg, params, images)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    loss = jnp.mean(logz - gold)
    acc = jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
    return loss, {"loss": loss, "accuracy": acc}


# ---------------------------------------------------------------------------
# CLIP: dual tower + contrastive loss
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class CLIPConfig:
    vision: ViTConfig
    text: TransformerConfig
    proj_dim: int = 512

    @staticmethod
    def tiny_test() -> "CLIPConfig":
        from .configs import tiny_test

        vision = ViTConfig(
            image_size=32, patch_size=8, d_model=64, n_layers=2, n_heads=4,
            d_ff=128, n_classes=0, proj_dim=32, dtype=jnp.float32,
            param_dtype=jnp.float32, remat=False)
        return CLIPConfig(vision=vision, text=tiny_test(), proj_dim=32)


def clip_init_params(cfg: CLIPConfig, key: jax.Array) -> Dict[str, Any]:
    from . import transformer

    kv, kt, kp = jax.random.split(key, 3)
    vis_cfg = cfg.vision
    if vis_cfg.proj_dim != cfg.proj_dim:
        from dataclasses import replace
        vis_cfg = replace(vis_cfg, proj_dim=cfg.proj_dim, n_classes=0)
    params = {
        "vision": init_params(vis_cfg, kv),
        "text": transformer.init_params(cfg.text, kt),
        "text_proj": (jax.random.normal(
            kp, (cfg.text.d_model, cfg.proj_dim), jnp.float32) * 0.02
        ).astype(cfg.text.param_dtype),
        "logit_scale": jnp.asarray(math.log(1 / 0.07), jnp.float32),
    }
    return params


def clip_encode_image(cfg: CLIPConfig, params, images) -> jax.Array:
    from dataclasses import replace

    vis_cfg = replace(cfg.vision, proj_dim=cfg.proj_dim, n_classes=0)
    feat = encode(vis_cfg, params["vision"], images)
    emb = feat @ params["vision"]["proj"].astype(feat.dtype)
    return emb / (jnp.linalg.norm(emb.astype(jnp.float32), axis=-1,
                                  keepdims=True) + 1e-8).astype(emb.dtype)


def clip_encode_text(cfg: CLIPConfig, params, tokens,
                     lengths: Optional[jax.Array] = None) -> jax.Array:
    """Causal text tower; feature = last real token's hidden state."""
    from . import transformer as tr

    x = params["text"]["embed"].astype(cfg.text.dtype)[tokens]
    x = wsc(x, ("batch", "seq", "act_embed"))
    B, S = tokens.shape
    sin, cos = tr.rope_tables(cfg.text, S)
    layer = partial(tr._layer, cfg.text)
    if cfg.text.remat:
        layer = jax.checkpoint(layer)
    (x, _, _), _ = lax.scan(layer, (x, sin, cos), params["text"]["layers"])
    x = tr.rms_norm(x, params["text"]["final_norm"], cfg.text.norm_eps)
    if lengths is None:
        feat = x[:, -1]
    else:
        feat = jnp.take_along_axis(
            x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    emb = feat @ params["text_proj"].astype(feat.dtype)
    return emb / (jnp.linalg.norm(emb.astype(jnp.float32), axis=-1,
                                  keepdims=True) + 1e-8).astype(emb.dtype)


def clip_loss(cfg: CLIPConfig, params, images, tokens,
              lengths: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """Symmetric InfoNCE over the global batch. Under a dp/fsdp-sharded
    mesh the (B, B) similarity matmul makes XLA all-gather the embeddings
    — exactly the global-batch contrastive semantics."""
    img = clip_encode_image(cfg, params, images).astype(jnp.float32)
    txt = clip_encode_text(cfg, params, tokens, lengths).astype(jnp.float32)
    scale = jnp.exp(jnp.clip(params["logit_scale"], -10.0, math.log(100.0)))
    logits = scale * (img @ txt.T)                    # (B, B)
    labels = jnp.arange(logits.shape[0])
    logz_i = jax.nn.logsumexp(logits, axis=1)
    logz_t = jax.nn.logsumexp(logits, axis=0)
    diag = jnp.diagonal(logits)
    loss = jnp.mean(logz_i - diag) / 2 + jnp.mean(logz_t - diag) / 2
    acc = jnp.mean((jnp.argmax(logits, axis=1) == labels
                    ).astype(jnp.float32))
    return loss, {"loss": loss, "clip_acc": acc,
                  "logit_scale": params["logit_scale"]}
