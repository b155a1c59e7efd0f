"""Named model configs matching BASELINE.json's target families."""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from .transformer import TransformerConfig


def tiny_test(vocab: int = 256) -> TransformerConfig:
    """Milliseconds-scale config for unit tests (CPU mesh)."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False)


def tiny_moe_test(vocab: int = 256) -> TransformerConfig:
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False,
        moe_experts=4, moe_top_k=2)


def tiny_afmoe_test(vocab: int = 256) -> TransformerConfig:
    """The period stack of models/periodic.py at a unit-test size: one
    dense layer, then one period of three window layers and a global
    one, sigmoid-routed experts beside a shared one. For the tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=5, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, tie_embeddings=False,
        arch="afmoe", n_dense_layers=1, global_attn_every=4,
        sliding_window=8, moe_experts=8, moe_top_k=2, moe_d_ff=32,
        moe_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.826)


def tiny_mellum_test(vocab: int = 256) -> TransformerConfig:
    """The period stack's other layer (`transformer.PERIOD_FORMS`) at a
    unit-test size: two periods of three window layers and a global one,
    every layer routed by a softmax over 8 experts, no shared expert, a
    rotary table a kind of layer (YaRN on the global ones). For the
    tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=32, d_ff=128, max_seq_len=128, norm_eps=1e-6,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        tie_embeddings=False, arch="mellum", global_attn_every=4,
        sliding_window=8, moe_experts=8, moe_top_k=2, moe_d_ff=32,
        score_func="softmax", route_norm=True,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 32, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}})


def tiny_sdar_test(vocab: int = 256, **changes) -> TransformerConfig:
    """The period stack's layer that generates by diffusion over blocks
    (`transformer.PERIOD_FORMS`: "sdar_moe") at a unit-test size: two
    full-attention layers, each routed by a softmax over 8 experts,
    blocks of four positions, the mask token the vocabulary's last. For
    the tests only."""
    kw = dict(
        vocab_size=vocab, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=128, norm_eps=1e-6,
        rope_theta=1e6, dtype=jnp.float32, param_dtype=jnp.float32,
        remat=False, tie_embeddings=False, arch="sdar_moe",
        global_attn_every=1, sliding_window=0, moe_experts=8, moe_top_k=2,
        moe_d_ff=64, score_func="softmax", route_norm=True, block_length=4,
        mask_token_id=vocab - 1, denoise_steps=4,
        remask="low_confidence_dynamic", confidence_threshold=0.9)
    kw.update(changes)
    return TransformerConfig(**kw)


def tiny_pangu_test(vocab: int = 256, router_experts: int = 16,
                    held: int = 4, first: int = 4) -> TransformerConfig:
    """The latent-attention stack of models/latent.py at a unit-test
    size: one dense layer, then two layers that hold experts [first,
    first + held) of the `router_experts` their sigmoid router scores,
    beside a shared one; latent attention with ranks of 32 and heads of
    16 + 8 (no position, rotary) over values of 16. For the tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rope_theta=25600000.0, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, tie_embeddings=False,
        arch="pangu_ultra_moe", n_dense_layers=1, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, moe_experts=held, moe_router_experts=router_experts,
        moe_first_expert=first, moe_top_k=2, moe_d_ff=32,
        moe_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.5)


def tiny_glm_test(vocab: int = 256, router_experts: int = 16,
                  held: int = 4, first: int = 4, index_topk: int = 8
                  ) -> TransformerConfig:
    """The latent stack's other layer at a unit-test size: `tiny_pangu_test`'s
    widths with two norms a layer, the router's selection bias, and the
    sparse-attention indexer (2 heads of 16, the first 8 rotated) choosing
    `index_topk` rows a query. For the tests only."""
    return dataclasses.replace(
        tiny_pangu_test(vocab, router_experts, held, first),
        arch="glm_moe_dsa", rope_theta=1000000.0, index_n_heads=2,
        index_head_dim=16, index_topk=index_topk)


def tiny_solar_test(vocab: int = 256, router_experts: int = 16,
                    held: int = 4, first: int = 4) -> TransformerConfig:
    """The period stack with linear-attention layers at a unit-test
    size: two periods of a global layer with no position and an output
    gate, then three gated delta-rule layers (2 heads of 16, a
    convolution over 4 positions); every layer routed, `held` of
    `router_experts` experts held, sigmoid scores with a selection bias,
    a shared expert. For the tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=8, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, tie_embeddings=False,
        arch="solar_open2", global_attn_every=4, moe_experts=held,
        moe_router_experts=router_experts, moe_first_expert=first,
        moe_top_k=2, moe_d_ff=32, moe_shared_experts=1,
        score_func="sigmoid", route_norm=True, route_scale=1.0,
        linear_n_heads=2, linear_head_dim=16, linear_conv_kernel=4)


def tiny_jamba_test(vocab: int = 256, offset: int = 2) -> TransformerConfig:
    """The period stack with state-space layers at a unit-test size: two
    periods of four layers, the one attention layer (4 heads over one KV
    head of 16, no position) at place `offset`, Mamba layers around it
    (128 channels of 16 coordinates, the step through rank 8, a
    convolution over 4 positions), every FFN a dense SwiGLU, the head
    tied. For the tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=8, n_heads=4, n_kv_heads=1,
        head_dim=16, d_ff=128, max_seq_len=128, dtype=jnp.float32,
        param_dtype=jnp.float32, remat=False, tie_embeddings=True,
        arch="jamba", global_attn_every=4, attn_layer_offset=offset,
        mamba_d_state=16, mamba_expand=2, mamba_dt_rank=8, mamba_d_conv=4)


def tiny_ouro_test(vocab: int = 256, ut_steps: int = 3,
                   threshold: float = 1.0) -> TransformerConfig:
    """The period stack looped at a unit-test size: three sandwich-normed
    layers (4 heads over 4 KV heads of 16, rotary, a dense SwiGLU) walked
    `ut_steps` times a token, nine cache slabs, an exit gate a pass, the
    head untied. For the tests only."""
    return TransformerConfig(
        vocab_size=vocab, d_model=64, n_layers=3, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128, max_seq_len=128, rope_theta=1e6,
        norm_eps=1e-6, dtype=jnp.float32, param_dtype=jnp.float32,
        remat=False, tie_embeddings=False, arch="ouro", global_attn_every=1,
        ut_steps=ut_steps, early_exit_threshold=threshold)


def tiny_kimi_test(vocab: int = 256, router_experts: int = 16,
                   held: int = 4, first: int = 4, periods: int = 2,
                   **changes) -> TransformerConfig:
    """The period stack with linear-attention layers around a
    latent-attention layer at a unit-test size, planned from lists as
    the published ones run (L L L G ..., a last period cut short): a
    leading delta-rule layer with a dense SwiGLU, `periods` periods
    L L G L and a tail L G (eleven layers); 2 delta-rule heads of 16 (the step
    not doubled), 4 latent heads (16 + 8 key values, 16 value values)
    over rows of 32 + 8 with no query rank and no rotation; `held` of
    `router_experts` experts held, sigmoid scores with a selection bias,
    a shared expert. For the tests only."""
    n = 1 + 4 * periods + 2
    full = [4 * (p + 1) for p in range(periods)] + [n]
    return TransformerConfig(**{**dict(
        vocab_size=vocab, d_model=64, n_layers=n, n_dense_layers=1,
        n_heads=4, n_kv_heads=4, head_dim=16, d_ff=128, max_seq_len=128,
        dtype=jnp.float32, param_dtype=jnp.float32, remat=False,
        tie_embeddings=False, arch="kimi_linear", global_attn_every=4,
        moe_experts=held, moe_router_experts=router_experts,
        moe_first_expert=first, moe_top_k=2, moe_d_ff=32,
        moe_shared_experts=1, score_func="sigmoid", route_norm=True,
        route_scale=2.446, linear_n_heads=2, linear_head_dim=16,
        linear_conv_kernel=4, q_lora_rank=None, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        linear_attn_config={
            "full_attn_layers": full, "head_dim": 16, "num_heads": 2,
            "kda_layers": [i for i in range(1, n + 1) if i not in full],
            "short_conv_kernel_size": 4}), **changes})


def gpt2_125m() -> TransformerConfig:
    """BASELINE config 1 (GPT-2 125M equivalent param count; rotary in
    place of learned positions — TPU-first choice, same capability)."""
    return TransformerConfig(
        vocab_size=50304,  # padded to 128 multiple for MXU tiling
        d_model=768, n_layers=12, n_heads=12, n_kv_heads=12, d_ff=3072,
        max_seq_len=1024, tie_embeddings=True)


def llama_654m() -> TransformerConfig:
    """Llama-family 654M: the largest-measured-on-one-chip point from
    round 2 (PARITY.md), now a named config. GQA 12/4, SwiGLU, untied
    head; f32 master weights fit alongside Adam state on a 16 GiB chip
    with full remat."""
    return TransformerConfig(
        vocab_size=32768, d_model=1536, n_layers=16, n_heads=12,
        n_kv_heads=4, d_ff=6144, max_seq_len=1024,
        tie_embeddings=False, remat=True, remat_policy=None)


def llama_1b4() -> TransformerConfig:
    """Llama-family ~1.46B — the largest config that trains on one
    16 GiB chip (VERDICT r2 next-round #1: a ≥1B measured point).
    Recipe: bf16 params + bf16 Adam moments (6 bytes/param state ≈
    8.8 GiB), full per-layer remat, chunked cross-entropy so the
    (B,S,32k) logits tensor is never materialized."""
    return TransformerConfig(
        vocab_size=32768, d_model=2048, n_layers=28, n_heads=16,
        n_kv_heads=8, d_ff=5632, max_seq_len=1024,
        tie_embeddings=False, remat=True, remat_policy=None,
        param_dtype=jnp.bfloat16, ce_chunk=512)


def llama3_8b() -> TransformerConfig:
    """BASELINE config 2 (Llama-3-8B shapes)."""
    return TransformerConfig(
        vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=500000.0,
        tie_embeddings=False)


def mixtral_8x7b() -> TransformerConfig:
    """BASELINE config 3 (Mixtral 8×7B shapes, top-2 MoE)."""
    return TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=8192, rope_theta=1e6,
        tie_embeddings=False, moe_experts=8, moe_top_k=2)


NAMED = {
    "tiny": tiny_test,
    "tiny_moe": tiny_moe_test,
    "tiny_afmoe": tiny_afmoe_test,
    "tiny_mellum": tiny_mellum_test,
    "tiny_pangu": tiny_pangu_test,
    "tiny_glm": tiny_glm_test,
    "tiny_solar": tiny_solar_test,
    "tiny_jamba": tiny_jamba_test,
    "tiny_ouro": tiny_ouro_test,
    "tiny_kimi": tiny_kimi_test,
    "gpt2-125m": gpt2_125m,
    "llama-654m": llama_654m,
    "llama-1b4": llama_1b4,
    "llama3-8b": llama3_8b,
    "mixtral-8x7b": mixtral_8x7b,
}


def get(name: str) -> TransformerConfig:
    if name not in NAMED:
        raise ValueError(f"Unknown config {name!r}; have {sorted(NAMED)}")
    return NAMED[name]()
