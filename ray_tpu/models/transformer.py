"""Decoder-only transformer, TPU-first.

Pure-JAX (functional params pytree + logical-axis metadata) rather than a
port of any torch module structure. Design choices for the MXU/HBM:
- bfloat16 activations, float32 params/optimizer (master weights)
- lax.scan over stacked layer params: one compiled layer body, fast
  compiles, layer-count-independent HLO
- jax.checkpoint per layer (rematerialize activations; HBM for FLOPs)
- every major activation carries a logical-axis sharding constraint so a
  ParallelPlan (dp/fsdp/tp/sp) reshards it without model changes
- GQA + rotary + RMSNorm + SwiGLU (Llama-family architecture, covers
  BASELINE configs GPT-2-125M* and Llama-3-8B; *GPT-2 is run with
  learned-position-free rotary variant at equal param count)

Capability reference: the reference trains such models only through
integrated torch frameworks (SURVEY.md §2.3 Train row); the model itself
is new TPU-native code.
"""

from __future__ import annotations

import importlib
import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..parallel.sharding import logical_to_mesh_axes
from ..parallel.sharding import with_sharding_constraint as wsc


# `TransformerConfig.arch` -> the module of `models/` that holds that
# architecture's stack. The one place an architecture is named: to add
# one, write its module and add its line. A stack module offers
#   weights  init_params(cfg, key), num_params(cfg)
#   cache    init_cache(cfg, num_slots, max_seq_len) -> stackparts.KVCache:
#              what it holds is the stack's own count. Cache slabs and
#              weight layers are counted apart: a stack keeps a slab for
#              each layer that keeps rows (`periodic.cache_layers`), and
#              one that walks its layers `cfg.ut_steps` times keeps one
#              for each (pass, layer). Nothing above the stack sizes a
#              cache from `cfg.n_layers`
#   walks    each returns `stackparts.Extras` last, whatever the
#            configuration: `routing`, the routed layers' stats (4,), or
#            (5,) where a layer holds a share of its experts (the pairs
#            routed over the router's whole width behind), None with no
#            routed layer; `exits`, each row's exit pass, None unless the
#            stack walks its layers `cfg.ut_steps` > 1 times
#            prefill(cfg, params, cache, tokens (W, S), lengths, slots)
#              -> (cache', final-normed hidden states (W, S, D), Extras:
#                  exits (W, S))
#            forward_free(cfg, params, tokens (W, S))
#              -> (final-normed hidden states, experts chosen or None,
#                  Extras: no routing, exits (W, S))
#            decode(cfg, params, cache, tokens (B,), live (B,) bool or None)
#              -> (cache', logits (B, V), Extras: exits (B,)).
#                  A slot that is not live reads and writes no cache row
#                  and its token meets no expert (`moe.routed_ffn`)
#            decode_block(cfg, params, cache, tokens (B, Bd), p0 (B,), live)
#              -> (cache', logits (B, Bd, V), Extras): a stack that
#              serves `cfg.block_length` > 0 (generation by diffusion over
#              blocks) offers this walk beside `decode`: the block's Bd
#              keys and values go to rows [p0, p0 + Bd) of each slot and
#              every query of the block sees rows [0, p0 + Bd). The rows
#              are final only once the caller advances `seq_lens` past
#              them (a commit pass over the block's final tokens): until
#              then the block's next pass overwrites them. With such a
#              configuration `prefill` masks block-causally and `decode`
#              raises (one token a step is not how it generates); a
#              stack without the walk refuses the configuration
#              (`TransformerConfig.__post_init__`)
#            last_logits(cfg, params, x (W, S, D), lengths) -> (W, V)
#            routed_layers(cfg): the layers the stats count over
#            routing_stats(cfg): how many entries the stats have, for a
#              stack with routed layers
#            A stack that serves `cfg.ut_steps` > 1 (the same layers
#            walked that many times a token, a learned exit gate a pass:
#            `stackparts.exit_select`) hands back every row's hidden
#            state and logits at that row's exit pass. A stack that does
#            not walk loops refuses the configuration
#            (`TransformerConfig.__post_init__`)
#   counts   counters(cfg), tile_counts, block_counts, result_counts,
#            by_products(cfg): what the engine reports of the stack's own
#            mechanism, reckoned on the host (`stackparts.counters`); the
#            engine names no mechanism (tests/test_stacks.py)
# and, where it has them (`offered`): `suffix` (the walk behind a shared
# prefix), `param_logical_axes` (sharding rules), `forward_train` (the
# walk `forward` and `loss_fn` differentiate). A stack that lacks one
# says why in its `MISSING`.
STACKS: Dict[str, str] = {"llama": "dense", "afmoe": "periodic",
                          "mellum": "periodic", "pangu_ultra_moe": "latent",
                          "sdar_moe": "periodic", "glm_moe_dsa": "latent",
                          "solar_open2": "periodic", "jamba": "periodic",
                          "ouro": "periodic", "kimi_linear": "periodic"}

# How a block of `TransformerConfig.block_length` positions is unmasked
# (models/generate.py, `_unmask`): the names a request or a configuration
# gives `remask`, in the order of the code the programs carry a slot.
REMASK_RULES = ("low_confidence_static", "low_confidence_dynamic",
                "sequential")


@dataclass(frozen=True)
class PeriodForm:
    """What a layer of the period stack (models/periodic.py) has: the
    stack reads this and never asks for an architecture by name.
    `rotary`: the kinds of layer ("window", "global") whose q and k get
    the rotary embedding, each with the table of its own section of
    `TransformerConfig.rope_parameters` (`rope_tables`). A period is one
    global layer and `global_attn_every` - 1 others: window layers that
    close with the global one, or, `linear`, gated delta-rule layers
    (`TransformerConfig.linear_n_heads`) that follow it."""

    attn_gate: bool       # attention output x sigmoid(h @ wg) before wo
    post_norms: bool      # RMS norms on the attention and FFN outputs
    embed_scale: bool     # embedding x sqrt(d_model)
    router_bias: bool     # a per-expert bias added for the selection only
    rotary: Tuple[str, ...]
    qk_norm: bool = True          # a learned norm over each head of q and k
    recurrent: str = ""           # a period's other layers: "linear" | "ssm"
    # The global layer's place in its period: 0 opens it, -1 closes it;
    # None: the configuration says (`TransformerConfig.attn_layer_offset`).
    global_at: Optional[int] = -1
    # The global layer is multi-head latent attention: its leaves, its
    # cache rows and its two orders of products are `models/mla.py`'s
    # attention half (the query's low rank where `q_lora_rank` says so;
    # rotated where "global" is in `rotary`), and `attn_gate` / `qk_norm`
    # do not apply to it.
    latent: bool = False
    # A linear layer's step is `2 sigmoid(h wb)`, in [0, 2]: the
    # transition's eigenvalue along k may be negative (the published
    # `kda_allow_neg_eigval`); else `sigmoid(h wb)`.
    neg_eigval: bool = False


# `TransformerConfig.arch` -> its layer, for the architectures of STACKS
# that the period stack serves.
PERIOD_FORMS: Dict[str, PeriodForm] = {
    # Arcee Trinity: four norms a layer, a gated attention output, a
    # scaled embedding, rotary on window layers only.
    "afmoe": PeriodForm(attn_gate=True, post_norms=True, embed_scale=True,
                        router_bias=True, rotary=("window",)),
    # JetBrains Mellum 2: a pre-norm layer of two norms, rotary on both
    # kinds of layer, a table a kind.
    "mellum": PeriodForm(attn_gate=False, post_norms=False,
                         embed_scale=False, router_bias=False,
                         rotary=("window", "global")),
    # JetLM SDAR (MoE): the same pre-norm layer, every layer full
    # attention; what is its own is how it generates (`block_length`).
    "sdar_moe": PeriodForm(attn_gate=False, post_norms=False,
                           embed_scale=False, router_bias=False,
                           rotary=("global",)),
    # Upstage Solar Open 2: a softmax GQA layer with no position and an
    # output gate opens each period, gated delta-rule layers follow it;
    # every layer routed, a selection bias.
    "solar_open2": PeriodForm(attn_gate=True, post_norms=False,
                              embed_scale=False, router_bias=True, rotary=(),
                              qk_norm=False, recurrent="linear", global_at=0,
                              neg_eigval=True),
    # AI21 Jamba: a softmax layer with no position, no gate and no q/k
    # norm at the place of its period the configuration names, Mamba
    # layers around it; the configuration says whether the FFNs are
    # routed (Jamba2-3B: none is).
    "jamba": PeriodForm(attn_gate=False, post_norms=False, embed_scale=False,
                        router_bias=False, rotary=(), qk_norm=False,
                        recurrent="ssm", global_at=None),
    # ByteDance Ouro (a looped language model): a sandwich-normed layer
    # (four norms, the second of each pair on the branch's output), MHA
    # with rotary, no q/k norm, no gate, a dense SwiGLU; what is its own
    # is the walk (`ut_steps` passes over the same layers, the final norm
    # after each, an exit gate a pass). Served as published: every pass
    # runs and keeps its own cache slab whatever the gate says. Not
    # served: skipping the passes behind a token's exit, and one slab
    # shared by several passes (the paper's cache-sharing variants).
    "ouro": PeriodForm(attn_gate=False, post_norms=True, embed_scale=False,
                       router_bias=False, rotary=("global",), qk_norm=False),
    # Moonshot Kimi Linear: gated delta-rule layers (the step not
    # doubled) and, where the published lists say, a latent-attention
    # layer with no position (`mla_use_nope`: nothing is rotated) and no
    # low rank on its query; a pre-norm layer of two norms, a selection
    # bias; the leading dense layer is a delta-rule layer too.
    "kimi_linear": PeriodForm(attn_gate=False, post_norms=False,
                              embed_scale=False, router_bias=True, rotary=(),
                              qk_norm=False, recurrent="linear", latent=True),
}


@dataclass(frozen=True)
class LatentForm:
    """What a layer of the latent stack (models/latent.py) has, as
    `PeriodForm` says it of the period stack: the stack reads this and
    never asks for an architecture by name. Whether a layer has the
    sparse-attention indexer is the configuration's own
    (`TransformerConfig.index_topk`)."""

    post_norms: bool      # RMS norms on the attention and FFN outputs
    router_bias: bool     # a per-expert bias added for the selection only


# `TransformerConfig.arch` -> its layer, for the architectures of STACKS
# that the latent stack serves.
LATENT_FORMS: Dict[str, LatentForm] = {
    # openPangu-Ultra-MoE: sandwich norms, four a layer; no selection bias.
    "pangu_ultra_moe": LatentForm(post_norms=True, router_bias=False),
    # GLM-5: a pre-norm layer of two norms, the router's selection bias
    # (`noaux_tc`), and the learned sparse-attention indexer.
    "glm_moe_dsa": LatentForm(post_norms=False, router_bias=True),
}


def _frozen(value):
    """A JSON value as something hashable (a config is a static argument
    of every jitted program): dicts as sorted tuples of pairs."""
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    if isinstance(value, (list, tuple)):
        return tuple(_frozen(v) for v in value)
    return value


def stack(cfg: "TransformerConfig"):
    """The stack module of `cfg`'s architecture."""
    return importlib.import_module(f"{__package__}.{STACKS[cfg.arch]}")


def offered(cfg: "TransformerConfig", name: str):
    """`name` of `cfg`'s stack; NotImplementedError, with the stack's own
    reason, where the stack does not have it."""
    st = stack(cfg)
    if not hasattr(st, name):
        raise NotImplementedError(st.MISSING[name])
    return getattr(st, name)


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: int = 12
    d_ff: int = 3072
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16        # activation dtype
    param_dtype: Any = jnp.float32   # master weights
    tie_embeddings: bool = True
    remat: bool = True
    # None = a layer recomputes in its backward what does not fit: where
    # `remat_fits` (the kept bytes and the training state a device
    # within `REMAT_DEVICE_BYTES`) the attention half is kept (`_remat`:
    # q, k, v, the flash output, its row statistic and the stream behind
    # attention) and only the FFN's norm, two products and activation
    # run again; where not, full per-layer remat. "dots" = save matmul outputs and recompute only
    # elementwise ops (less recompute, more HBM).
    remat_policy: Optional[str] = None
    # >0: blockwise vocab-projection + cross entropy with this chunk
    # size — the f32 (B, S, V) logits tensor is never materialized
    # (chunked_cross_entropy). 0 = classic full-logits loss.
    ce_chunk: int = 0
    # attention: "auto" = ops/flash_attention.py's dispatch (kernels on a
    # TPU at or above the kv crossover, XLA-fused reference otherwise);
    # "reference" forces the einsum path. seq_parallel picks the sequence-
    # parallel strategy when the mesh has an sp axis > 1 (ops/ kernels).
    attn_impl: str = "auto"
    seq_parallel: str = "ring"       # "ring" | "ulysses"
    # MoE (0 experts = dense FFN)
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.02
    # 0 = d_model // n_heads (set in __post_init__).
    head_dim: int = 0
    # "llama": every layer alike (pre-norm, rotary, GQA, SwiGLU or the
    # routed FFN above). "afmoe": the period stack of models/periodic.py
    # (leading dense layers, then periods of window layers closed by a
    # global one; QK-norm, a gated attention output, four norms a layer,
    # rotary on window layers only, a scaled embedding). Served only:
    # forward / loss_fn raise for it. "mellum": the same stack with
    # another layer (PERIOD_FORMS: two norms, no gate, rotary on both
    # kinds of layer). "sdar_moe": that layer again, every layer global,
    # generating a block at a time (`block_length`, at the end).
    # "pangu_ultra_moe": the latent-attention stack of
    # models/latent.py (the fields at the end); "glm_moe_dsa": the same
    # stack with another layer (LATENT_FORMS: two norms, a selection
    # bias) and the sparse-attention indexer (`index_topk`).
    # "solar_open2": the period stack again, a global layer then
    # linear-attention layers (`linear_n_heads`, at the end). "jamba":
    # the period stack with state-space layers (`mamba_d_state`, at the
    # end) around a global layer at `attn_layer_offset`. "ouro": the
    # period stack, every layer global, walked `ut_steps` times a token
    # (at the end). "kimi_linear": the period stack, linear-attention
    # layers around a latent-attention layer, each layer's kind read
    # from `linear_attn_config`. STACKS above holds the names.
    arch: str = "llama"
    n_dense_layers: int = 0          # leading layers with a dense FFN
    global_attn_every: int = 0       # period length: one layer of it is global
    sliding_window: int = 0          # keys a window layer sees (0 = all)
    moe_d_ff: int = 0                # width of a routed / shared expert
    moe_shared_experts: int = 0      # always-on experts of width moe_d_ff
    score_func: str = "softmax"      # router scores: "softmax" | "sigmoid"
    route_norm: bool = True          # chosen scores renormalised to sum 1
    route_scale: float = 1.0
    # A rotary description a section, as a model's config.json gives it
    # ({"full_attention": {"rope_type": "yarn", "rope_theta": ..,
    # "factor": .., ...}, "sliding_attention": {"rope_type": "default",
    # "rope_theta": ..}}); None = `rope_theta`, unscaled, for every
    # section. Kept as sorted tuples of pairs (hashable): `rope_section`.
    rope_parameters: Any = None
    # Latent attention (models/latent.py), under the published keys: the
    # ranks the query and the keys-and-values are projected down to
    # (`q_lora_rank` 0, or a published `null`: the query is projected
    # from the layer's input, no rank and no norm between), and a head's
    # three widths (the part of q and k with no position, the rotary
    # part, which every head's key shares, and the values).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # An expert layer that holds a share of its experts: the router
    # scores `moe_router_experts` of them (0 = `moe_experts`, all held)
    # and the layer holds [moe_first_expert, moe_first_expert +
    # moe_experts), computing the part of the sum those give.
    moe_router_experts: int = 0
    moe_first_expert: int = 0
    # Learned sparse attention over the latent cache (models/latent.py):
    # a second scorer a layer, `index_n_heads` heads of `index_head_dim`
    # with a cache of one key a token of its own, scores every row a
    # query may see, and the query attends the `index_topk` best of them
    # (all of them while it sees no more). 0 = every row, no indexer.
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # The dtype the choice is made in (a name or a type; None = `dtype`):
    # the residual stream between such a stack's layers, the indexer's
    # projections of it, the keys it caches and its scores' operands.
    # Every other product and the latent cache keep `dtype`.
    index_dtype: Any = None
    # Generation by diffusion over blocks (models/generate.py, the block
    # programs): 0 = one token a step, every other configuration's value.
    # A block of `block_length` positions (a power of two) opens masked
    # (the embedding of `mask_token_id`), is denoised by passes that see
    # the committed rows and the whole block, and reaches the cache by a
    # pass over its final tokens; attention is causal between blocks and
    # unmasked inside one. The engine's defaults a request may override:
    # `denoise_steps` passes unmask a block by the rule `remask`
    # (`REMASK_RULES`), the dynamic one every position surer than
    # `confidence_threshold`.
    # Linear attention (models/periodic.py, a form with `linear`): heads
    # of `linear_head_dim` for keys and values alike, each keeping a
    # (linear_head_dim, linear_head_dim) float32 state a slot that every
    # token rewrites whole (the gated delta rule, `ops/delta_rule`), and
    # a causal depthwise convolution over the last `linear_conv_kernel`
    # positions of the q, k and v projections.
    linear_n_heads: int = 0
    linear_head_dim: int = 0
    linear_conv_kernel: int = 4
    # The published group of that name, kept as sorted tuples of pairs.
    # Read for two of its keys alone: where it has `kda_layers` and
    # `full_attn_layers` (layers counted from 1), they say each layer's
    # kind, and the period stack plans its scan steps from them
    # (`periodic.layer_plan`: they cover 1..n_layers once, or the
    # configuration is refused). Without them: whole periods, as
    # `global_attn_every` and the form place them.
    linear_attn_config: Any = None
    # State-space layers (models/periodic.py, a form with `recurrent`
    # "ssm"), under the published keys: `mamba_expand` x d_model channels,
    # each keeping `mamba_d_state` float32 coordinates a slot (the
    # selective scan, `ops/selective_scan`) behind a causal depthwise
    # convolution over the last `mamba_d_conv` positions; the step a
    # channel comes through rank `mamba_dt_rank`. `attn_layer_offset`:
    # the place of such a period's global layer.
    mamba_d_state: int = 0
    mamba_expand: int = 2
    mamba_dt_rank: int = 0
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attn_layer_offset: int = 0
    # The dtype a period stack caches keys and values in (a name or a
    # type; None = `dtype`, or, float32 activations on bf16 weights, two
    # bf16 terms a row). bfloat16 under float32 activations: one bf16
    # value a row, half the bytes; a decode step's attention then takes
    # bf16 operands, a tile's attention over itself stays float32.
    cache_dtype: Any = None
    # A looped stack (models/periodic.py), under the published keys'
    # meaning: a token walks the same `n_layers` weight layers `ut_steps`
    # times, the final norm after each pass and its output the next
    # pass's input; pass t of layer l keeps cache slab t x (layers of l's
    # kind) + l and attends to pass t's rows only. A learned gate reads
    # each pass's output; a token's logits are those of the first pass
    # whose cumulative exit mass reaches `early_exit_threshold`, else of
    # the last (1.0, the published value: the last). 1 = every other
    # configuration's walk.
    ut_steps: int = 1
    early_exit_threshold: float = 1.0
    block_length: int = 0
    mask_token_id: int = 0
    denoise_steps: int = 0           # 0 = `block_length`
    remask: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if not self.head_dim:
            object.__setattr__(self, "head_dim",
                               self.d_model // self.n_heads)
        object.__setattr__(self, "rope_parameters",
                           _frozen(self.rope_parameters))
        object.__setattr__(self, "linear_attn_config",
                           _frozen(self.linear_attn_config))
        if self.sliding_window is None:     # a published `null`: no window
            object.__setattr__(self, "sliding_window", 0)
        if self.q_lora_rank is None:        # a published `null`: no rank
            object.__setattr__(self, "q_lora_rank", 0)
        if self.arch not in STACKS:
            raise ValueError(f"arch must be one of {sorted(STACKS)}, got "
                             f"{self.arch!r}")
        listed = self.listed_global is not None
        if self.arch in PERIOD_FORMS:
            body = self.n_layers - self.n_dense_layers
            if self.global_attn_every < 1 or body < 0 or (
                    not listed and body % self.global_attn_every):
                raise ValueError(
                    f"{self.arch}: n_layers - n_dense_layers ({body}) must "
                    f"be whole periods of global_attn_every "
                    f"({self.global_attn_every})")
        if listed:
            if self.arch not in PERIOD_FORMS \
                    or not PERIOD_FORMS[self.arch].recurrent:
                raise ValueError(
                    "linear_attn_config lists each layer's kind: only a "
                    "period stack with linear layers plans from lists")
            stack(self).layer_plan(self)    # refuses lists it cannot group
        if self.cache_dtype is not None and STACKS[self.arch] != "periodic":
            raise ValueError("cache_dtype: only the period stack states "
                             "its rows' dtype")
        recurrent = PERIOD_FORMS[self.arch].recurrent \
            if self.arch in PERIOD_FORMS else ""
        if recurrent and (self.sliding_window or self.block_length or (
                self.n_dense_layers and not listed)):
            raise ValueError(
                f"{self.arch}: a period stack whose other layers keep a "
                "recurrent state has no window, no leading dense layer "
                "(but where linear_attn_config lists each layer's kind) "
                "and no block walk")
        if (recurrent == "linear") != bool(self.linear_n_heads) or (
                self.linear_n_heads and (self.linear_head_dim < 1
                                         or self.linear_conv_kernel < 2)):
            raise ValueError(
                f"linear_n_heads {self.linear_n_heads}: a period stack whose "
                "form has linear layers, and no other, has linear_n_heads "
                "heads of linear_head_dim and a convolution of 2 positions "
                "or more")
        if (recurrent == "ssm") != bool(self.mamba_d_state) or (
                self.mamba_d_state and (
                    self.mamba_dt_rank < 1 or self.mamba_d_conv < 2
                    or self.mamba_expand < 1 or self.mamba_proj_bias
                    or not 0 <= self.attn_layer_offset
                    < self.global_attn_every)):
            raise ValueError(
                f"mamba_d_state {self.mamba_d_state}: a period stack whose "
                "form has state-space layers, and no other, has "
                "mamba_d_state coordinates a channel, a step through "
                "mamba_dt_rank, a convolution of 2 positions or more, "
                "projections with no bias (mamba_proj_bias is not written) "
                "and its global layer at attn_layer_offset < "
                "global_attn_every")
        if self.ut_steps != 1 and (
                self.ut_steps < 1 or STACKS[self.arch] != "periodic"
                or recurrent or self.sliding_window or self.is_moe
                or self.block_length):
            raise ValueError(
                f"ut_steps {self.ut_steps}: only the period stack walks its "
                "layers more than once a token, and only layers that keep "
                "every row, with a dense FFN, one token a step (no "
                "recurrent kind, sliding_window 0, no experts, no "
                "block_length): a state, a ring, routing stats or an open "
                "block a pass is not written")
        if self.index_topk:
            if STACKS[self.arch] != "latent" or self.index_n_heads < 1 \
                    or self.index_head_dim < self.qk_rope_head_dim \
                    or self.index_topk < 1 or not self.q_lora_rank:
                raise ValueError(
                    f"index_topk {self.index_topk}: only the latent stack "
                    "has the indexer, with index_n_heads heads of "
                    "index_head_dim >= qk_rope_head_dim, its queries "
                    "from the query's rank (q_lora_rank)")
        if self.block_length:
            Bd = self.block_length
            if self.arch not in PERIOD_FORMS or self.sliding_window:
                raise ValueError(
                    f"block_length {Bd}: only the period stack has the "
                    "block walk (`decode_block`), and only over layers "
                    "that keep every row (sliding_window 0: a ring "
                    "cannot take a block's rows back)")
            if Bd < 1 or Bd & (Bd - 1) \
                    or not 0 <= self.mask_token_id < self.vocab_size \
                    or not 0 <= self.denoise_steps <= Bd \
                    or self.remask not in REMASK_RULES:
                raise ValueError(
                    f"block_length {Bd} must be a power of two, "
                    f"mask_token_id {self.mask_token_id} a token, "
                    f"denoise_steps {self.denoise_steps} at most a block "
                    f"and remask {self.remask!r} one of {REMASK_RULES}")
            if not self.denoise_steps:
                object.__setattr__(self, "denoise_steps", Bd)

    @property
    def is_moe(self) -> bool:
        return self.moe_experts > 0

    @property
    def listed_global(self) -> Optional[Tuple[bool, ...]]:
        """Whether each layer is a global one, in order, where
        `linear_attn_config` lists the layers of each kind (`kda_layers`,
        `full_attn_layers`, counted from 1); None where it does not.
        Lists that do not cover 1..n_layers once are refused."""
        group = dict(self.linear_attn_config or ())
        if "kda_layers" not in group and "full_attn_layers" not in group:
            return None
        linear = list(group.get("kda_layers") or ())
        full = list(group.get("full_attn_layers") or ())
        if sorted(linear + full) != list(range(1, self.n_layers + 1)):
            raise ValueError(
                f"linear_attn_config: kda_layers {linear} and "
                f"full_attn_layers {full} must cover the layers 1.."
                f"{self.n_layers} once each")
        return tuple(n in full for n in range(1, self.n_layers + 1))

    @property
    def router_experts(self) -> int:
        return self.moe_router_experts or self.moe_experts

    @property
    def expert_d_ff(self) -> int:
        return self.moe_d_ff or self.d_ff

    @property
    def period_form(self) -> PeriodForm:
        return PERIOD_FORMS[self.arch]

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    @property
    def latent_form(self) -> LatentForm:
        return LATENT_FORMS[self.arch]

    def rope_section(self, section: Optional[str]) -> Dict[str, Any]:
        """The rotary description of `section` (None, or no
        `rope_parameters`: `rope_theta`, unscaled)."""
        if section is None or self.rope_parameters is None:
            return {"rope_type": "default", "rope_theta": self.rope_theta}
        return dict(dict(self.rope_parameters)[section])

    def num_params(self) -> int:
        return stack(self).num_params(self)


# ---------------------------------------------------------------------------
# Parameter init + logical axes: the stack's own
# ---------------------------------------------------------------------------

def param_logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Same pytree structure as params, leaves = logical-axis tuples."""
    return offered(cfg, "param_logical_axes")(cfg)


def init_params(cfg: TransformerConfig, key: jax.Array) -> Dict[str, Any]:
    return stack(cfg).init_params(cfg, key)


def init_params_sharded(cfg: TransformerConfig, key: jax.Array, mesh
                        ) -> Dict[str, Any]:
    """init_params with every weight created in its mesh sharding: each
    device materializes only its shard, nothing is placed whole on the
    default device first. Same values as init_params for the same key
    (threefry is partitionable)."""
    from ..parallel.sharding import tree_shardings

    shardings = tree_shardings(param_logical_axes(cfg), mesh)
    with jax.sharding.set_mesh(mesh):
        return jax.jit(partial(init_params, cfg),
                       out_shardings=shardings)(key)


# ---------------------------------------------------------------------------
# Model pieces
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * scale.astype(jnp.float32)).astype(dt)


def rope_tables(cfg: TransformerConfig, seq_len: int,
                section: Optional[str] = None, dim: int = 0
                ) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos), each (seq_len, dim / 2), of `cfg.rope_section(
    section)`; `dim`: the width rotated (0 = `cfg.head_dim`). "default": pos x theta^(-2i/d). "yarn" (arXiv:2309.00071,
    as transformers' `_compute_yarn_parameters` has it): the frequencies
    that turn more than `beta_fast` times over the original context are
    kept, those that turn fewer than `beta_slow` times are divided by
    `factor`, a linear ramp between; sin and cos are multiplied by
    `attention_factor` (q and k both carry it)."""
    rope = cfg.rope_section(section)
    dim = dim or cfg.head_dim
    half = dim // 2
    theta = float(rope["rope_theta"])
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    kind = rope["rope_type"]
    if kind == "default":
        ang = pos[:, None] * freqs[None, :]          # (S, half)
        return jnp.sin(ang), jnp.cos(ang)
    if kind != "yarn":
        raise ValueError(f"rope_type must be 'default' or 'yarn', got "
                         f"{kind!r}")
    # Every yarn key as a config.json states it: none is guessed.
    factor = float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])

    def turns_at(rotations: float) -> float:
        # The pair index whose frequency makes `rotations` turns over
        # the original context.
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(turns_at(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(turns_at(float(rope["beta_slow"]))), dim - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    freqs = (1.0 - ramp) * freqs + ramp * freqs / factor
    scale = float(rope["attention_factor"])
    ang = pos[:, None] * freqs[None, :]
    return jnp.sin(ang) * scale, jnp.cos(ang) * scale


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, S, H, Dh). Rotate pairs (x1, x2) = (x[..., :half], x[..., half:])."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[None, :, None, :].astype(x.dtype)
    cos = cos[None, :, None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _mesh_sizes() -> Dict[str, int]:
    """{axis: size} of the mesh a function is traced under; {} with none."""
    mesh = jax.sharding.get_abstract_mesh()
    return dict(getattr(mesh, "shape", None) or {})


def _attend(cfg: TransformerConfig, q: jax.Array, k: jax.Array,
            v: jax.Array) -> jax.Array:
    """Dispatch causal attention to the right kernel for the ambient mesh.

    No mesh (or all relevant axes size 1): plain fused flash attention
    (ops/flash_attention.py decides kernel vs reference). Sharded mesh: a
    shard_map manual region — pallas kernels are opaque to the auto
    partitioner, so sharded attention MUST be manual. With an `sp` axis
    > 1 the sequence stays sharded end-to-end: ring attention rotates kv
    shards over ICI (or Ulysses all-to-all, per cfg.seq_parallel) —
    never an all-gather of the sequence.
    """
    from ..ops import flash_attention, ring_attention, ulysses_attention

    if cfg.attn_impl == "reference":
        return flash_attention(q, k, v, causal=True, force_reference=True)

    mesh = jax.sharding.get_abstract_mesh()
    sizes = _mesh_sizes()
    used = {a for a, n in sizes.items() if n > 1} & {
        "dcn", "dp", "fsdp", "ep", "tp", "sp"}
    if not used:
        return flash_attention(q, k, v, causal=True)

    q_axes = ("batch", "seq", "act_heads", None)
    kv_axes = ("batch", "seq", "act_kv_heads", None)
    qspec = logical_to_mesh_axes(q_axes, mesh=mesh)
    kvspec = logical_to_mesh_axes(kv_axes, mesh=mesh)
    sp = sizes.get("sp", 1)

    def local_attn(q, k, v):
        if sp > 1:
            if cfg.seq_parallel == "ulysses":
                return ulysses_attention(q, k, v, axis_name="sp",
                                         causal=True)
            return ring_attention(q, k, v, axis_name="sp", causal=True)
        return flash_attention(q, k, v, causal=True)

    return jax.shard_map(
        local_attn, mesh=mesh, in_specs=(qspec, kvspec, kvspec),
        out_specs=qspec, check_vma=False)(q, k, v)


def attention(cfg: TransformerConfig, lp: Dict[str, jax.Array],
              x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """Causal self-attention with GQA. x: (B, S, D) in activation dtype."""
    B, S, D = x.shape
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    q = (x @ lp["wq"].astype(x.dtype)).reshape(B, S, H, Dh)
    k = (x @ lp["wk"].astype(x.dtype)).reshape(B, S, KVH, Dh)
    v = (x @ lp["wv"].astype(x.dtype)).reshape(B, S, KVH, Dh)
    q = apply_rope(q, sin, cos)
    k = apply_rope(k, sin, cos)
    q = wsc(q, ("batch", "seq", "act_heads", None))
    k = wsc(k, ("batch", "seq", "act_kv_heads", None))
    v = wsc(v, ("batch", "seq", "act_kv_heads", None))

    out = _attend(cfg, q, k, v).reshape(B, S, H * Dh)
    out = out @ lp["wo"].astype(x.dtype)
    return wsc(out, ("batch", "seq", "act_embed"))


def dense_ffn(lp: Dict[str, jax.Array], x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ lp["w_gate"].astype(x.dtype)) \
        * (x @ lp["w_up"].astype(x.dtype))
    h = wsc(h, ("batch", "seq", "act_mlp"))
    return h @ lp["w_down"].astype(x.dtype)


def moe_ffn(cfg: TransformerConfig, lp: Dict[str, jax.Array],
            x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Top-k routed MoE with capacity-bounded one-hot dispatch
    (einsum dispatch/combine — the XLA-friendly formulation; tokens over
    capacity are dropped). Returns (out, aux_loss)."""
    B, S, D = x.shape
    E, K = cfg.moe_experts, cfg.moe_top_k
    T = B * S
    cap = max(1, int(cfg.moe_capacity_factor * T * K / E))

    xt = x.reshape(T, D)
    logits = (xt @ lp["router"].astype(x.dtype)).astype(jnp.float32)  # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)

    # Load-balancing auxiliary loss (switch-transformer style).
    gate_mean = jnp.mean(probs, axis=0)                      # (E,)
    top1 = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top1, E, dtype=jnp.float32), axis=0)
    aux = E * jnp.sum(gate_mean * frac) * cfg.moe_aux_loss_weight

    topk_p, topk_e = lax.top_k(probs, K)                     # (T,K)
    topk_p = topk_p / (jnp.sum(topk_p, axis=-1, keepdims=True) + 1e-9)

    # Position of each (token, k) in its expert's buffer.
    onehot = jax.nn.one_hot(topk_e, E, dtype=jnp.int32)      # (T,K,E)
    flat = onehot.reshape(T * K, E)
    pos = (jnp.cumsum(flat, axis=0) - 1).reshape(T, K, E)
    pos = jnp.sum(pos * onehot, axis=-1)                     # (T,K)
    keep = pos < cap
    # dispatch: (T, K, E, cap) one-hot → (E, cap, D) expert inputs
    disp = (jax.nn.one_hot(topk_e, E, dtype=x.dtype)[..., None]
            * jax.nn.one_hot(jnp.where(keep, pos, cap), cap + 1,
                             dtype=x.dtype)[..., None, :])[..., :cap]
    expert_in = jnp.einsum("td,tkec->ecd", xt, disp)
    expert_in = wsc(expert_in, ("expert", None, "act_embed"))

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in,
                               lp["w_gate"].astype(x.dtype))) \
        * jnp.einsum("ecd,edf->ecf", expert_in, lp["w_up"].astype(x.dtype))
    h = wsc(h, ("expert", None, "act_mlp"))
    expert_out = jnp.einsum("ecf,efd->ecd", h, lp["w_down"].astype(x.dtype))

    combine = disp * topk_p.astype(x.dtype)[..., None, None]
    out = jnp.einsum("ecd,tkec->td", expert_out, combine)
    return out.reshape(B, S, D), aux


# What a layer's `jax.checkpoint` keeps by name where it fits: the flash
# call's residuals (`ops/flash_attention.RESIDUAL_NAMES`: without all
# five its forward kernel runs again) and the stream behind attention
# (without it `wo` is multiplied again to feed the FFN's norm).
ATTN_STREAM = "attn_stream"

# What a training step holds of each parameter: the weight, its gradient
# and Adam's two moments (`train/step.make_optimizer`), in its dtype.
STATE_COPIES = 4

# The most the kept values and that state may take of a device: the
# 15.75e9 a step compiled for the smallest chip this trains on (v5e, 16
# GiB) is held to, less the 4e9 a step under full remat needs beside its
# state at 8,192 tokens a device (the described compile: 4.06e9 at
# internlm2-1.8b over fsdp=4, 3.24e9 at llama-654m and 3.87e9 at
# llama-1b4 on one chip). internlm2-1.8b over fsdp=4 at 2 x 4,096 tokens
# a device holds 7.56e9 of state and keeps 3.23e9: it passes, and
# compiles to 14.22e9; at three sequences a device it does not. llama-654m
# at 8 x 1,024 on one chip (10.47e9 + 1.48e9) does not either, and
# should not: there XLA holds the kept values twice.
REMAT_DEVICE_BYTES = int(15.75e9 - 4e9)


def _shards(logical: Tuple[Optional[str], ...],
            mesh_sizes: Dict[str, int]) -> int:
    """Into how many parts a mesh of `mesh_sizes` ({axis: size}) cuts a
    value of these logical axes."""
    n = 1
    for target in logical_to_mesh_axes(logical):
        for axis in (target,) if isinstance(target, str) else target or ():
            n *= mesh_sizes.get(axis, 1)
    return n


def remat_kept_bytes(cfg: TransformerConfig, batch: int, seq: int,
                     mesh_sizes: Dict[str, int]) -> int:
    """Bytes a device that the flash call's residuals and `ATTN_STREAM`
    hold for a (batch, seq) step over the stack's layers: each value's
    width a token in the activation dtype (the row statistic in float32)
    over the mesh axes its logical axes are sharded on."""
    item = jnp.dtype(cfg.dtype).itemsize
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    a_token = (
        (2 * H * Dh * item + 4 * H, "act_heads"),       # q, out; lse
        (2 * KVH * Dh * item, "act_kv_heads"),          # k, v
        (cfg.d_model * item, "act_embed"))              # the stream
    return cfg.n_layers * sum(
        -(-batch * seq * width // _shards(("batch", "seq", last),
                                          mesh_sizes))
        for width, last in a_token)


def param_bytes(cfg: TransformerConfig, params: Dict[str, Any],
                mesh_sizes: Dict[str, int]) -> int:
    """Bytes a device of `params` (arrays or their shapes) under the
    stack's `param_logical_axes`."""
    def is_axes(x):
        return isinstance(x, tuple)

    parts = jax.tree.map(
        lambda axes, p: -(-p.size * jnp.dtype(p.dtype).itemsize
                          // _shards(axes, mesh_sizes)),
        param_logical_axes(cfg), params, is_leaf=is_axes)
    return sum(jax.tree.leaves(parts))


def remat_fits(cfg: TransformerConfig, params: Dict[str, Any], batch: int,
               seq: int, mesh_sizes: Dict[str, int]) -> bool:
    """Whether a (batch, seq) training step of `params` on this mesh has
    room to keep the attention half of every layer."""
    return remat_kept_bytes(cfg, batch, seq, mesh_sizes) \
        + STATE_COPIES * param_bytes(cfg, params, mesh_sizes) \
        <= REMAT_DEVICE_BYTES


def _remat(cfg: TransformerConfig, layer, fits: bool):
    """`layer` under the checkpoint `cfg.remat_policy` asks for; with
    none named, one that keeps the attention half where it `fits`."""
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    elif cfg.remat_policy is not None:
        raise ValueError(
            f"remat_policy must be None or 'dots', got "
            f"{cfg.remat_policy!r}")
    elif fits:
        from ..ops.flash_attention import RESIDUAL_NAMES

        policy = jax.checkpoint_policies.save_only_these_names(
            *RESIDUAL_NAMES, ATTN_STREAM)
    else:
        policy = None
    return jax.checkpoint(layer, policy=policy)


def _layer(cfg: TransformerConfig, carry, lp):
    x, sin, cos = carry
    a = attention(cfg, lp, rms_norm(x, lp["attn_norm"], cfg.norm_eps),
                  sin, cos)
    x = checkpoint_name(x + a, ATTN_STREAM)
    h = rms_norm(x, lp["ffn_norm"], cfg.norm_eps)
    if cfg.is_moe:
        f, aux = moe_ffn(cfg, lp, h)
    else:
        f, aux = dense_ffn(lp, h), jnp.zeros((), jnp.float32)
    x = x + f
    x = wsc(x, ("batch", "seq", "act_embed"))
    return (x, sin, cos), aux


def forward_hidden(cfg: TransformerConfig, params: Dict[str, Any],
                   tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) int32 → (final hidden states (B, S, D), aux_loss)
    — the trunk without the vocab projection (the chunked-CE loss
    applies the head blockwise instead of materializing logits). The
    dense stack's training walk (`dense.forward_train`)."""
    B, S = tokens.shape
    # Constrain the table to replicated for the lookup: the stored param
    # is (vocab→tp, embed→fsdp)-sharded, and a gather from an
    # embed-sharded operand into a batch-fsdp-sharded activation makes
    # XLA's SPMD partitioner fall back to "involuntary full
    # rematerialization" (the fsdp axis must move between tensor dims,
    # which gather can't reshard in place). Replicating first turns that
    # into one explicit all-gather + a local gather + a free slice.
    tokens = wsc(tokens, ("batch", "seq"))
    emb = wsc(params["embed"].astype(cfg.dtype), (None, None))
    x = wsc(emb[tokens], ("batch", "seq", "act_embed"))
    sin, cos = rope_tables(cfg, S)

    layer = partial(_layer, cfg)
    if cfg.remat:
        layer = _remat(cfg, layer, remat_fits(cfg, params, B, S,
                                              _mesh_sizes()))
    (x, _, _), aux = lax.scan(layer, (x, sin, cos), params["layers"])

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.sum(aux)


def _lm_head(cfg: TransformerConfig, params: Dict[str, Any]) -> jax.Array:
    return (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"]).astype(cfg.dtype)


def forward(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """tokens (B, S) int32 → (logits (B, S, V) float32, aux_loss)."""
    x, aux = offered(cfg, "forward_train")(cfg, params, tokens)
    return _logits(cfg, params, x), aux


def _logits(cfg: TransformerConfig, params: Dict[str, Any],
            x: jax.Array) -> jax.Array:
    logits = (x @ _lm_head(cfg, params)).astype(jnp.float32)
    return wsc(logits, ("batch", "seq", "act_vocab"))


def token_cross_entropy(logits: jax.Array, targets: jax.Array,
                        mask: Optional[jax.Array], aux: jax.Array
                        ) -> Tuple[jax.Array, Dict]:
    """Next-token cross entropy (mean over unmasked positions) + metrics."""
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum(nll * mask) / denom
    total = ce + aux
    return total, {"loss": total, "ce": ce, "aux": aux,
                   "tokens": jnp.sum(mask)}


def _chunk_nll(xc: jax.Array, head: jax.Array, tc: jax.Array,
               mc: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One chunk's float32 logits, their log-sum-exp and the sum of the
    masked negative log-likelihoods."""
    logits = (xc @ head).astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
    return logits, logz, jnp.sum((logz - gold) * mc)


def _gathered(head: jax.Array) -> jax.Array:
    """The head whole along `embed` before a scan over chunks: the
    parameter is sharded there (fsdp), and gathered where it is used it
    is gathered once a chunk, inside the scan."""
    return wsc(head, (None, "vocab"))


@jax.custom_vjp
def _chunked_nll(xs: jax.Array, head: jax.Array, ts: jax.Array,
                 ms: jax.Array) -> jax.Array:
    """Mean masked negative log-likelihood of chunks xs (n, B, chunk, D)
    under `head` (D, V). Undifferentiated (an evaluation), the scan
    makes the logits and nothing else."""
    head = _gathered(head)

    def body(tot, inp):
        xc, tc, mc = inp
        return tot + _chunk_nll(xc, head, tc, mc)[2], None

    tot, _ = lax.scan(body, jnp.zeros(()), (xs, ts, ms))
    return tot / jnp.maximum(jnp.sum(ms), 1.0)


def _chunked_nll_fwd(xs, head, ts, ms):
    """The differentiated forward: a cross entropy's gradient is known
    the moment a chunk's logits are, so the pass that makes them makes
    both gradients while they are there (softmax - onehot in float32,
    its two products over operands of the activation dtype accumulated
    in float32, as the transposes of `xc @ head` are) and keeps those:
    the logits are neither held nor multiplied again.

    No collective inside the scan: on the chip one costs the step
    nearly its whole length, whatever a profile shows beside it
    (PERF.md, PR 43: eight all-reduces of the head's gradient a step 37
    ms, eight all-gathers of the head 25). So the head is gathered
    before the scan, and its gradient, which sums over the batch that a
    mesh cuts, is carried as one float32 partial sum a batch shard and
    reduced once behind the scan (a carry of the head's own shape the
    partitioner reduces a chunk). The barrier has that reduction done
    before the backward pass takes `dxs`: left to the scheduler it waits
    for the optimizer, and the partial sums (758 MB a device in the
    cell) with it."""
    count = jnp.maximum(jnp.sum(ms), 1.0)
    head = _gathered(head)
    shards = _shards(("batch",), _mesh_sizes())
    if xs.shape[1] % shards:
        shards = 1
    partial_axes = ("batch", None, "vocab")

    def body(carry, inp):
        tot, dhead = carry
        xc, tc, mc = inp
        logits, logz, nll = _chunk_nll(xc, head, tc, mc)
        with jax.named_scope("head_grad"):
            p = jnp.exp(logits - logz[..., None])
            p = (p - jax.nn.one_hot(tc, p.shape[-1], dtype=p.dtype)) \
                * (mc / count)[..., None]
            p = p.astype(xc.dtype)
            dxc = jnp.einsum("bcv,dv->bcd", p, head)
            dhead = wsc(dhead + jnp.einsum(
                "sbcd,sbcv->sdv", xc.reshape((shards, -1) + xc.shape[1:]),
                p.reshape((shards, -1) + p.shape[1:]),
                preferred_element_type=jnp.float32), partial_axes)
        return (tot + nll, dhead), dxc

    zero = wsc(jnp.zeros((shards,) + head.shape, jnp.float32), partial_axes)
    (tot, dhead), dxs = lax.scan(body, (jnp.zeros(()), zero), (xs, ts, ms))
    dhead = wsc(dhead.sum(0).astype(head.dtype), ("embed", "vocab"))
    return tot / count, lax.optimization_barrier((dxs, dhead))


def _chunked_nll_bwd(res, g):
    dxs, dhead = res
    return ((g * dxs).astype(dxs.dtype), (g * dhead).astype(dhead.dtype),
            None, None)


_chunked_nll.defvjp(_chunked_nll_fwd, _chunked_nll_bwd)


def chunked_cross_entropy(cfg: TransformerConfig, params: Dict[str, Any],
                          x: jax.Array, targets: jax.Array,
                          mask: Optional[jax.Array], aux: jax.Array,
                          chunk: int) -> Tuple[jax.Array, Dict]:
    """Fused/blockwise vocab projection + cross entropy: scans the
    sequence in chunks, so the full f32 (B, S, V) logits tensor is never
    materialized (for GPT-2-125M at B16×S1024 that tensor is 3.3 GB
    each for value and grad — the dominant HBM cost of the step), and
    under differentiation a chunk's pass makes its gradients too
    (`_chunked_nll`). Numerically identical to token_cross_entropy (same
    per-position logsumexp in f32)."""
    B, S, D = x.shape
    head = _lm_head(cfg, params)
    n_chunks = S // chunk
    xs = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)
    ts = targets.reshape(B, n_chunks, chunk).swapaxes(0, 1)
    if mask is None:
        ms = jnp.ones((n_chunks, B, chunk), jnp.float32)
    else:
        ms = mask.astype(jnp.float32).reshape(
            B, n_chunks, chunk).swapaxes(0, 1)
    ce = _chunked_nll(xs, head, ts, ms)
    total = ce + aux
    return total, {"loss": total, "ce": ce, "aux": aux,
                   "tokens": jnp.sum(ms)}


def loss_fn(cfg: TransformerConfig, params: Dict[str, Any],
            tokens: jax.Array, targets: jax.Array,
            mask: Optional[jax.Array] = None) -> Tuple[jax.Array, Dict]:
    """The scopes name the phases in a device trace: an operation of
    the trunk's forward pass reads `.../jvp(fwd)/...` under
    `value_and_grad`, its backward pass (remat recomputation included)
    `.../transpose(jvp(fwd))/...`, the head and the cross entropy the
    same under `loss_head`."""
    S = tokens.shape[1]
    chunked = cfg.ce_chunk > 0 and S > cfg.ce_chunk
    if cfg.ce_chunk > 0 and S % cfg.ce_chunk != 0:
        # Accepted ≠ enforced: silently materializing the full
        # logits tensor is exactly what the option exists to avoid.
        raise ValueError(
            f"ce_chunk={cfg.ce_chunk} must divide the sequence "
            f"length (got S={S})")
    with jax.named_scope("fwd"):
        x, aux = offered(cfg, "forward_train")(cfg, params, tokens)
    with jax.named_scope("loss_head"):
        if chunked:
            return chunked_cross_entropy(cfg, params, x, targets, mask,
                                         aux, cfg.ce_chunk)
        return token_cross_entropy(_logits(cfg, params, x), targets, mask,
                                   aux)
