"""What the stacks of `transformer.STACKS` are built from, below all of
them: this module imports no stack and not `generate.py`; every stack
imports it.

- The cache every stack hands the programs (`KVCache`), what its walks
  hand back beside their results (`Extras`), what a stack counts on the
  host for the engine (`counters`: the defaults), and one token or one
  block a slot against a carried cache of keys and values a head.
- A layer's pieces: the RMS norm, SwiGLU, a branch's way into the
  residual stream, the FFN half (`models/moe.py`'s routed layer beside
  its shared experts, or a dense SwiGLU); the final norm and the head.
- The skeleton of a stack whose layers are not all alike (`periodic.py`,
  `latent.py`): a plan of groups of like layers (`Group`), the seeded
  weights and the parameter count of a plan, the FFN's leaves, and the
  walk over a plan (`run`). Such a stack adds its attention leaves, its
  cache, the attention half of its layer and its entry points.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .moe import (EXPERT_LEAVES, dot as _dot, routed_ffn, routing_stats,
                  routing_sums)
from .transformer import TransformerConfig, apply_rope


class KVCache(NamedTuple):
    """Static decode state. k/v: (L, B, S_max, KVH, Dh) activation dtype;
    seq_lens: (B,) int32 — tokens already written per slot.

    One buffer each for the life of an engine: every program that takes
    a cache donates it and returns it updated in place. The decode
    programs carry k and v whole through their layer and step loops,
    write one row a slot a layer and read, of each layer, the rows the
    owned slots hold (`_attend_cache`); a row no request owns is never
    written. A row below `seq_lens` is final; what lies at or past it is
    padding a prefill left or, where a configuration generates a block
    of positions a pass (`decode_block_multi`), the rows [seq_lens,
    seq_lens + block_length) of the open block as its last pass wrote
    them: every pass of the block overwrites them, and they are final
    only once a pass over the block's final tokens has advanced
    `seq_lens` past them.

    A period stack (`models/periodic.py`) keeps two kinds of state: k/v
    hold its global layers, (Lg, B, S_max, KVH, Dh), and kw/vw its
    window layers, (Lw, B, min(window, S_max), KVH, Dh), a ring written
    at `position mod rows`. Every other model leaves kw/vw None.

    A latent stack (`models/latent.py`) keeps neither keys nor values a
    head: `c`, (L, B, S_max, C), holds a token's latent vector and its
    rotary key, every head's keys and values are products of it, and k
    and v are None. Every other model leaves c None. Where such a stack
    chooses the rows a query attends (`TransformerConfig.index_topk`) it
    keeps a fifth kind of state beside them: `ki`, (L, B, S_max,
    index_head_dim) float32, the one key a token a layer that its indexer
    scores (after its norm and rotation); None anywhere else.

    A period stack with linear-attention layers keeps, beside its global
    layers' k/v, a sixth kind of state that does not grow with the
    tokens held and is never final: `s`, (Ll, B, H, dk, dv) float32, a
    head's recurrent state, which every step of an owned slot rewrites
    whole, and `tails`, (Ll, B, conv - 1, 3 x H x dk) in the activation
    dtype, the last inputs of the layer's short convolutions. An
    admission tile writes both as the prompt's last token left them
    (`seq_lens` hides a stale row; nothing would hide a stale state);
    None anywhere else. Where such a stack's global layers are latent
    (`PeriodForm.latent`) their rows are `c`, (Lg, B, S_max, C), beside
    `s` and `tails`, and k and v are None: two kinds of entry in one
    cache that are both unlike keys and values.

    The leading axis counts cache slabs, not weight layers: a stack
    that walks its layers `cfg.ut_steps` times a token
    (`models/periodic.py`) keeps pass t of layer l at slab t x L + l,
    (ut_steps x L, B, S_max, KVH, Dh), each written and read by its own
    pass alone."""

    k: Optional[jax.Array]
    v: Optional[jax.Array]
    seq_lens: jax.Array
    kw: Optional[jax.Array] = None
    vw: Optional[jax.Array] = None
    c: Optional[jax.Array] = None
    ki: Optional[jax.Array] = None
    s: Optional[jax.Array] = None
    tails: Optional[jax.Array] = None

    @property
    def _rows(self) -> jax.Array:
        return self.c if self.k is None else self.k

    @property
    def max_seq_len(self) -> int:
        return self._rows.shape[2]

    @property
    def num_slots(self) -> int:
        return self._rows.shape[1]


class Extras(NamedTuple):
    """What a stack's walks and the programs over them hand back beside
    their results, last and always: a field nobody fills is None, an empty
    pytree, so a program has one arity whatever the configuration.
    routing: int32 (4,) or (5,), `moe.routed_ffn`'s stats summed over the
    routed layers (and over a block's steps). exits: int32, each row's
    exit pass from 0 where the stack walks its layers `cfg.ut_steps`
    times: (W, S) a tile's walk, (W,) its program's, (B,) a step's, (k, B)
    a block's."""

    routing: Optional[jax.Array] = None
    exits: Optional[jax.Array] = None


# ---------------------------------------------------------------------------
# What a stack counts on the host for the engine: the defaults
# ---------------------------------------------------------------------------
# Plain Python over ints (`transformer.STACKS`). `counters`: the zeroed
# counters a configuration reports beyond the engine's own. Each `*_counts`
# returns (what the counters gain, by counter; what the span says, by
# attribute: one dict twice where the names agree): of an admission tile of
# `bucket` positions whose rows hold `lengths` tokens as the program sees
# them (`bucket` each on the queue side, 1 for a row nobody fills), `tokens`
# of them real; of a decode block of `k` steps at dispatch, whose owned
# slots hold `first_rows[i]` rows once its first step's row is written and
# `held` summed over its steps; and of a block's `extras` once on the host
# (a tile's: `k` 0), where `taken[i]` of slot i's (row i's) results reached
# a caller, its first ones. `by_products`: whether the programs fill any
# field of `Extras`. Here, what a stack with nothing to add re-exports.

def counters(cfg: TransformerConfig) -> Dict[str, Any]:
    return {}


def _nothing(cfg: TransformerConfig, *told):
    return {}, {}


tile_counts = block_counts = result_counts = _nothing


def by_products(cfg: TransformerConfig) -> bool:
    return False


def routing_counters(layers: int) -> Dict[str, int]:
    """The zeroed counters of `layers` routed layers (`routing_counts`)."""
    if not layers:
        return {}
    names = list(routing_sums([0] * 4))
    return dict.fromkeys(
        ["moe_expert_steps"] + names + ["prefill_" + n for n in names], 0)


def routing_counts(cfg, layers: int, k: int, routing) -> Dict[str, int]:
    """`Extras.routing` under the counters' names: a block's of `k` steps,
    with the expert-steps it offered (steps x layers x experts held; the
    program says how many took a row), or, under `prefill_`, a tile's."""
    if routing is None:
        return {}
    sums = routing_sums(routing)
    if not k:
        return {"prefill_" + name: n for name, n in sums.items()}
    return dict(moe_expert_steps=k * layers * cfg.moe_experts, **sums)


# ---------------------------------------------------------------------------
# One token, or one block, a slot against a carried cache
# ---------------------------------------------------------------------------

def _rope(x, sin, cos):
    """apply_rope accepting either shared (S, half) tables or per-slot
    (B, S, half) tables (decode: every slot is at its own position)."""
    if sin.ndim == 2:
        return apply_rope(x, sin, cos)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    sin = sin[:, :, None, :].astype(x.dtype)     # (B, S, 1, half)
    cos = cos[:, :, None, :].astype(x.dtype)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _last_rows(x, lengths):
    """The last real position of each row of x (W, S, D) -> (W, 1, D)."""
    idx = (lengths - 1).astype(jnp.int32)[:, None, None]
    return jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)


def rows_held(positions, S: int, live=None):
    """The rows of its S a slot at `positions` (B,) holds once this
    step's row is written: `positions + 1`, all S once a ring has gone
    round, and none for a slot no request owns (`live` (B,) bool; None:
    every slot is owned)."""
    n = jnp.minimum(positions + 1, S).astype(jnp.int32)
    return n if live is None else jnp.where(live, n, 0)


def masked_softmax(scores, n_rows, live):
    """Softmax of scores (B, KVH, G, S) over the first `n_rows` (B,) of
    S; where a slot may hold none (`live` given), zeros for it."""
    valid = (jnp.arange(scores.shape[-1])[None, :]
             < n_rows[:, None])[:, None, None, :]
    probs = jax.nn.softmax(jnp.where(valid, scores, -jnp.inf), axis=-1)
    return probs if live is None else jnp.where(valid, probs, 0.0)


def _attend_cache(cfg: TransformerConfig, q, k, v, k_all, v_all, l,
                  write_at, positions, live=None):
    """One token a slot against layer `l` of a carried cache (L, B, S,
    KVH, Dh): write this step's k and v at row `write_at` (B,), then
    attend over the rows the slot holds (`rows_held`). Returns (out (B,
    1, H*Dh), k_all, v_all). For a ring of S rows `write_at` is
    `positions mod S`: every row is seen once `positions` has passed
    S - 1. A slot that holds no row attends to nothing: zeros.

    On a TPU, where the rows tile, the read is `ops/decode_attention`'s
    kernel: the cache where it lies, only the rows held. Elsewhere the
    products below, over every row with a mask."""
    from ..ops import decode_attention as da

    B, S = k_all.shape[1], k_all.shape[2]
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim

    # Write new kv at each slot's position. A true scatter (one row per
    # slot), overwriting: prefill leaves pad-position kv beyond
    # `length`, so the target row may hold stale values. A slot the
    # engine no longer owns keeps advancing and can reach S: its write
    # falls out of bounds and is dropped, never clamped onto row S-1.
    rows = jnp.arange(B)
    k_all = k_all.at[l, rows, write_at].set(k[:, 0], mode="drop")
    v_all = v_all.at[l, rows, write_at].set(v[:, 0], mode="drop")
    n_rows = rows_held(positions, S, live)
    G = H // KVH
    qg = q.reshape(B, KVH, G, Dh)
    if da.usable(k_all, Dh):
        return da.decode_attention(qg, k_all, v_all, l, n_rows), k_all, v_all
    k_cache = lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
    v_cache = lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)

    # GQA decode attention over the cache with a length mask. The cache
    # stays in its own dtype; products accumulate in float32.
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32) / (Dh ** 0.5)
    probs = masked_softmax(scores, n_rows, live).astype(k_cache.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    return out.reshape(B, 1, H * Dh), k_all, v_all


def _attend_cache_block(cfg: TransformerConfig, q, k, v, k_all, v_all, l,
                        p0, live):
    """A block of Bd positions a slot against layer `l` of a carried
    cache: q (B, Bd, H, Dh), k and v (B, Bd, KVH, Dh), the block standing
    at rows [p0, p0 + Bd) (p0 (B,)). Writes the block's k and v there,
    then every query of the block attends over rows [0, p0 + Bd): all Bd
    see the same keys, so they stand beside the heads of their group,
    (B, KVH, Bd x G, Dh), and a slot's rows are read once for the whole
    block, by the kernel `_attend_cache` uses or, where it does not run,
    by the same products over every row with a mask. A slot that is not
    `live` (B,) writes nothing and gets zeros. Returns (out (B, Bd,
    H*Dh), k_all, v_all)."""
    from ..ops import decode_attention as da

    B, S = k_all.shape[1], k_all.shape[2]
    Bd = q.shape[1]
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // KVH
    # A slot that does not write aims past the cache's end: dropped.
    at = jnp.where(live[:, None], p0[:, None] + jnp.arange(Bd)[None, :], S)
    rows = jnp.arange(B)[:, None]
    k_all = k_all.at[l, rows, at].set(k.astype(k_all.dtype), mode="drop")
    v_all = v_all.at[l, rows, at].set(v.astype(v_all.dtype), mode="drop")
    n_rows = rows_held(p0 + Bd - 1, S, live)
    qg = q.reshape(B, Bd, KVH, G, Dh).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KVH, Bd * G, Dh)
    if da.usable(k_all, Dh):
        out = da.decode_attention(qg, k_all, v_all, l, n_rows)
    else:
        k_cache = lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
        v_cache = lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
        scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                            preferred_element_type=jnp.float32) / (Dh ** 0.5)
        probs = masked_softmax(scores, n_rows, live).astype(k_cache.dtype)
        out = jnp.einsum("bkgs,bskd->bkgd", probs, v_cache)
    out = out.reshape(B, KVH, Bd, G, Dh).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, Bd, H * Dh), k_all, v_all


# ---------------------------------------------------------------------------
# Pieces of a layer, the final norm and the head
# ---------------------------------------------------------------------------

def _norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMS norm, float32 out whatever comes in."""
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def _swiglu(m: jax.Array, gate, up, down) -> jax.Array:
    h = jax.nn.silu(_dot(m, gate)) * _dot(m, up)
    return _dot(h.astype(m.dtype), down)


def joins(x, branch, gain, eps: float):
    """x + a branch's output, through an RMS norm of the branch's own
    where the layer has one (`gain`: that norm's, else None)."""
    if gain is not None:
        branch = _norm(branch, gain, eps)
    return x + branch.astype(x.dtype)


def ffn_half(cfg: TransformerConfig, lp, x, experts_at, post_norms: bool,
             rows=None):
    """The second half of a layer on x (B, S, D): `ffn_norm`, the routed
    experts (plus the always-on shared ones where the configuration has
    them) or a dense SwiGLU, and the join, through `post_ffn_norm` where
    the layer's branches have norms of their own (`post_norms`).
    `experts_at`: None for a dense FFN, else (the stack's expert
    matrices, this layer's first group in them). `rows` (B*S,) bool: the
    rows somebody owns, the only ones a routed layer's experts take
    (`moe.routed_ffn`; None: every row). Returns (x, routing stats,
    experts chosen (B*S, K)), the last two None for a dense FFN."""
    B, S, _ = x.shape
    dt, eps = cfg.dtype, cfg.norm_eps
    m = _norm(x, lp["ffn_norm"], eps)                      # float32
    stats = experts = None
    if experts_at is not None:
        flat = m.reshape(B * S, -1)
        f, stats, experts = routed_ffn(cfg, lp, flat, dt, *experts_at,
                                       rows=rows)
        if cfg.moe_shared_experts:
            with jax.named_scope("moe_shared"):
                f = f + _swiglu(flat.astype(dt), lp["shared_gate"],
                                lp["shared_up"], lp["shared_down"])
        f = f.reshape(B, S, -1)
    else:
        with jax.named_scope("ffn"):
            f = _swiglu(m.astype(dt), lp["w_gate"], lp["w_up"], lp["w_down"])
    gain = lp["post_ffn_norm"] if post_norms else None
    return joins(x, f, gain, eps), stats, experts


def _final(cfg: TransformerConfig, params, x):
    return _norm(x, params["final_norm"], cfg.norm_eps).astype(cfg.dtype)


def head_logits(cfg: TransformerConfig, params, x) -> jax.Array:
    """Final-normed x (..., D) -> float32 logits (..., V). The product
    takes the head as it lies and x in its dtype: float32 activations
    never make a float32 copy of the head (1.6 GB at 200,192 rows)."""
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def last_logits(cfg: TransformerConfig, params, x, lengths) -> jax.Array:
    """Logits (W, V) at the last real position of final-normed x (W, S, D)."""
    return head_logits(cfg, params, _last_rows(x, lengths)[:, 0])


def exit_select(cfg: TransformerConfig, params, hs):
    """Where a looped stack's rows leave it. hs (T, ..., D): the
    final-normed output of each of the T = `cfg.ut_steps` passes ->
    (each row's state at its exit pass (..., D), the exit pass (...,)
    int32 from 0, the exit mass (..., T) float32). The gate of pass t,
    `lam_t = sigmoid(h_t . w + b)`, gives the mass `p_t = lam_t x
    prod_{j<t} (1 - lam_j)`, the last pass the rest; a row exits at the
    first pass whose cumulative mass reaches
    `cfg.early_exit_threshold`, else at the last. Float32 on the vector
    unit (a product of two float32 vectors on the matrix unit would
    take bf16 passes)."""
    f32 = jnp.float32
    gate = params["exit_gate"]
    lam = jax.nn.sigmoid(jnp.moveaxis(
        jnp.sum(hs.astype(f32) * gate["w"].astype(f32), axis=-1), 0, -1)
        + gate["b"].astype(f32))                               # (..., T)
    stay = jnp.cumprod(1.0 - lam, axis=-1)
    before = jnp.concatenate(
        [jnp.ones_like(stay[..., :1]), stay[..., :-1]], axis=-1)
    mass = jnp.concatenate(
        [(lam * before)[..., :-1], before[..., -1:]], axis=-1)
    reached = jnp.cumsum(mass, axis=-1) >= cfg.early_exit_threshold
    last = hs.shape[0] - 1
    exits = jnp.where(jnp.any(reached, axis=-1),
                      jnp.argmax(reached, axis=-1), last).astype(jnp.int32)
    x = jnp.take_along_axis(hs, exits[None, ..., None], axis=0)[0]
    return x, exits, mass


# ---------------------------------------------------------------------------
# A stack whose layers are not all alike: the plan, the weights, the walk
# ---------------------------------------------------------------------------

class Group(NamedTuple):
    """A run of like layers, one entry of a stack's plan: its leaves are
    stacked under `lead` and the walk gives it one `lax.scan`."""

    key: str                 # the weights' key in `params`
    lead: Tuple[int, ...]    # (scan steps,) or (scan steps, layers a step)
    routed: bool             # a routed FFN, else a dense one

    @property
    def layers(self) -> int:
        return math.prod(self.lead)


def routed_layers(plan: Sequence[Group]) -> int:
    """Layers whose use of their experts `decode` reports."""
    return sum(group.layers for group in plan if group.routed)


def ffn_shapes(cfg: TransformerConfig, routed: bool, router_bias: bool
               ) -> Dict[str, Tuple[int, ...]]:
    """The FFN's leaves of one layer: a dense SwiGLU, or the router (with
    its selection bias where the architecture has one), the experts held
    and the shared experts."""
    d = cfg.d_model
    if not routed:
        f = cfg.d_ff
        return {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}
    E, f = cfg.moe_experts, cfg.expert_d_ff
    shapes = {"router": (d, cfg.router_experts), "w_gate": (E, d, f),
              "w_up": (E, d, f), "w_down": (E, f, d)}
    if router_bias:
        shapes["router_bias"] = (cfg.router_experts,)
    if cfg.moe_shared_experts:
        fs = f * cfg.moe_shared_experts
        shapes.update(shared_gate=(d, fs), shared_up=(d, fs),
                      shared_down=(fs, d))
    return shapes


def num_params(cfg: TransformerConfig, plan: Sequence[Group],
               layer_shapes) -> int:
    total = cfg.vocab_size * cfg.d_model * (1 if cfg.tie_embeddings else 2) \
        + cfg.d_model
    if cfg.ut_steps > 1:
        total += cfg.d_model + 1                    # the exit gate
    for group in plan:
        for shape in layer_shapes(cfg, group).values():
            if isinstance(shape, dict):     # one layer of a step's own
                total += group.lead[0] * sum(
                    math.prod(s) for s in shape.values())
            else:
                total += group.layers * math.prod(shape)
    return total


def init_params(cfg: TransformerConfig, key: jax.Array,
                plan: Sequence[Group], layer_shapes,
                draws=None) -> Dict[str, Any]:
    """Scaled-normal weights as `transformer.init_params` makes them:
    norm gains one, the selection bias zero, residual-branch outputs
    scaled down by the depth a token walks (`n_layers` x `ut_steps`); a
    looped configuration's `exit_gate` (w (D,), b ()) drawn as the rest,
    from a key folded out of `key` so that no other leaf's draw moves;
    `layer_shapes(cfg, group)` says the leaves
    of a layer of that group by name, each with its shape. Leaves that one layer of a scan
    step has and the step's others lack are a dict under a name of that
    layer's, stacked under the group's steps alone (a step hands the
    layer its slice as a scanned operand, which a product reads where it
    lies; cut out of a stack over the step's layers it was copied first,
    0.2 GB a leaf at 3 x 4096 x 8192). `draws`: {leaf: f(key, shape) -> float32}
    for a leaf drawn another way. Each leaf is drawn, scaled and
    cast in one expression, so under jit no float32 copy of a stacked
    leaf is kept. What a seed makes is pinned (tests/test_stacks.py): a
    key a group in plan order, of it a key a leaf in their names' order."""
    pd = cfg.param_dtype
    draws = draws or {}
    k_emb, k_head, k_layers = jax.random.split(key, 3)

    def normal(key, shape, scale):
        return (jax.random.normal(key, shape, dtype=jnp.float32)
                * scale).astype(pd)

    d = cfg.d_model
    params = {"embed": normal(k_emb, (cfg.vocab_size, d), 0.02),
              "final_norm": jnp.ones((d,), dtype=pd)}
    if not cfg.tie_embeddings:
        params["lm_head"] = normal(k_head, (d, cfg.vocab_size), 0.02)
    if cfg.ut_steps > 1:
        k_w, k_b = jax.random.split(jax.random.fold_in(key, cfg.ut_steps))
        params["exit_gate"] = {"w": normal(k_w, (d,), 0.02),
                               "b": normal(k_b, (), 0.02)}

    def draw(leaf, full, k):
        if leaf in draws:
            return draws[leaf](k, full).astype(pd)
        if leaf.endswith("norm"):
            return jnp.ones(full, dtype=pd)
        if leaf == "router_bias":
            return jnp.zeros(full, dtype=pd)
        if leaf in ("wo", "w_down", "shared_down"):
            return normal(k, full, 0.02 / math.sqrt(
                2 * cfg.n_layers * cfg.ut_steps))
        return normal(k, full, 0.02)

    for group, k_group in zip(plan, jax.random.split(k_layers, len(plan))):
        shapes = layer_shapes(cfg, group)
        leaves = {}
        for (leaf, shape), k in zip(
                sorted(shapes.items()),
                jax.random.split(k_group, len(shapes))):
            if isinstance(shape, dict):
                leaves[leaf] = {
                    sub: draw(sub, group.lead[:1] + its, ks)
                    for (sub, its), ks in zip(
                        sorted(shape.items()),
                        jax.random.split(k, len(shape)))}
            else:
                leaves[leaf] = draw(leaf, group.lead + shape, k)
        params[group.key] = leaves
    return params


def run(cfg: TransformerConfig, params, plan: Sequence[Group], x, layer_at,
        state, leaves_at=None):
    """x through every layer of `plan`: one `lax.scan` a group, a step's
    layers unrolled in its body (where the group's leaves have that
    axis; `leaves_at(i, weights, j)`: the j-th layer's leaves of a step's
    of group `i`, where not every leaf has it), `state` (the caches, or
    nothing) riding in the carry beside x.
    `layer_at(i, g, j)` hands back the layer at step `g` (a number the
    device counts) of group `i`, the `j`-th of its step: `layer(lp, x,
    experts_at, state) -> (x, state, routing stats or None, experts
    chosen (B*S, K) or None)`, `lp` its leaves, `experts_at` as
    `ffn_half` takes it. Returns (x, state, routing stats summed over
    layers, experts chosen: a tuple a group of arrays (steps, B*S, K),
    one a routed layer of a step)."""
    stats = jnp.zeros((routing_stats(cfg),), jnp.int32)
    chosen = []
    for i, group in enumerate(plan):
        stacked = params[group.key]
        # The expert matrices stay whole, every layer's groups in one
        # array, and are not scanned over: models/moe.grouped_experts.
        expert_w = {k: stacked[k].reshape((-1,) + stacked[k].shape[-2:])
                    for k in EXPERT_LEAVES} if group.routed else None
        if group.routed:
            stacked = {k: v for k, v in stacked.items()
                       if k not in EXPERT_LEAVES}
        steps, *unrolled = group.lead

        def body(carry, scanned, i=i, unrolled=unrolled, expert_w=expert_w):
            x, state, stats = carry
            weights, g = scanned
            experts = []
            for j in range(math.prod(unrolled)):
                if leaves_at is not None:
                    lp = leaves_at(i, weights, j)
                else:
                    lp = jax.tree.map(lambda a: a[j], weights) if unrolled \
                        else weights
                layer = layer_at(i, g, j)
                # The layer's place in its group, so its first expert.
                at = g * unrolled[0] + j if unrolled else g
                x, state, st, ex = layer(
                    lp, x, expert_w and (expert_w, at * cfg.moe_experts),
                    state)
                if st is not None:
                    stats = stats + st
                if ex is not None:
                    experts.append(ex)
            return (x, state, stats), tuple(experts)

        (x, state, stats), experts = lax.scan(
            body, (x, state, stats), (stacked, jnp.arange(steps)))
        chosen.append(experts)
    return x, state, stats, tuple(chosen)


def chosen_by_layer(chosen) -> List[jax.Array]:
    """`run`'s experts chosen, for one row of tokens (S,), in layer
    order: each (S, K)."""
    return [layer[g] for group in chosen if group
            for g in range(group[0].shape[0]) for layer in group]
