"""The gated delta rule with a decay a channel (Kimi Linear's KDA,
arXiv:2510.26692): linear attention whose state a head, `S` (dk, dv)
float32, every token rewrites whole.

    S' = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T          o_t = S_t^T q_t

`g_t` (dk,) <= 0 is the log-decay a channel, `beta_t` in [0, 2] the
step (past 1: the transition's eigenvalue along k goes negative). The
caller normalises q and k and scales q.

Two walks, as every cache has:

- `chunk_scan`: a tile, C = 64 positions a step, the state carried
  between chunks. Inside a chunk the C updates are one triangular system
  (the WY form): with G the running sum of g inside the chunk,
  `A_ij = sum_d k_id k_jd exp(G_id - G_jd)` for j < i,
  `(I + diag(beta) A) U = diag(beta) (V - (K exp G) S_0)`, then
  `O = (Q exp G) S_0 + P U` with P as A but of q and with its diagonal,
  and `S_C = diag(exp G_C) S_0 + (K exp(G_C - G))^T U`. No exponent is
  ever positive (a single factorisation `exp(G_i) exp(-G_j)` overflows
  float32 from a decay of 1.4 a token on): a pair's decay is either
  summed a channel as it stands or is a product of two factors taken
  against a running sum between the two positions, each at most 1. The
  system is solved by blocks of 16, each diagonal block's inverse a
  product of four (I - N)(I + N^2)(I + N^4)(I + N^8), always in float32
  at the highest precision: an error in U stays in the state. Operands
  of the other products take q's dtype (bf16: one pass a product,
  float32 accumulated, the state rounded where it enters a product and
  nowhere else; float32: the highest precision).
  On a TPU a kernel (`_scan_pallas`, `kda_scan`): a grid step a chunk of
  a row through `_HEADS_A_STEP` heads, whose states stay in VMEM from
  the row's first chunk to its last; the chunk's (C, 8, d) blocks are cut
  out of q, k, v, g (B, S, H, d) where they lie and `o` is written
  there; a chunk wholly past its row's length gets no step's work and no
  loads. Inside a step `_SCAN_HEADS_ABREAST` heads are one array with the
  heads in front, so every operation is written once and the compiler
  has four independent chains to pack. A pair (i, j < i) belongs to the
  one width w, a power of two, at which i's block of w follows j's, and
  is factored against the running sum at the last row of j's block: six
  products of (C, d) by (d, C) a head make A and P, those of widths
  under 16 in float32 at the highest precision whatever the operands'
  dtype (they are the 16 x 16 diagonal blocks). The kernel is one jitted
  callee: every site of a program that calls it with equal avals (a
  kimi tile's five, a queue-side tile's rows) shares one trace and one
  lowering to Mosaic, and its body is ~1,100 lines of jaxpr (the four
  blocks of 16, the three squarings and the six widths are all that is
  unrolled). Anywhere else (off the TPU, a tile that is no whole number
  of chunks, heads that are not 128 x 128 in blocks of eight) XLA, a
  `lax.scan` step a chunk (`_chunk_scan_xla`): 16 x 16 diagonal blocks
  of A and P sum `exp(G_i - G_j)` a channel as it stands (j <= i), a
  block below them is factored against the running sum where its rows
  begin, and the right-hand side is [V, K exp G], the state multiplied
  in behind the solve. On a TPU v5e (my chip run, PR 59) a layer's walk
  of kimi's 4,096 positions with 2,900 real, 32 heads: XLA 6.51 ms, the
  kernel 2.33; solar's 2,048 / 1,300, 64 heads: 7.69 and 2.16; `o`
  within 3.3e-7 and the state within 4.6e-6 of the XLA walk.
- `decode_update`: one token a slot, layer `l` of the carried states
  (L, B, H, dk, dv) read and written where they lie, in float32
  throughout. On a TPU a kernel (`_update_pallas`): a grid step a slot a
  request owns and `_HEADS_A_STEP` heads, the states aliased in and out,
  so a state moves once in and once out and a slot that is not `live` is
  given no step. Anywhere else the same update in XLA, `_SLOTS_A_PASS`
  slots a pass, a slot that is not `live` keeping its state bit for bit.

PERF.md section 6 has what the chip measured: PR 46 of both walks, PR 59
of the tile's kernel and of what it costs a program's set-up.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK, BLOCK = 64, 16
_HI = lax.Precision.HIGHEST


def _mm(eq: str, a, b, dtype):
    """einsum of a and b as `dtype` operands, float32 out."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      precision=_HI if dtype == jnp.float32 else None,
                      preferred_element_type=jnp.float32)


def _decayed_products(x, k, G, dtype):
    """`sum_d x_id k_jd exp(G_id - G_jd)` for j <= i, zero above: x
    (n, ..., C, dk) (n stacked left operands), k, G (..., C, dk), G the
    running sum of log-decays, never increasing along C -> (n, ..., C, C)
    float32."""
    C = k.shape[-2]
    nb = C // BLOCK
    lead = k.shape[:-2]

    def blocks(a):
        return a.reshape(a.shape[:-2] + (nb, BLOCK, a.shape[-1]))

    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    # The diagonal blocks, every exponent as it stands.
    i, j = jnp.arange(BLOCK)[:, None], jnp.arange(BLOCK)[None, :]
    decay = jnp.exp(jnp.where(
        (j <= i)[..., None], Gb[..., :, None, :] - Gb[..., None, :, :],
        -jnp.inf))                                   # (..., nb, 16, 16, dk)
    kd = kb[..., None, :, :] * decay
    diag = jnp.sum(xb[..., :, None, :] * kd, axis=-1)    # (n, ..., nb, 16, 16)
    rows = []
    for a in range(nb):
        parts = []
        if a:
            # Against the running sum where block a begins: rows lose
            # what they decayed since, columns what was left to decay.
            ref = Gb[..., a - 1, -1:, :]                         # (..., 1, dk)
            left = xb[..., a, :, :] * jnp.exp(Gb[..., a, :, :] - ref)
            right = k[..., :a * BLOCK, :] * jnp.exp(
                ref - G[..., :a * BLOCK, :])
            parts.append(_mm("...id,...jd->...ij", left,
                             jnp.broadcast_to(right, x.shape[:1] + right.shape),
                             dtype))
        parts.append(diag[..., a, :, :])
        if a < nb - 1:
            parts.append(jnp.zeros(
                x.shape[:1] + lead + (BLOCK, C - (a + 1) * BLOCK),
                jnp.float32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _solve_unit_lower(M, rhs):
    """X with M X = rhs for M (..., C, C) unit lower triangular (what
    lies on or above its diagonal is not read), rhs (..., C, n): forward
    substitution by blocks of 16, float32 at the highest precision."""
    C = M.shape[-1]
    nb = C // BLOCK
    eye = jnp.eye(BLOCK, dtype=jnp.float32)
    strict = jnp.tril(jnp.ones((BLOCK, BLOCK), bool), -1)

    def mm(a, b):
        return jnp.einsum("...ij,...jk->...ik", a, b, precision=_HI)

    out = []
    for a in range(nb):
        lo, hi = a * BLOCK, (a + 1) * BLOCK
        N = jnp.where(strict, M[..., lo:hi, lo:hi], 0.0)
        inv, power = eye - N, N
        for _ in range(3):                   # N^16 = 0
            power = mm(power, power)
            inv = mm(inv, eye + power)
        r = rhs[..., lo:hi, :]
        if a:
            r = r - mm(M[..., lo:hi, :lo], jnp.concatenate(out, axis=-2))
        out.append(mm(inv, r))
    return jnp.concatenate(out, axis=-2)


def chunk_scan(q, k, v, g, beta, lengths=None, state=None
               ) -> Tuple[jax.Array, jax.Array]:
    """A tile through the recurrence: q, k (B, S, H, dk) and v (B, S, H,
    dv) in the products' dtype, g (B, S, H, dk) and beta (B, S, H)
    float32 -> (o (B, S, H, dv) float32, the state behind each row's
    last real position (B, H, dk, dv) float32). `lengths` (B,): a
    position at or past its row's length changes no state (None: every
    position is real); what `o` holds there is padding. `state`: what
    the rows start from (None: zeros). On a TPU, where `scan_usable`,
    the kernel `kda_scan`; anywhere else `_chunk_scan_xla`."""
    if scan_usable(q, k, v):
        B, S, H, dk = k.shape
        # Every call site of a program hands the one jitted callee equal
        # avals: the kernel is traced once a process a shape and lowered
        # once a program.
        return _scan_pallas(
            q, k, v, g, beta,
            jnp.full((B,), S, jnp.int32) if lengths is None else lengths,
            jnp.zeros((B, H, dk, dk), jnp.float32) if state is None
            else state)
    return _chunk_scan_xla(q, k, v, g, beta, lengths, state)


def _chunk_scan_xla(q, k, v, g, beta, lengths=None, state=None
                    ) -> Tuple[jax.Array, jax.Array]:
    """`chunk_scan` in XLA, a `lax.scan` step a chunk: the path off the
    TPU and at the shapes the kernel does not take, and what the kernel's
    tests compare it with."""
    B, S, H, dk = k.shape
    dv = v.shape[-1]
    dtype = q.dtype
    C = CHUNK if S >= CHUNK else -(-S // BLOCK) * BLOCK
    pad = -S % C
    real = jnp.ones((B, S), bool) if lengths is None \
        else jnp.arange(S)[None, :] < lengths[:, None]
    g = jnp.where(real[..., None, None], g.astype(jnp.float32), 0.0)
    beta = jnp.where(real[..., None], beta.astype(jnp.float32), 0.0)

    def chunks(a):                       # (B, S, H, ...) -> (N, B, H, C, ...)
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((B, (S + pad) // C, C) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 1), 2, 0)

    def one(S0, xs):
        q, k, v, g, beta = xs            # (B, H, C, .) a chunk
        q, k = q.astype(jnp.float32), k.astype(jnp.float32)
        G = jnp.cumsum(g, axis=-2)
        both = _decayed_products(jnp.stack([k, q]), k, G, dtype)
        A, P = both[0], both[1]
        eG = jnp.exp(G)
        rhs = beta[..., None] * jnp.concatenate(
            [v.astype(jnp.float32), k * eG], axis=-1)
        solved = _solve_unit_lower(beta[..., None] * A, rhs)
        U = solved[..., :dv] - _mm("...ck,...kv->...cv", solved[..., dv:],
                                   S0, dtype)
        o = _mm("...ck,...kv->...cv", q * eG, S0, dtype) \
            + _mm("...ij,...jv->...iv", P, U, dtype)
        last = G[..., -1:, :]
        S1 = jnp.exp(last)[..., 0, :, None] * S0 + _mm(
            "...ck,...cv->...kv", k * jnp.exp(last - G), U, dtype)
        return S1, o

    if state is None:
        state = jnp.zeros((B, H, dk, dv), jnp.float32)
    state, o = lax.scan(one, state, (
        chunks(q), chunks(k), chunks(v), chunks(g),
        chunks(beta[..., None])[..., 0]))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)      # (B, N, C, H, dv)
    return o.reshape(B, S + pad, H, dv)[:, :S], state


def step(S, q, k, v, g, beta) -> Tuple[jax.Array, jax.Array]:
    """One token: S (..., dk, dv) float32; q, k, g (..., dk); v (...,
    dv); beta (...,), all float32 -> (o (..., dv), S')."""
    S = S * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - jnp.sum(k[..., None] * S, axis=-2))
    S = S + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * S, axis=-2), S


_SLOTS_A_PASS = 16
_HEADS_A_STEP = 8        # heads whose states one grid step of the kernel holds
_LANES = 128


def usable(states: jax.Array) -> bool:
    """Whether `decode_update` runs its kernel here: on a TPU, float32
    states of 128 x 128 a head, the heads in whole steps."""
    from .flash_attention import on_tpu

    _, _, H, dk, dv = states.shape
    return (on_tpu() and states.dtype == jnp.float32
            and dk == dv == _LANES and H % _HEADS_A_STEP == 0)


def _update_kernel(l_ref, n_ref, slot_ref, cols_ref, vb_ref, s_ref,
                   o_ref, out_ref, pad_ref, *, hb, steps_a_slot):
    """One grid step: `hb` heads' states of one owned slot. `cols_ref`
    (4 hb, dk): the rows exp(g), k, beta k and q of those heads, which
    the update needs as columns (a value a row of a state): they are
    turned once, as one 128 x 128 tile."""
    t = pl.program_id(0)

    @pl.when(t // steps_a_slot < n_ref[0])
    def _update():
        pad_ref[:4 * hb, :] = cols_ref[...]
        cols = pad_ref[...].T                        # (dk, 128)
        for j in range(hb):
            S = s_ref[j] * cols[:, j:j + 1]                       # decayed
            seen = jnp.sum(S * cols[:, 2 * hb + j:2 * hb + j + 1], axis=0,
                           keepdims=True)                         # (1, dv)
            S = S + cols[:, hb + j:hb + j + 1] * (vb_ref[j:j + 1, :] - seen)
            out_ref[j] = S
            o_ref[j:j + 1, :] = jnp.sum(
                S * cols[:, 3 * hb + j:3 * hb + j + 1], axis=0,
                keepdims=True)

    @pl.when(t // steps_a_slot >= n_ref[0])
    def _nobody():
        # The one step a grid has when no slot is owned: as it was.
        out_ref[...] = s_ref[...]
        o_ref[...] = jnp.zeros_like(o_ref)


def _owned(live, B: int):
    """(live (B,) bool, the slots a request owns first and in order (B,)
    int32, how many they are): a kernel's work list, which gives a slot
    nobody owns no step."""
    if live is None:
        live = jnp.ones((B,), bool)
    return live, jnp.argsort(~live, stable=True).astype(jnp.int32), \
        jnp.sum(live).astype(jnp.int32)


def _tails_kernel(l_ref, n_ref, slot_ref, new_ref, tail_ref, out_ref):
    t = pl.program_id(0)
    held = tail_ref.shape[0]

    @pl.when(t < n_ref[0])
    def _move():
        out_ref[:held - 1, :] = tail_ref[1:, :]
        out_ref[held - 1:, :] = new_ref[...]

    @pl.when(t >= n_ref[0])
    def _nobody():
        out_ref[...] = tail_ref[...]


def move_tails(tails, l, new, live: Optional[jax.Array] = None,
               interpret: Optional[bool] = None) -> jax.Array:
    """The short convolutions' tails a position on: layer `l` of `tails`
    (L, B, held, C), a slot's last `held` inputs oldest first, loses each
    owned slot's oldest and takes `new` (B, 1, C) behind the rest; a slot
    that is not `live` keeps its tail bit for bit. On a TPU (`interpret`
    None; True: in the Pallas interpreter) a kernel, a grid step an owned
    slot, the tails aliased in and out: XLA's update of the layer's slice
    inside the carried array was rematerialised around every use of the
    array (0.17 GB copied twice a layer a step at 96 slots) and, fused
    with the select by `live`, streamed other tokens than the reference's
    (my chip runs, PR 46). Anywhere else that update in XLA."""
    L, B, held, C = tails.shape
    new = new.astype(tails.dtype)
    if interpret is None:
        from .flash_attention import on_tpu
        interpret = False if on_tpu() and C % _LANES == 0 else None
    if interpret is None:
        tail = lax.dynamic_index_in_dim(tails, l, 0, keepdims=False)
        moved = jnp.concatenate([tail[:, 1:], new], axis=1)
        if live is not None:
            moved = jnp.where(live[:, None, None], moved, tail)
        return lax.dynamic_update_slice(tails, moved[None], (l, 0, 0, 0))
    _, slots, n = _owned(live, B)

    def own(t, l_ref, n_ref, slot_ref):
        return (l_ref[0], slot_ref[t], 0, 0)

    return pl.pallas_call(
        _tails_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n, 1),),
            in_specs=[
                pl.BlockSpec((None, 1, C),
                             lambda t, l_ref, n_ref, slot_ref: (
                                 slot_ref[t], 0, 0)),
                pl.BlockSpec((None, None, held, C), own)],
            out_specs=pl.BlockSpec((None, None, held, C), own)),
        out_shape=jax.ShapeDtypeStruct(tails.shape, tails.dtype),
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        metadata={"kernel": "kda_tails"},
    )(jnp.reshape(l, (1,)).astype(jnp.int32), jnp.reshape(n, (1,)), slots,
      new, tails)


def _update_pallas(states, l, q, k, v, g, beta, live, interpret=False):
    """`decode_update` as a kernel: a grid step a (slot a request owns,
    `_HEADS_A_STEP` heads), the states aliased in and out, so a state is
    read once and written once where it lies and a slot nobody owns is
    given no step: neither read nor written."""
    _, B, H, dk, dv = states.shape
    hb = _HEADS_A_STEP
    nh = H // hb
    f32 = jnp.float32
    live, slots, n = _owned(live, B)
    q, k, g = (a.astype(f32) for a in (q, k, g))
    beta = beta.astype(f32)[..., None]
    cols = jnp.stack([jnp.exp(g), k, beta * k, q], axis=1)     # (B, 4, H, dk)
    cols = cols.reshape(B, 4, nh, hb, dk).transpose(0, 2, 1, 3, 4) \
        .reshape(B, nh, 4 * hb, dk)
    vb = beta * v.astype(f32)

    def own(t, l_ref, n_ref, slot_ref):
        return (slot_ref[t // nh], t % nh, 0)

    o, states = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb, steps_a_slot=nh),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n, 1) * nh,),
            in_specs=[
                pl.BlockSpec((None, None, 4 * hb, dk),
                             lambda t, l_ref, n_ref, slot_ref: (
                                 slot_ref[t // nh], t % nh, 0, 0)),
                pl.BlockSpec((None, hb, dv), own),
                pl.BlockSpec((None, None, hb, dk, dv),
                             lambda t, l_ref, n_ref, slot_ref: (
                                 l_ref[0], slot_ref[t // nh], t % nh, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, hb, dv), own),
                pl.BlockSpec((None, None, hb, dk, dv),
                             lambda t, l_ref, n_ref, slot_ref: (
                                 l_ref[0], slot_ref[t // nh], t % nh, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((_LANES, dk), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(states.shape, f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        metadata={"kernel": "kda_update"},
    )(jnp.reshape(l, (1,)).astype(jnp.int32), jnp.reshape(n, (1,)), slots,
      cols, vb, states)
    # A slot nobody owns was given no step: its rows of `o` were never
    # written.
    return jnp.where(live[:, None, None], o, 0.0), states


def decode_update(states, l, q, k, v, g, beta,
                  live: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """One token a slot against layer `l` of the carried `states` (L, B,
    H, dk, dv) float32: q, k, g (B, H, dk), v (B, H, dv), beta (B, H)
    -> (o (B, H, dv) float32, states'). A slot that is not `live` (B,)
    keeps the state it had, bit for bit (None: every slot is owned); its
    `o` is nobody's. The slots are worked off `_SLOTS_A_PASS` at a time,
    each pass's states cut out of the carried array and put back where
    they lay: what a step holds beside the states is a pass's copies, not
    the layer's (96 slots x 64 heads x 128 x 128: 0.4 GB a copy)."""
    if usable(states):
        return _update_pallas(states, l, q, k, v, g, beta, live)
    _, B, H, dk, dv = states.shape
    f32 = jnp.float32
    n = max(d for d in range(1, min(B, _SLOTS_A_PASS) + 1) if B % d == 0)
    operands = tuple(a.astype(f32) for a in (q, k, v, g, beta))

    def one_pass(i, carry):
        states, o = carry
        b0 = i * n
        S = lax.dynamic_slice(states, (l, b0, 0, 0, 0),
                              (1, n, H, dk, dv))[0]
        out, S1 = step(S, *(lax.dynamic_slice_in_dim(a, b0, n, 0)
                            for a in operands))
        if live is not None:
            owned = lax.dynamic_slice_in_dim(live, b0, n, 0)
            S1 = jnp.where(owned[:, None, None, None], S1, S)
        return (lax.dynamic_update_slice(states, S1[None], (l, b0, 0, 0, 0)),
                lax.dynamic_update_slice_in_dim(o, out, b0, 0))

    states, o = lax.fori_loop(0, B // n, one_pass,
                              (states, jnp.zeros((B, H, dv), f32)))
    return o, states


# ---------------------------------------------------------------------------
# The tile's walk as a kernel
# ---------------------------------------------------------------------------

def scan_usable(q, k, v) -> bool:
    """Whether `chunk_scan` runs its kernel here: on a TPU, heads of 128
    x 128 in whole grid steps, whole chunks, float32 or bf16 operands."""
    from .flash_attention import on_tpu

    _, S, H, dk = k.shape
    return (on_tpu() and dk == v.shape[-1] == _LANES and 0 < S
            and S % CHUNK == 0 and q.dtype == k.dtype == v.dtype
            and q.dtype in (jnp.float32, jnp.bfloat16)
            and H % _HEADS_A_STEP == 0)


def scan_chunks(positions: int, lengths) -> Tuple[int, int]:
    """(chunks the walk of a tile of `positions` runs where a chunk
    wholly past its row's last token is skipped, each row to the chunk
    that holds the last of its `lengths` tokens; chunks its rows span):
    host arithmetic, for a counter."""
    of = -(-positions // CHUNK)
    return sum(min(-(-n // CHUNK), of) for n in lengths), of * len(lengths)


_SCAN_HEADS_ABREAST = 4     # heads a pass of the kernel's loop works as one


def _scan_kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref,
                 o_ref, s_ref, st_scr, x_scr, u_scr, *, hb, dtype):
    """One grid step: a chunk of `CHUNK` positions of one row through
    `hb` heads, `_SCAN_HEADS_ABREAST` of them a pass of one `fori_loop`,
    every array of a pass with those heads in front (hh, ...). `st_scr`
    (hb, dv, dk): the heads' states, transposed (a decay a channel scales
    lanes), from the row's first chunk to its last; `x_scr`, `u_scr` (hh,
    C, dv): the system's right-hand side and its solution, a block of 16
    rows at a time."""
    b, h0, n = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    C, hh, f32 = CHUNK, _SCAN_HEADS_ABREAST, jnp.float32
    length = len_ref[b]

    def turned(src, dst):
        def one(j, _):
            dst[j] = src[j].T
        lax.fori_loop(0, hb, one, None)

    @pl.when(n == 0)
    def _start():
        turned(s0_ref, st_scr)

    @pl.when(n * C >= length)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n * C < length)
    def _chunk():
        def iota(shape, d):
            return lax.broadcasted_iota(jnp.int32, shape, d)

        row, col = iota((C, C), 0), iota((C, C), 1)
        at = iota((C, _LANES), 0)
        real = n * C + iota((C, 1), 0) < length
        eye = (row == col).astype(f32)
        same = row // BLOCK == col // BLOCK
        mine = iota(beta_ref.shape, 1) - h0 * hb

        def down(x, by):            # row i of a head takes row i - by
            return pltpu.roll(x, by % C, 1)

        def turn(G, w):
            """G at the row where a row's pair of blocks of w turns from
            the first block to the second: row (i // 2w) 2w + w - 1 for
            row i."""
            if w >= 4:              # whole tiles of 8 rows: a broadcast
                pairs = G.reshape(hh, C // (2 * w), 2 * w, _LANES)
                return jnp.broadcast_to(
                    pairs[:, :, w - 1:w, :], pairs.shape).reshape(G.shape)
            if w == 2:
                return jnp.where(
                    at & 3 == 0, down(G, -1), jnp.where(
                        at & 3 == 1, G, jnp.where(
                            at & 3 == 2, down(G, 1), down(G, 2))))
            return jnp.where(at & 1 == 1, down(G, 1), G)

        def heads(i, _):
            j0 = i * hh

            def cut(ref):                 # hh heads' (C, dk) of (C, hb, dk)
                return jnp.stack([
                    ref.reshape(C * hb, _LANES)[pl.ds(j0 + u, C, stride=hb), :]
                    for u in range(hh)])

            q, k, v, g = (cut(r) for r in (q_ref, k_ref, v_ref, g_ref))
            beta = jnp.where(real, jnp.stack([jnp.sum(
                jnp.where(mine == j0 + u, beta_ref[...], 0.0), axis=1,
                keepdims=True) for u in range(hh)]), 0.0)       # (hh, C, 1)
            G = jnp.where(real, g, 0.0)
            for by in (1, 2, 4, 8, 16, 32):      # the running sum
                G = G + jnp.where(at >= by, down(G, by), 0.0)

            # A (k against k, below the diagonal) and P (q against k, the
            # diagonal too). The pair (i, j < i) belongs to the one width
            # w, a power of two, at which i's block of w follows j's, and
            # is decayed through the running sum at the last row of j's
            # block: rows lose what they decayed since, columns what was
            # left to decay, so no exponent is ever positive. Widths
            # under 16 are the 16 x 16 diagonal blocks: float32 whatever
            # the operands' dtype.
            A = jnp.zeros((hh, C, C), f32)
            P = eye * jnp.sum(q * k, axis=-1, keepdims=True)
            for w in (32, 16, 8, 4, 2, 1):
                decay = jnp.exp(-jnp.abs(G - turn(G, w)))
                kd, qd = k * decay, q * decay
                # The rows of a block that follows one: k's where they
                # lie, q's in the rows of the block before.
                both = _mm("hid,hjd->hij",
                           jnp.where((at // w) % 2 == 1, kd, down(qd, -w)),
                           kd, dtype if w >= BLOCK else f32)
                here = ((row // w) % 2 == 1) & (col // w == row // w - 1)
                A = jnp.where(here, both, A)
                P = jnp.where(here, down(both, w), P)

            # (I + diag(beta) A) U = diag(beta) (V - (K exp G) S_0): the
            # diagonal blocks' inverses at once (a product of
            # block-diagonal matrices is their blocks' products), then
            # forward by blocks of 16.
            M = beta * A
            power = jnp.where(same, M, 0.0)
            inv = eye - power
            for _ in range(3):                   # N^16 = 0
                power = _mm("hij,hjk->hik", power, power, f32)
                inv = _mm("hij,hjk->hik", inv, eye + power, f32)
            ST = st_scr[pl.ds(j0, hh)]                        # (hh, dv, dk)
            eG = jnp.exp(G)
            seen = _mm("hid,hvd->hiv",
                       jnp.concatenate([k * eG, q * eG], axis=1), ST,
                       dtype)                                 # (hh, 2 C, dv)
            x_scr[...] = beta * (v - seen[:, :C])
            u_scr[...] = jnp.zeros_like(u_scr)
            for lo in range(0, C, BLOCK):
                rows = slice(lo, lo + BLOCK)
                if lo:
                    x_scr[:, rows, :] -= _mm("hij,hjv->hiv", M[:, rows],
                                             u_scr[...], f32)
                # Off its diagonal blocks `inv` is zeros: of x only these
                # rows are read.
                u_scr[:, rows, :] = _mm("hij,hjv->hiv", inv[:, rows],
                                        x_scr[...], f32)
            U = u_scr[...]
            o = seen[:, C:] + _mm("hij,hjv->hiv", P, U, dtype)
            for u in range(hh):
                o_ref[:, j0 + u, :] = o[u]
            last = G[:, C - 1:, :]
            st_scr[pl.ds(j0, hh)] = jnp.exp(last) * ST + _mm(
                "hcv,hcd->hvd", U, k * jnp.exp(last - G), dtype)

        lax.fori_loop(0, hb // hh, heads, None)

    @pl.when(n == pl.num_programs(2) - 1)
    def _end():
        turned(st_scr, s_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _scan_pallas(q, k, v, g, beta, lengths, state, interpret: bool = False):
    """`chunk_scan` as a kernel: a grid step a (row, `_HEADS_A_STEP`
    heads, chunk), the chunk axis innermost and in order. The heads'
    states stay in VMEM from a row's first chunk to its last; a chunk's
    (C, heads, d) blocks are cut out of the tile where it lies and `o`
    is written there; a chunk wholly past its row's length is given no
    work and no loads (its blocks are the last real chunk's, which are
    not fetched again), its `o` zeros. The tile enters as float32 (a
    position's eight heads are then one (8, 128) tile, and a head's rows
    one strided load): bf16 operands are widened where they are made and
    rounded again where they enter a product. Jitted, so that the sites
    of a program that call it with equal avals share one trace and one
    lowering."""
    hb, hh = _HEADS_A_STEP, _SCAN_HEADS_ABREAST
    B, S, H, dk = k.shape
    f32 = jnp.float32
    lengths = jnp.clip(lengths.astype(jnp.int32), 0, S)

    def chunk(b, h, n, len_ref):
        # The last chunk that holds a real position, for those behind it.
        return (b, jnp.minimum(n, jnp.maximum(
            (len_ref[b] + CHUNK - 1) // CHUNK - 1, 0)), h, 0)

    def heads(b, h, n, len_ref):
        return (b, h, 0, 0)

    tile = pl.BlockSpec((1, CHUNK, hb, dk), chunk)
    return pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, dtype=q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H // hb, S // CHUNK),
            in_specs=[tile, tile, tile, tile,
                      pl.BlockSpec((None, CHUNK, H),
                                   lambda b, h, n, len_ref:
                                   chunk(b, 0, n, len_ref)[:3]),
                      pl.BlockSpec((None, hb, dk, dk), heads)],
            out_specs=[pl.BlockSpec((None, CHUNK, hb, dk),
                                    lambda b, h, n, len_ref: (b, n, h, 0)),
                       pl.BlockSpec((None, hb, dk, dk), heads)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dk), f32),
                            pltpu.VMEM((hh, CHUNK, dk), f32),
                            pltpu.VMEM((hh, CHUNK, dk), f32)]),
        out_shape=[jax.ShapeDtypeStruct((B, S, H, dk), f32),
                   jax.ShapeDtypeStruct((B, H, dk, dk), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata={"kernel": "kda_scan"},
    )(lengths, q.astype(f32), k.astype(f32), v.astype(f32), g.astype(f32),
      beta.astype(f32), state.astype(f32))
