"""Mamba-1's selective scan (arXiv:2312.00752): a diagonal recurrence a
channel and a state coordinate, `h` (N, C) float32 a layer a sequence (N
= d_state coordinates on the sublanes, C = d_inner channels on the
lanes), with a step `dt_t` (C,) > 0 the input chooses:

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * u_t) * B_t[:, None]
    y_t = sum_n h_t[n] * C_t[n]

`A` (N, C) < 0, `u_t` (C,) the convolved input, `B_t`, `C_t` (N,) what
the token writes and reads. N x C independent scalar recurrences with no
matrix form: vector-unit work in a tile, bytes in a decode step. What
stands around it a channel is here too, one definition each for every
walk: the step's bias and softplus (`step_size`) and the skip `D * u`
under the gate `silu(z)` (`gated`); a tile applies them in XLA, a decode
step in the kernel. Everything here is float32 whatever the activations
are.

One recurrence (`_advance`), two walks, as every cache has:

- `scan`: a tile from a carried state. On a TPU a kernel
  (`_scan_pallas`, `ssm_scan`): a grid step a (row, `_CHANNELS` channels,
  `_POSITIONS` positions), time innermost, the block's (N, channels)
  state resident in the output block it leaves in, `dt` and `u` streamed
  in, `y` streamed out, `B` and `C` turned outside to (N, 8) a group of 8
  positions so that a position's column is a static lane slice. A
  position at or past its row's length has `dt = 0`: the state passes
  through it as it came (exp(0) = 1, nothing written). Anywhere else a
  `lax.scan` a position.
- `decode_update`: one position a slot through layer `l` of the carried
  states (L, slots, N, C) and of the convolutions' tails (L, slots, K -
  1, C), both aliased in and out. On a TPU a kernel (`_update_pallas`,
  `ssm_update`): a grid step a slot a request owns (the work list
  `delta_rule._owned` makes on the device), and what the token does a
  channel once `u` is known happens in that step, beside the 655 KB of
  state it moves: the step's bias and softplus, the update, the skip
  and the gate written as the one row the output projection multiplies,
  the tail a position on. A state moves once in and once out, the rows
  come as XLA's products left them, and a slot nobody owns is neither
  read nor written. Anywhere else the same in XLA, such a slot keeping
  state and tail bit for bit.

XLA's own forms do not serve a tile at a serving width: an associative
scan materialises (S, C, N) float32, 671 MB an array a layer at 2,048
positions of 5,120 channels, and a `lax.scan` a position pays a launch a
position a layer.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _owned

_LANES = 128
_GROUP = 8               # positions whose B and C columns one load holds
_CHANNELS = (512, 256, 128)   # channels a grid step of the tile's scan holds
_POSITIONS = 256         # positions a grid step of the tile's scan walks


def _advance(h, dt, u, A, b, c):
    """One position of the recurrence: h (..., N, C); dt, u (..., 1, C);
    A (N, C); b, c (..., N, 1) -> (h', y (..., 1, C)), float32."""
    h = jnp.exp(dt * A) * h + (dt * u) * b
    return h, jnp.sum(h * c, axis=-2, keepdims=True)


def usable(n_state: int, channels: int) -> bool:
    """Whether the kernels run here: on a TPU, the state's coordinates in
    whole sublanes and its channels in whole lanes."""
    from .flash_attention import on_tpu

    return on_tpu() and n_state % 8 == 0 and channels % _LANES == 0


# ---------------------------------------------------------------------------
# A tile
# ---------------------------------------------------------------------------

def _scan_kernel(dt_ref, u_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, last_ref):
    """One grid step: `_POSITIONS` positions of one row's block of
    channels. `last_ref` (N, channels) stays where it is while the grid
    walks the row's positions: the state between two steps."""
    @pl.when(pl.program_id(2) == 0)
    def _start():
        last_ref[...] = h0_ref[...]

    A = a_ref[...]

    def group(i, h):
        at = pl.multiple_of(i * _GROUP, _GROUP)
        dt, u = dt_ref[pl.ds(at, _GROUP), :], u_ref[pl.ds(at, _GROUP), :]
        b, c = b_ref[i], c_ref[i]                        # (N, 8)
        for j in range(_GROUP):
            h, y = _advance(h, dt[j:j + 1], u[j:j + 1], A, b[:, j:j + 1],
                            c[:, j:j + 1])
            y_ref[pl.ds(at + j, 1), :] = y
        return h

    last_ref[...] = lax.fori_loop(0, dt_ref.shape[0] // _GROUP, group,
                                  last_ref[...])


def _scan_pallas(dt, u, Bm, Cm, A, state, interpret=False):
    """`scan` as a kernel; S a multiple of 8."""
    B, S, C = dt.shape
    N = A.shape[0]
    cb = next(c for c in _CHANNELS if C % c == 0)
    tc = next(t for t in (_POSITIONS, 128, 64, 32, 16, 8) if S % t == 0)

    def turned(x):              # (B, S, N) -> (B, S / 8, N, 8)
        return x.reshape(B, S // _GROUP, _GROUP, N).transpose(0, 1, 3, 2)

    streamed = pl.BlockSpec((None, tc, cb), lambda b, c, t: (b, t, c))
    columns = pl.BlockSpec((None, tc // _GROUP, N, _GROUP),
                           lambda b, c, t: (b, t, 0, 0))
    held = pl.BlockSpec((None, N, cb), lambda b, c, t: (b, 0, c))
    return pl.pallas_call(
        _scan_kernel,
        grid=(B, C // cb, S // tc),
        in_specs=[streamed, streamed, columns, columns,
                  pl.BlockSpec((N, cb), lambda b, c, t: (0, c)), held],
        out_specs=[streamed, held],
        out_shape=[jax.ShapeDtypeStruct((B, S, C), jnp.float32),
                   jax.ShapeDtypeStruct((B, N, C), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=bool(interpret),
        metadata={"kernel": "ssm_scan"},
    )(dt, u, turned(Bm), turned(Cm), A, state)


def scan(dt, u, Bm, Cm, A, lengths=None, state=None,
         interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """A tile through the recurrence: dt, u (B, S, C), Bm, Cm (B, S, N),
    A (N, C) -> (y (B, S, C) float32, the state behind each row's last
    real position (B, N, C) float32). `lengths` (B,): a position at or
    past its row's length changes no state (None: every position is
    real); what `y` holds there is padding. `state`: what the rows start
    from (None: zeros). `interpret` None: the kernel where `usable`, else
    a `lax.scan` a position; True: the kernel in the Pallas interpreter."""
    f32 = jnp.float32
    dt, u, Bm, Cm, A = (x.astype(f32) for x in (dt, u, Bm, Cm, A))
    B, S, C = dt.shape
    N = A.shape[0]
    if lengths is not None:
        dt = jnp.where((jnp.arange(S)[None, :] < lengths[:, None])[..., None],
                       dt, 0.0)
    if state is None:
        state = jnp.zeros((B, N, C), f32)
    if interpret is None and not usable(N, C):
        def one(h, xs):
            dt, u, b, c = xs
            h, y = _advance(h, dt[:, None], u[:, None], A, b[..., None],
                            c[..., None])
            return h, y[:, 0]

        last, y = lax.scan(one, state.astype(f32), tuple(
            jnp.moveaxis(x, 1, 0) for x in (dt, u, Bm, Cm)))
        return jnp.moveaxis(y, 0, 1), last
    pad = -S % _GROUP
    if pad:                     # dt = 0 behind the tile: nothing moves
        dt, u, Bm, Cm = (jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                         for x in (dt, u, Bm, Cm))
    y, last = _scan_pallas(dt, u, Bm, Cm, A, state.astype(f32),
                           bool(interpret))
    return y[:, :S], last


# ---------------------------------------------------------------------------
# One position a slot
# ---------------------------------------------------------------------------

def step_size(pre, bias):
    """The step the input chooses, `dt = softplus(pre + bias)` > 0: `pre`
    the step's projection, `bias` a channel; float32."""
    return jax.nn.softplus(pre + bias)


def gated(y, u, D, z):
    """What leaves the mixer for its output projection: the scan's `y`
    with the skip `D u`, under the gate `silu(z)`; float32."""
    return (y + D * u) * jax.nn.silu(z)


def _column(row, n: int):
    """row (1, n) -> (n, 1): a value a sublane from a value a lane, by a
    mask and a sum over lanes (a transpose would want a whole tile)."""
    at = lax.broadcasted_iota(jnp.int32, (n, n), 0) \
        == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(at, row, 0.0), axis=1, keepdims=True)


def _update_kernel(l_ref, n_ref, slot_ref, new_ref, pre_ref, u_ref, b_ref,
                   c_ref, z_ref, a_ref, bias_ref, d_ref, s_ref, tail_ref,
                   o_ref, out_ref, moved_ref):
    """One grid step: everything an owned slot's token does a channel
    once `u` is known. `new_ref`, `pre_ref`, `u_ref`, `z_ref` (1, C): the
    token's input, its step before the bias and the softplus, its
    convolved input and its gate; `b_ref`, `c_ref` (1, N) as they leave
    their norms; `tail_ref` (K - 1, C) the slot's last inputs, oldest
    first. `o_ref` lies where `new_ref`'s array does: a slot's row is
    read before it is written."""
    t = pl.program_id(0)
    held = tail_ref.shape[0]

    @pl.when(t < n_ref[0])
    def _update():
        N = a_ref.shape[0]
        new, u = new_ref[...], u_ref[...]
        out_ref[...], y = _advance(
            s_ref[...], step_size(pre_ref[...], bias_ref[...]), u,
            a_ref[...], _column(b_ref[...], N), _column(c_ref[...], N))
        o_ref[...] = gated(y, u, d_ref[...], z_ref[...]).astype(o_ref.dtype)
        moved_ref[:held - 1, :] = tail_ref[1:, :]
        moved_ref[held - 1:, :] = new

    @pl.when(t >= n_ref[0])
    def _nobody():
        # The one step a grid has when no slot is owned: as it was.
        out_ref[...] = s_ref[...]
        moved_ref[...] = tail_ref[...]
        o_ref[...] = new_ref[...]


def _update_pallas(states, tails, l, new, pre, bias, u, Bm, Cm, z, A, D,
                   live, interpret=False):
    _, B, N, C = states.shape
    held = tails.shape[2]
    _, slots, n = _owned(live, B)

    def own(t, l_ref, n_ref, slot_ref):
        return (slot_ref[t], 0, 0)

    def where_it_lies(t, l_ref, n_ref, slot_ref):
        return (l_ref[0], slot_ref[t], 0, 0)

    row = pl.BlockSpec((None, 1, C), own)
    column = pl.BlockSpec((None, 1, N), own)
    channel = pl.BlockSpec((1, C), lambda t, *_: (0, 0))
    state = pl.BlockSpec((None, None, N, C), where_it_lies)
    tail = pl.BlockSpec((None, None, held, C), where_it_lies)
    out, states, tails = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(jnp.maximum(n, 1),),
            in_specs=[row, row, row, column, column, row,
                      pl.BlockSpec((N, C), lambda t, *_: (0, 0)), channel,
                      channel, state, tail],
            out_specs=[row, state, tail]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, C), new.dtype),
                   jax.ShapeDtypeStruct(states.shape, states.dtype),
                   jax.ShapeDtypeStruct(tails.shape, tails.dtype)],
        # A slot nobody owns is given no step: its state and tail stay
        # what they were, and its row of the output, which lies where
        # `new` did, is the row of `new` it came with: finite, nobody's.
        input_output_aliases={3: 0, 12: 1, 13: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        metadata={"kernel": "ssm_update"},
    )(jnp.reshape(l, (1,)).astype(jnp.int32), jnp.reshape(n, (1,)), slots,
      new[:, None], pre[:, None], u[:, None], Bm[:, None], Cm[:, None],
      z[:, None], A, bias[None], D[None], states, tails)
    return out[:, 0], states, tails


def decode_update(states, tails, l, new, pre, bias, u, Bm, Cm, z, A, D,
                  live: Optional[jax.Array] = None,
                  interpret: Optional[bool] = None
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One position a slot through layer `l` of the carried `states` (L,
    B, N, C) float32 and `tails` (L, B, K - 1, C), a slot's last K - 1
    inputs oldest first: `new` (B, C) this token's input in the tails'
    dtype, `pre` (B, C) and `bias` (C,) its step before `step_size`, u
    (B, C) its convolved input (over the tail and `new`: the caller has
    it, the step's narrow products start from it), Bm, Cm (B, N), z (B,
    C) the gate, A (N, C), `D` (C,) the skip's -> (`gated(y, u, D, z)`
    (B, C) in `new`'s dtype, states', tails'): one update of the state,
    the skip and the gate, the tail a position on. A slot that is not
    `live` (B,) keeps the state and the tail it had, bit for bit (None:
    every slot is owned); its row of the output is nobody's and finite,
    the row of `new` it came with (the kernel writes the output where
    `new` lay and gives such a slot no step). All arithmetic float32.
    `interpret` as `scan` takes it."""
    f32 = jnp.float32
    pre, bias, u, Bm, Cm, z, A, D = (
        x.astype(f32) for x in (pre, bias, u, Bm, Cm, z, A, D))
    _, B, N, C = states.shape
    new = new.astype(tails.dtype)
    if interpret is not None or usable(N, C):
        return _update_pallas(states, tails, l, new, pre, bias, u, Bm, Cm, z,
                              A, D, live, bool(interpret))
    tail = lax.dynamic_index_in_dim(tails, l, 0, keepdims=False)
    h0 = lax.dynamic_index_in_dim(states, l, 0, keepdims=False)
    h, y = _advance(h0, step_size(pre, bias)[:, None], u[:, None], A,
                    Bm[..., None], Cm[..., None])
    out = gated(y[:, 0], u, D, z).astype(new.dtype)
    moved = jnp.concatenate([tail[:, 1:], new[:, None]], axis=1)
    if live is not None:
        h = jnp.where(live[:, None, None], h, h0)
        moved = jnp.where(live[:, None, None], moved, tail)
        out = jnp.where(live[:, None], out, new)
    return (out, lax.dynamic_update_slice(states, h[None], (l, 0, 0, 0)),
            lax.dynamic_update_slice(tails, moved[None], (l, 0, 0, 0)))
