"""Selective state-space recurrence with a decay a (channel, state) pair
(the "S6" layer of Mamba-1: a state ``h`` [N, C] over C channels and N
state dims, ``h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c] + dt_t[c]
x_t[c] B_t[n]``, ``y_t[c] = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]``), in
the three forms a server needs (``ops/ssm.py`` holds the same three for
the layer whose decay is one scalar a head):

- ``mamba_step``: the recurrence once, one token a lane, in plain
  ``jax.numpy``: what the tests hold the other two to.
- ``mamba_scan``: a whole sequence (prefill, the uncached forward). The
  decay differs by channel AND state dim, so a chunk's outputs are no
  masked product of scalar decays as ``ssm_chunked``'s are, and a whole
  prompt's decays [S, N, C] in float32 would be 5.4 GB at 16,384 tokens:
  a Pallas TPU kernel walks the tokens in time blocks with the state of a
  tile of channels in registers, forming each token's decays from ``dt``
  as it goes. The channels lie folded over sublanes AND lanes, [8, C / 8],
  and the N state dims are a Python loop whose ``B_t[n]`` and ``C_t[n]``
  are SCALARS read from SMEM: every operation is a whole-vreg elementwise
  one, the sum over n is an accumulation, and nothing is broadcast along
  lanes or turned. Bound by the vector unit (an exponential and ~6
  operations a state element a token), not by bytes.
- ``mamba_update``: the same step on one layer of the slots' stacked
  state leaf [layers, B, N, C], in place. Plain XLA: a call moves 31 MB at
  48 slots (38 us at the chip's bandwidth) of a ~25 ms step, so what a
  kernel could save (XLA reads the new state once more for ``y``) is
  under 1 % of a step; a parked lane's state is bit for bit what it was.

Shapes: ``x`` and ``dt`` [B, S, C] (``dt`` after its softplus, float32),
``A`` [N, C] (negative), ``Bm`` and ``Cm`` [B, S, N], ``D`` [C]; a state
is [B, N, C] in float32: the N = 16 state dims in the sublanes and the
channels in the lanes (the chip lays a 16-minor array out badly). Decays
and every accumulation are float32.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

# Tokens one grid step of ``mamba_scan`` walks: its x, dt and y blocks are
# [T, 8, 128] float32 (128 KB each at 256, double-buffered) and its B and C
# scalars 2 x T x N words of SMEM.
_TIME_BLOCK = 256
_LANES = 128
_FOLD = 8  # sublanes a tile of channels is folded over


def mamba_step(state, x, dt, A, Bm, Cm, D) -> Tuple[jax.Array, jax.Array]:
    """One token a lane: ``state`` [B,N,C] float32, ``x`` and ``dt``
    [B,C], ``Bm`` and ``Cm`` [B,N]. Returns ``(y [B,C] in x's type, the
    new state)``."""
    dt, x32 = dt.astype(F32), x.astype(F32)
    keep = jnp.exp(dt[:, None, :] * A.astype(F32)[None])
    new = keep * state + (dt * x32)[:, None, :] * Bm.astype(F32)[:, :, None]
    y = (new * Cm.astype(F32)[:, :, None]).sum(1) + x32 * D.astype(F32)
    return y.astype(x.dtype), new


def mamba_update(states, layer, x, dt, A, Bm, Cm, D, live=None
                 ) -> Tuple[jax.Array, jax.Array]:
    """``mamba_step`` on layer ``layer`` of the stacked states [layers, B,
    N, C] float32, in place: returns ``(y [B,C] in x's type, the whole
    leaf with that layer's states stepped)``. ``live`` [B] bool names the
    lanes that count (None: all): a lane outside it is PARKED, its state
    is bit for bit what it was and its ``y`` zeros."""
    state = lax.dynamic_index_in_dim(states, layer, 0, keepdims=False)
    y, new = mamba_step(state, x, dt, A, Bm, Cm, D)
    if live is not None:
        new = jnp.where(live[:, None, None], new, state)
        y = jnp.where(live[:, None], y, jnp.zeros_like(y))
    return y, lax.dynamic_update_index_in_dim(states, new, layer, 0)


def mamba_scan(x, dt, A, Bm, Cm, D, state0: Optional[jax.Array] = None,
               valid: Optional[jax.Array] = None, *,
               time_block: int = _TIME_BLOCK
               ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a whole sequence; returns ``(y [B,S,C] in x's
    type, the state after the last token [B,N,C] float32)``.

    ``state0``: the state before the first token (zeros where None).
    ``valid`` [B, S] bool marks the tokens that count: where it is false
    ``dt`` is taken as 0, so the decay is 1 and nothing is added, and the
    state after a padded bucket IS the state after its last valid token
    (the outputs at such positions are junk nobody reads). A sequence the
    time block does not divide is padded the same way.

    One Pallas kernel: grid (B, channel tiles, time blocks), the time axis
    sequential with the tile's state resident in its output block; a tile
    is ``_FOLD`` x 128 channels (all of them where C / 8 is no multiple of
    128), a time block ``time_block`` tokens. Off the TPU it runs in the
    Pallas interpreter."""
    b, s, c = x.shape
    n = A.shape[0]
    if c % _FOLD:
        raise ValueError(f"mamba_scan needs channels a multiple of {_FOLD}")
    dt = dt.astype(F32)
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    t_blk = min(time_block, -(-s // 8) * 8)
    pad = -s % t_blk
    if pad:
        x, dt, Bm, Cm = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                         for a in (x, dt, Bm, Cm))
    sp = s + pad
    width = c // _FOLD  # lanes a folded channel row holds
    lanes = _LANES if width % _LANES == 0 else width

    def folded(a):  # [.., C] -> [.., 8, C / 8]
        return a.astype(F32).reshape(a.shape[:-1] + (_FOLD, width))

    first = (jnp.zeros((b, n, c), F32) if state0 is None
             else state0.astype(F32))

    def kernel(b_ref, c_ref, x_ref, dt_ref, a_ref, h0_ref, y_ref, h_ref):
        ti = pl.program_id(2)

        @pl.when(ti == 0)
        def _start():
            h_ref[...] = h0_ref[...]

        def token(t, h):
            x_t, dt_t = x_ref[t], dt_ref[t]  # [8, lanes]
            dtx = dt_t * x_t
            y = jnp.zeros_like(x_t)
            new = []
            for j in range(n):
                at = t * n + j
                h_j = jnp.exp(dt_t * a_ref[j]) * h[j] + dtx * b_ref[at]
                y = y + h_j * c_ref[at]
                new.append(h_j)
            y_ref[t] = y
            return tuple(new)

        h = lax.fori_loop(0, t_blk, token,
                          tuple(h_ref[j] for j in range(n)))
        for j in range(n):
            h_ref[j] = h[j]

    def rows(bi, ci, ti):
        return bi, ti, 0, ci

    def tile(bi, ci, ti):
        return bi, 0, 0, ci

    # a time block's B and C: t_blk x N scalars, flat
    smem = pl.BlockSpec((t_blk * n,),
                        lambda bi, ci, ti: (bi * (sp // t_blk) + ti,),
                        memory_space=pltpu.SMEM)
    rows_spec = pl.BlockSpec((None, t_blk, _FOLD, lanes), rows)
    state_spec = pl.BlockSpec((None, n, _FOLD, lanes), tile)
    y, last = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, sp, _FOLD, width), F32),
                   jax.ShapeDtypeStruct((b, n, _FOLD, width), F32)],
        grid=(b, width // lanes, sp // t_blk),
        in_specs=[smem, smem, rows_spec, rows_spec,
                  pl.BlockSpec((n, _FOLD, lanes),
                               lambda bi, ci, ti: (0, 0, ci)),
                  state_spec],
        out_specs=[rows_spec, state_spec],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="mamba_scan",
    )(Bm.astype(F32).reshape(-1), Cm.astype(F32).reshape(-1), folded(x),
      folded(dt), folded(A), folded(first))
    y = y.reshape(b, sp, c)[:, :s] + x[:, :s].astype(F32) * D.astype(F32)
    return y.astype(x.dtype), last.reshape(b, n, c)
