"""Gated delta rule with a decay a CHANNEL (the "KDA" linear-attention
layer): a state ``S`` [Dk, Dv] a head, for a token's key ``k`` and query
``q`` [Dk] (L2-normalised by the caller), value ``v`` [Dv], log-decay
``g`` [Dk] (<= 0, ``alpha = exp(g)``) and write strength ``beta``:

    S <- Diag(alpha_t) S_{t-1}
    S <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t = S^T q_t

(the published ``S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k
v^T``), in the three forms ``ops/ssm.py`` has for its scalar decay:

- ``kda_chunked``: a whole sequence in chunks (prefill, the uncached
  forward). A chunk's ``u_t = beta_t (v_t - S^T k_t)`` depend on each
  other through the keys before them in the chunk: ``(I + Diag(beta) A) U
  = Diag(beta) (V - K_g S_0)`` with ``A[t, i] = sum_d k_t[d] k_i[d]
  exp(G_t[d] - G_i[d])`` (i < t; ``G`` the decays' running sum inside the
  chunk), a unit-lower-triangular system a chunk a head (the delta rule's
  WY form). It is solved for every chunk at once, before the state is
  known (``U = U_v - W S_0``); between chunks a ``lax.scan`` carries the
  state. A decay a channel cannot leave ``A`` as one product of decayed
  keys: ``exp(-G_i)`` overflows float32 within a chunk where a channel
  forgets fast. So a chunk's rows go in sub-blocks of 16, each with its
  own reference point (the running sum where the block starts): a row's
  key is decayed from there to itself (<= 1), a column's key from itself
  to there (<= 1 for an earlier block; at most 16 tokens' decay undone
  inside the row's own block), and every factor stays finite.
- ``kda_step``: the recurrence once, a decode step's one token a lane, in
  plain ``jnp``: what the tests hold the other two to.
- ``kda_update``: the same step as the served path runs it, a Pallas TPU
  kernel over the slots' WHOLE stacked state leaf [layers, B, H, Dk, Dv],
  aliased to its output, the layer a prefetched scalar: each tile of
  states is read once and written once, and the decay, the read ``sum_k
  k S``, the update and ``o`` are formed while the tile is in fast
  memory, in ``kda_step``'s order. It takes ``alpha``, ``k``, ``q`` as
  rows over Dk and turns them to the columns a state's tile needs once
  a slot for all the tile's heads.

Shapes: ``q``, ``k``, ``g`` [B, S, H, Dk], ``v`` [B, S, H, Dv], ``beta``
[B, S, H] (``kda_step`` / ``kda_update``: no S); a state is [B, H, Dk, Dv]
in float32. Decays, running sums, the solve and every accumulation are
float32; the operands of the large products are in ``v``'s type (bf16 on
the chip, float32 in the tests). The chunked form is XLA's own fusions.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.ssm import live_tiles_first, tile_at

F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST

# What one grid step of ``kda_update`` reads of the states (and writes
# back), as ``ops/ssm.ssm_update``: 16 heads of 128 x 128 float32.
_TILE_BYTES = 2 ** 20
# Rows of a chunk that share a reference point, and the largest log-decay
# undone from it (e^80 is finite in float32 and in bf16; only entries the
# causal mask drops reach it).
_SUB_BLOCK = 16
_UNDO_MOST = 80.0


def _solve_unit_lower(low, rhs, sub: int):
    """``(I + low) X = rhs`` for strictly lower-triangular ``low`` [...,
    C, C] and ``rhs`` [..., C, N], float32: the diagonal sub-blocks of
    ``sub`` rows are inverted row by row (elementwise float32), then the
    blocks are substituted forward with true float32 products."""
    c = low.shape[-1]
    nb = c // sub
    lead = low.shape[:-2]
    blocks = low.reshape(lead + (nb, sub, nb, sub))
    diag = jnp.stack([blocks[..., b, :, b, :] for b in range(nb)], -3)
    eye = jnp.eye(sub, dtype=F32)

    def row(r, inv):  # inv's rows < r are final: row r of (I + D)^-1
        new = eye[r] - (diag[..., r, :, None] * inv).sum(-2)
        return inv.at[..., r, :].set(new)

    inv = lax.fori_loop(1, sub, row, jnp.broadcast_to(eye, diag.shape))
    rhs = rhs.reshape(lead + (nb, sub, rhs.shape[-1]))
    out = []
    for b in range(nb):
        r = rhs[..., b, :, :]
        for j in range(b):
            r = r - jnp.einsum("...ts,...sn->...tn", blocks[..., b, :, j, :],
                               out[j], precision=_HIGHEST)
        out.append(jnp.einsum("...ts,...sn->...tn", inv[..., b, :, :], r,
                              precision=_HIGHEST))
    return jnp.concatenate(out, -2)


def kda_chunked(q, k, v, g, beta, chunk: int,
                state0: Optional[jax.Array] = None,
                valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a whole sequence; returns ``(o [B,S,H,Dv] in
    v's type, the state after the last token [B,H,Dk,Dv] float32)``.

    ``state0``: the state before the first token (zeros where None).
    ``valid`` [B, S] bool marks the tokens that count: where it is false
    ``g`` and ``beta`` are taken as 0, so the token neither decays nor
    writes and the state after a padded bucket IS the state after its
    last valid token (the outputs there are junk nobody reads). A
    sequence that ``chunk`` does not divide is padded the same way."""
    b, s, h, dk = k.shape
    dv = v.shape[-1]
    g, beta = g.astype(F32), beta.astype(F32)
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    pad = -s % chunk
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    nc = (s + pad) // chunk
    sub = _SUB_BLOCK if chunk % _SUB_BLOCK == 0 else chunk
    nb = chunk // sub
    mm = v.dtype  # the large products' operands

    def heads(a):  # [b, S, h, x] -> [b, nc, h, C, x]
        return a.reshape(b, nc, chunk, h, -1).transpose(0, 1, 3, 2, 4)

    qc, kc, vc = (heads(a).astype(F32) for a in (q, k, v))
    bc = heads(beta[..., None])  # [b,nc,h,C,1]
    run = jnp.cumsum(heads(g), axis=-2)  # G: up to and including a token
    total = run[..., -1:, :]  # the chunk's whole log-decay [b,nc,h,1,dk]

    # a sub-block's reference point: G where the block starts
    blocked = run.reshape(b, nc, h, nb, sub, dk)
    ref = jnp.concatenate([jnp.zeros_like(blocked[..., :1, -1, :]),
                           blocked[..., :-1, -1, :]], -2)  # [b,nc,h,nb,dk]
    to_row = jnp.exp(blocked - ref[..., None, :])  # <= 1
    from_col = jnp.exp(jnp.minimum(
        ref[..., None, :] - run[..., None, :, :], _UNDO_MOST))  # [..,nb,C,dk]
    k_col = (kc[..., None, :, :] * from_col).astype(mm)

    def against_keys(x):  # sum_d x_t k_i exp(G_t - G_i): [b,nc,h,C,C]
        rows = (x.reshape(b, nc, h, nb, sub, dk) * to_row).astype(mm)
        return jnp.einsum("...ntd,...nid->...nti", rows, k_col,
                          preferred_element_type=F32).reshape(
                              b, nc, h, chunk, chunk)

    at = jnp.arange(chunk)
    a_kk = jnp.where(at[:, None] > at[None, :], against_keys(kc), 0.0)
    a_qk = jnp.where(at[:, None] >= at[None, :], against_keys(qc), 0.0)
    decayed = jnp.exp(run)  # from the chunk's start to each token
    solved = _solve_unit_lower(
        bc * a_kk, jnp.concatenate([bc * kc * decayed, bc * vc], -1), sub)
    w, u_v = solved[..., :dk].astype(mm), solved[..., dk:]
    q_in = (qc * decayed).astype(mm)  # what the carried state gives a query
    k_end = (kc * jnp.exp(total - run)).astype(mm)  # a write, at chunk's end
    a_qk = a_qk.astype(mm)

    def one_chunk(state, per_chunk):
        w, u_v, q_in, a_qk, k_end, keep = per_chunk
        carried = state.astype(mm)
        u = u_v - jnp.einsum("bhtk,bhkv->bhtv", w, carried,
                             preferred_element_type=F32)
        o = (jnp.einsum("bhtk,bhkv->bhtv", q_in, carried,
                        preferred_element_type=F32)
             + jnp.einsum("bhti,bhiv->bhtv", a_qk, u.astype(mm),
                          preferred_element_type=F32))
        state = keep[..., None] * state + jnp.einsum(
            "bhtk,bhtv->bhkv", k_end, u.astype(mm),
            preferred_element_type=F32)
        return state, o.astype(mm)

    first = (jnp.zeros((b, h, dk, dv), F32) if state0 is None
             else state0.astype(F32))
    last, o = lax.scan(one_chunk, first, tuple(
        jnp.moveaxis(a, 1, 0) for a in (
            w, u_v, q_in, a_qk, k_end, jnp.exp(total[..., 0, :]))))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, nc * chunk, h, dv)[:, :s]
    return o, last


def kda_step(state, q, k, v, g, beta) -> Tuple[jax.Array, jax.Array]:
    """One token a lane: ``state`` [B,H,Dk,Dv] float32, ``q``, ``k``, ``g``
    [B,H,Dk], ``v`` [B,H,Dv], ``beta`` [B,H]. Returns ``(o [B,H,Dv] in v's
    type, the new state)``. The plain form: elementwise float32 products
    and two sums over Dk; the served path runs ``kda_update``."""
    q32, k32, v32 = (a.astype(F32) for a in (q, k, v))
    state = jnp.exp(g.astype(F32))[..., None] * state
    read = (k32[..., None] * state).sum(-2)  # S^T k
    u = beta.astype(F32)[..., None] * (v32 - read)
    state = state + k32[..., None] * u[..., None, :]
    return (q32[..., None] * state).sum(-2).astype(v.dtype), state


def _divisor_at_most(n: int, most: int) -> int:
    return max(d for d in range(1, max(min(n, most), 1) + 1) if n % d == 0)


def kda_update(states, layer, q, k, v, g, beta, live=None, *,
               tile_bytes: int = _TILE_BYTES) -> Tuple[jax.Array, jax.Array]:
    """``kda_step`` on layer ``layer`` of the stacked states [layers, B,
    H, Dk, Dv] float32, in place: returns ``(o [B,H,Dv] in v's type, the
    whole leaf with that layer's states stepped)``. One Pallas kernel: the
    leaf is aliased to the output and a tile is indexed (layer, slots,
    heads) where it lies, so nothing slices a layer out and no other
    layer's bytes are touched. A lane outside ``live`` [B] bool (None:
    every lane is live) is PARKED: its state is bit for bit what it was
    and its ``o`` is zeros. A tile none of whose slots is live is neither
    read nor written and its body does not run (``ops/ssm.
    live_tiles_first``); a parked slot inside a live tile goes through
    with its decay taken as 1 and its ``beta`` as 0.

    A tile is ``tile_bytes`` of whole heads of one slot, over slots where
    a slot's heads are fewer. What a (slot, head) needs beside its state
    goes in as rows a whole number of lanes wide: ``alpha``, ``k`` and
    ``q`` over Dk, [B, 3, H, Dk], and ``v`` and ``beta`` over Dv, [B, 2,
    H, Dv]. A state's rows lie along the sublanes, so what is over Dk has
    to multiply it as COLUMNS: the kernel turns a slot's rows of a tile,
    [3 x heads, Dk], to [Dk, 3 x heads] ONCE for all the tile's heads (one
    transpose of 1/Dv of the data; a turn a head and a vector held the
    kernel at 57 % of its bytes' time where a plain copy reaches 77 %:
    PERF.md, PR 47), and a head takes its three columns out of that. Then
    ``kda_step``'s own float32 products and sums in ``kda_step``'s own
    order: ``decayed = alpha S``, ``read = sum_k k decayed``, ``u = beta
    (v - read)``, the new state ``decayed + k u^T``, ``o = sum_k q new``.
    Off the TPU the kernel runs in the Pallas interpreter, handed the one
    layer it touches (the interpreter copies every operand whole at every
    grid step)."""
    n_slots, h, dk = k.shape
    dv = v.shape[-1]
    q32, k32, v32 = (a.astype(F32) for a in (q, k, v))
    alpha, beta = jnp.exp(g.astype(F32)), beta.astype(F32)
    if live is not None:
        alpha = jnp.where(live[:, None, None], alpha, 1.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    over_k = jnp.stack([alpha, k32, q32], 1)  # [B,3,H,Dk]
    over_v = jnp.stack(
        [v32, jnp.broadcast_to(beta[..., None], v32.shape)], 1)  # [B,2,H,Dv]

    per = max(tile_bytes // (dk * dv * 4), 1)  # heads a tile
    th = _divisor_at_most(h, per)
    tb = _divisor_at_most(n_slots, per // th)
    order, tiles = live_tiles_first(live, n_slots, tb)

    def kernel(_layer, *refs):
        k_ref, v_ref, s_ref, o_ref, new_ref = refs[len(order):]
        for b in range(tb):
            # column c * th + e: the decay (c = 0), k, q of the tile's head e
            turned = k_ref[b].reshape(3 * th, dk).T  # [Dk, 3 th]
            for e in range(th):
                decay, key, query = (
                    turned[:, c * th + e:c * th + e + 1] for c in range(3))
                decayed = decay * s_ref[b, e]  # [Dk, Dv]
                read = (key * decayed).sum(0, keepdims=True)  # [1, Dv]
                u = v_ref[b, 1, e:e + 1] * (v_ref[b, 0, e:e + 1] - read)
                new = decayed + key * u
                new_ref[b, e] = new
                o_ref[b, e:e + 1, :] = (query * new).sum(0, keepdims=True)

    interpret = jax.default_backend() != "tpu"
    stack, at = states, layer
    if interpret:
        stack, at = lax.dynamic_index_in_dim(states, layer, 0), 0

    def tile(i, j, layer, *order):
        return layer[0], tile_at(i, *order), j, 0, 0

    def rows(i, j, layer, *order):
        return tile_at(i, *order), 0, j, 0

    def heads(i, j, layer, *order):
        return tile_at(i, *order), j, 0

    o, new = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n_slots, h, dv), F32),
                   jax.ShapeDtypeStruct(stack.shape, F32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(order),
            grid=(tiles, h // th),
            in_specs=[pl.BlockSpec((tb, 3, th, dk), rows),
                      pl.BlockSpec((tb, 2, th, dv), rows),
                      pl.BlockSpec((None, tb, th, dk, dv), tile)],
            out_specs=[pl.BlockSpec((tb, th, dv), heads),
                       pl.BlockSpec((None, tb, th, dk, dv), tile)],
        ),
        input_output_aliases={3 + len(order): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 2),
        interpret=interpret,
        name="kda_update",
    )(jnp.asarray(at, jnp.int32).reshape(1), *order, over_k, over_v, stack)
    if interpret:
        new = lax.dynamic_update_index_in_dim(states, new[0], layer, 0)
    if live is not None:  # an unvisited tile's o is whatever the buffer held
        o = jnp.where(live[:, None, None], o, 0.0)
    return o.astype(v.dtype), new
