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
  WY form), solved before the state is known (``U = U_v - W S_0``). A
  decay a channel cannot leave ``A`` as one product of decayed keys:
  ``exp(-G_i)`` overflows float32 within a chunk where a channel forgets
  fast. So a chunk's rows go in sub-blocks of 16, each with its own
  reference point (the running sum where the block starts): a row's key
  is decayed from there to itself (<= 1), a column's key from itself to
  there (<= 1 for an earlier block; at most 16 tokens' decay undone inside
  the row's own block), and every factor stays finite. One Pallas TPU
  kernel over (sequence, heads, chunks): a chunk's intermediates never
  leave fast memory and the state is carried there from chunk to chunk;
  q, k, v, g, beta are read once where they lie and o is written once.
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
the chip, float32 in the tests).
"""

from __future__ import annotations

import functools
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
# Heads a grid step of ``kda_chunked`` where a head is whole lanes wide.
_HEADS_A_STEP = 4


def _chunk_of_heads(states, q, k, v, g, beta, sub: int):
    """One chunk of a grid step's heads inside the kernel, as lists over
    the heads: ``states`` [Dk, Dv] float32 before the chunk, ``q``, ``k``
    [C, Dk] and ``v`` [C, Dv] in the products' type, ``g`` [C, Dk]
    float32, ``beta`` [C, 1] float32. Returns (the heads' o [C, Dv]
    float32, their states after the chunk). Every stage runs over all the
    heads before the next one starts: a head's stages wait for each other
    (the MXU's results, the solve's chain) and the other heads' work is
    what fills the wait."""
    heads = range(len(k))
    c, dk = k[0].shape
    mm, nb = v[0].dtype, c // sub
    exact = _HIGHEST if mm == F32 else None  # the tests' float32 operands

    def dot(x, y, dims=((1,), (0,)), precision=exact):
        return lax.dot_general(x, y, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)

    def iota(shape, axis):
        return lax.broadcasted_iota(jnp.int32, shape, axis)

    def block_of(i):  # the sub-block of a row or a column (i >= 0)
        return lax.div(i, jnp.int32(sub))

    def masked(mask, x):  # x where the mask holds, 0 elsewhere
        return lax.select(mask, x, jnp.zeros_like(x))

    # the index masks are formed once, with ``lax``'s own division and
    # remainder: every operation here is traced and lowered for each
    # admission program before the compile cache is asked (PERF.md, PR 52)
    at, to = iota((c, c), 0), iota((c, c), 1)
    before, upto = at > to, at >= to
    earlier = block_of(at) > block_of(to)  # a column of an earlier block
    ones = upto.astype(jnp.bfloat16)
    qc, kc, vc, run, decayed, low, a_qk, rhs = ([] for _ in range(8))
    for e in heads:
        qc.append(q[e].astype(F32))
        kc.append(k[e].astype(F32))
        vc.append(v[e].astype(F32))
        # G, the decays' sum up to and including a token, in float32: g's
        # three bf16 parts hold it whole, the ones are exact and the MXU
        # adds in float32
        hi = g[e].astype(jnp.bfloat16)
        mid = (g[e] - hi.astype(F32)).astype(jnp.bfloat16)
        lo = (g[e] - hi.astype(F32) - mid.astype(F32)).astype(jnp.bfloat16)
        parts = dot(ones, jnp.concatenate([hi, mid, lo], 1), precision=None)
        run.append(parts[:, :dk] + parts[:, dk:2 * dk] + parts[:, 2 * dk:])
        decayed.append(jnp.exp(run[e]))  # from the chunk's start to a token
        # sum_d x_t k_i exp(G_t - G_i) for x = k and x = q, a sub-block of
        # rows at a time from its reference point (G where it starts)
        a_k, a_q = [], []
        for n in range(nb):
            rows = slice(n * sub, (n + 1) * sub)
            ref = (run[e][n * sub - 1:n * sub] if n
                   else jnp.zeros_like(run[e][:1]))
            to_row = jnp.exp(run[e][rows] - ref)  # <= 1
            from_col = jnp.exp(jnp.minimum(ref - run[e], _UNDO_MOST))
            both = dot(jnp.concatenate([kc[e][rows] * to_row,
                                        qc[e][rows] * to_row]).astype(mm),
                       (kc[e] * from_col).astype(mm), ((1,), (1,)))
            a_k.append(both[:sub])
            a_q.append(both[sub:])
        low.append(beta[e] * masked(before, jnp.concatenate(a_k)))
        a_qk.append(masked(upto, jnp.concatenate(a_q)).astype(mm))
        rhs.append(jnp.concatenate(
            [beta[e] * kc[e] * decayed[e], beta[e] * vc[e]], 1))

    # (I + low) X = beta [k decayed | v], float32 throughout. The diagonal
    # sub-blocks of as many heads as fill the lanes lie side by side,
    # [sub, heads x C], and are inverted together column by column
    # (elementwise; row s is final once the columns before s are done).
    # One true float32 product of the inverses, block-diagonal, gives every
    # block's P = D^-1 rhs and Q = D^-1 (low left of the block); the
    # blocks are then substituted forward, X_b = P_b - Q_b X_<b.
    width, side = rhs[0].shape[1], max(128 // c, 1)
    block = block_of(iota((sub, c), 1))
    in_block = {n: block == n for n in range(1, nb)}
    by_width, solved = {}, []
    for first in range(0, len(k), side):
        group = heads[first:first + side]
        wide = len(group) * c
        if wide not in by_width:  # what groups of one width share
            lane = iota((sub, wide), 1)
            within = lax.rem(lane, jnp.int32(sub))
            by_width[wide] = (
                lane - within,  # a lane's own block's first lane
                (iota((sub, wide), 0) == within).astype(F32),
                block_of(iota((wide, wide), 0))
                == block_of(iota((wide, wide), 1)))
        start, inv, same_block = by_width[wide]
        diag = []
        for e in group:
            d = low[e][:sub]
            for n in range(1, nb):
                d = lax.select(in_block[n], low[e][n * sub:(n + 1) * sub], d)
            diag.append(d)
        diag = jnp.concatenate(diag, 1)
        for s in range(sub - 1):
            column = jnp.take_along_axis(diag, start + s, axis=1,
                                         mode="promise_in_bounds")
            inv = inv - column * inv[s:s + 1]
        inverses = masked(same_block, jnp.concatenate([inv] * (wide // sub)))
        left = [masked(earlier, low[e]) for e in group]
        solved.append(dot(inverses, jnp.concatenate(
            [jnp.concatenate([rhs[e] for e in group]),
             jnp.concatenate(left)], 1), precision=_HIGHEST))
    x = [[] for _ in heads]
    for n in range(nb):
        for e in heads:
            pq, i = solved[e // side], e % side
            rows = slice(i * c + n * sub, i * c + (n + 1) * sub)
            x[e].append(pq[rows, :width] if n == 0 else pq[rows, :width] - dot(
                pq[rows, width:width + n * sub], jnp.concatenate(x[e]),
                precision=_HIGHEST))

    carried, u = [], []
    for e in heads:
        solution = jnp.concatenate(x[e])
        carried.append(states[e].astype(mm))
        u.append(solution[:, dk:] - dot(solution[:, :dk].astype(mm),
                                        carried[e]))
    o, new = [], []
    for e in heads:
        o.append(dot((qc[e] * decayed[e]).astype(mm), carried[e])
                 + dot(a_qk[e], u[e].astype(mm)))
        total = run[e][c - 1:c]  # the chunk's whole log-decay [1, Dk]
        k_end = (kc[e] * jnp.exp(total - run[e])).astype(mm)  # at its end
        keep = jnp.exp(jnp.broadcast_to(total, (8, dk)).T[:, :1])  # [Dk, 1]
        new.append(keep * states[e]
                   + dot(k_end, u[e].astype(mm), ((0,), (0,))))
    return o, new


@functools.partial(jax.jit, static_argnames="chunk")
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
    sequence that ``chunk`` does not divide is padded the same way.

    One Pallas kernel: grid (B, blocks of heads, chunks), the chunk axis
    sequential with the block's states resident in their output block;
    a grid step reads a chunk's q, k, v, g and beta where they lie
    ([B, S, H x D]: a head is a slice of whole lanes) and writes its o. A
    chunk past a sequence's last valid token is neither read nor
    computed: its o is zeros and the state passes through. Off the TPU
    the kernel runs in the Pallas interpreter. Jitted, so that a program's
    call sites of one shape (a run of like layers each) trace and lower
    the kernel's body once: its ~900 operations are traced and lowered for
    every admission program before the compile cache can be asked."""
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
    sp = s + pad
    nc = sp // chunk
    sub = _SUB_BLOCK if chunk % _SUB_BLOCK == 0 else chunk
    # heads a grid step: a head's slice must be whole lanes, else all
    th = _HEADS_A_STEP if (dk % 128 == 0 and dv % 128 == 0
                           and h % _HEADS_A_STEP == 0) else h
    # the chunks up to a sequence's last token that counts
    if valid is None:
        live = jnp.full((b,), nc, jnp.int32)
    else:
        live = -(-jnp.max(jnp.where(valid, jnp.arange(1, s + 1), 0), 1)
                 // chunk).astype(jnp.int32)

    def kernel(live_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, first_ref,
               o_ref, state_ref):
        computed = pl.program_id(2) < live_ref[pl.program_id(0)]

        @pl.when(pl.program_id(2) == 0)
        def _start():
            state_ref[...] = first_ref[...]

        @pl.when(computed)
        def _chunk():
            beta, es = beta_ref[...], range(th)
            o, new = _chunk_of_heads(
                [state_ref[e] for e in es],
                [q_ref[:, e * dk:(e + 1) * dk] for e in es],
                [k_ref[:, e * dk:(e + 1) * dk] for e in es],
                [v_ref[:, e * dv:(e + 1) * dv] for e in es],
                [g_ref[:, e * dk:(e + 1) * dk] for e in es],
                [beta[:, e:e + 1] for e in es], sub)
            for e in es:
                state_ref[e] = new[e]
                o_ref[:, e * dv:(e + 1) * dv] = o[e].astype(o_ref.dtype)

        @pl.when(jnp.logical_not(computed))
        def _skipped():
            o_ref[...] = jnp.zeros_like(o_ref)

    def rows(bi, hi, ci, live):  # a skipped chunk fetches nothing new
        return bi, jnp.maximum(jnp.minimum(ci, live[bi] - 1), 0), hi

    def strengths(bi, hi, ci, live):  # beta lies [B, H / th, S, th]
        return bi, hi, rows(bi, hi, ci, live)[1], 0

    def flat(a):  # [B, S, H, x] -> [B, S, H x]: a head is a lane slice
        return a.reshape(b, sp, -1)

    first = (jnp.zeros((b, h, dk, dv), F32) if state0 is None
             else state0.astype(F32))
    states = pl.BlockSpec((None, th, dk, dv),
                          lambda bi, hi, ci, live: (bi, hi, 0, 0))
    o, last = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((b, sp, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, h, dk, dv), F32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // th, nc),
            in_specs=[pl.BlockSpec((None, chunk, th * dk), rows),
                      pl.BlockSpec((None, chunk, th * dk), rows),
                      pl.BlockSpec((None, chunk, th * dv), rows),
                      pl.BlockSpec((None, chunk, th * dk), rows),
                      pl.BlockSpec((None, None, chunk, th), strengths),
                      states],
            out_specs=[pl.BlockSpec((None, chunk, th * dv),
                                    lambda bi, hi, ci, live: (bi, ci, hi)),
                       states],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() != "tpu",
        name="kda_chunk",
    )(live, flat(q), flat(k), flat(v), flat(g),
      beta.reshape(b, sp, h // th, th).transpose(0, 2, 1, 3), first)
    return o.reshape(b, sp, h, dv)[:, :s], last


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
