"""EVA attention (EvaByte; "Efficient Attention via Control Variates",
arXiv:2302.04542, as the released model runs it): pooled chunk summaries
and the attention of whole sequences over them.

Token ``t`` lies in window ``t // window``; a chunk holds ``chunk``
tokens. ``eva_pool`` folds every chunk's keys and values into ONE row
each under two learned vectors a head; ``eva_attention`` lets a query
attend, in one softmax, its own window's rows up to itself and the
summaries of every chunk of every window before its own (never its own
window's: an open window has no visible summary). Both visibilities are
arithmetic: a window is a causal sequence of its own, and the summaries
its queries see are a PREFIX of the sequence's summaries, the same for
every query of the window. So ``eva_attention`` is one Pallas TPU kernel
in the form of ``ops/attention.blocked_causal_attention`` (whose
running-softmax body it shares), forward only: a grid over (sequence,
head, window, block of queries, block of summaries or rows), no [S, S]
array, no score outside fast memory, nothing multiplied above a window's
diagonal, past a window's closed summaries or past the prompt. The
serving paths' decode step reads the same rows through
``ops/decode_attention`` (``generation._decode_eva``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import (
    NEG_INF,
    PREFILL_VMEM,
    prefill_blocks,
    softmax_block,
)


def eva_pool(k, v, phi, mu, chunk: int):
    """Chunk summaries: k, v [..., N, H, D] (N a multiple of ``chunk``;
    the keys ROTATED already) under phi, mu [H, D] -> (k~, v~) [..., N /
    chunk, H, D] in k's type: ``k~_c = sum_j softmax_j(k_j . phi /
    sqrt(D)) k_j`` over the chunk's tokens and ``v~_c`` the same under
    ``mu`` over the values; logits, softmax and sums in float32."""
    f32 = jnp.float32
    lead, (n, h, d) = k.shape[:-3], k.shape[-3:]
    kc = k.reshape(lead + (n // chunk, chunk, h, d)).astype(f32)
    vc = v.reshape(kc.shape).astype(f32)

    def weights(w):  # [..., chunks, chunk, H]
        return jax.nn.softmax(
            (kc * w.astype(f32)).sum(-1) * d ** -0.5, axis=-2)[..., None]

    return ((weights(phi) * kc).sum(-3).astype(k.dtype),
            (weights(mu) * vc).sum(-3).astype(v.dtype))


def _blocks(s: int, summaries: int, window: int, block: int):
    """How ``eva_attention`` cuts a sequence of ``s`` tokens with
    ``summaries`` pooled rows: (windows, queries a block, rows a block,
    summaries a block, blocks of summaries a sweep). The window's blocks
    are the prefill kernel's for heads of their own (``prefill_blocks``);
    the summaries go in blocks as long, or in one where they are fewer."""
    bq, bk = prefill_blocks(window, 1, block)
    bp = min(bk, summaries)
    return -(-s // window), bq, bk, bp, -(-summaries // bp) if bp else 0


@functools.partial(jax.jit, static_argnames=("window", "chunk", "block"))
def eva_attention(q, k, v, ks, vs, length=None, *, window: int, chunk: int,
                  block: int = 1024):
    """q [B, S, H, D] against k, v [B, S and up to its window's end, H, D]
    and the summaries ks, vs [B, NS, H, D] of the chunks from the
    sequence's start on (``eva_pool``; at least those of the windows
    before the last query's): query t attends the rows
    ``window * (t // window) <= j <= t`` and the summaries ``c < (window /
    chunk) * (t // window)`` in ONE softmax, scores x 1 / sqrt(D) in
    float32, the two products on the operands' own type with float32
    sums. ``length`` [B] or a scalar: the real tokens (all S where none is
    given). Returns [B, S, H, D] in q's type, the rows at and past
    ``length`` ZEROS.

    One Pallas TPU kernel, forward only: grid (B, heads, windows, blocks
    of queries, blocks of summaries then of the window's rows), the last
    axis innermost. A head's queries and keys are read as [D, tokens] and
    its values as [tokens, D], heads outermost, and its output written
    [tokens, D]: where the projections and the rotation leave them and
    the output's projection takes it, so that no operand is laid out anew
    around the call (a kernel over [S, H x D] rows cost six copies of the
    bucket a layer, a third of its own time). The block's queries are
    turned to rows once; then window w's queries sweep, in one running
    softmax held in fast memory (``ops/attention.softmax_block``), the
    first ``(window / chunk) w`` summaries and the window's rows up to
    the diagonal. A block of summaries at or past that count, a block of
    rows above the diagonal and a block of queries past ``length`` are
    neither fetched nor computed (the index maps ask for the block they
    hold already); only the block the count falls in and the block the
    diagonal crosses build a compare (``eva_block_pairs`` counts what is
    left). The sequence is padded to whole windows (the 2,560 and 3,072
    buckets: a window and a part), the summaries to whole blocks. Off the
    TPU the kernel runs in the Pallas interpreter. Jitted, so that a
    program's layers trace and lower the body once."""
    B, S, H, D = q.shape
    f32 = jnp.float32
    scale = D ** -0.5
    per = window // chunk
    n, bq, bk, bp, ahead = _blocks(S, ks.shape[1], window, block)
    nq, steps = window // bq, ahead + window // bk
    length = jnp.broadcast_to(
        jnp.asarray(S if length is None else length, jnp.int32), (B,))

    def kernel(len_ref, q_ref, k_ref, v_ref, *refs):
        o_ref, qs_ref, m_ref, l_ref, acc_ref = refs[-5:]
        w, i, j = (pl.program_id(a) for a in (2, 3, 4))
        # the window's real tokens, and the summaries its queries see
        real = jnp.clip(len_ref[pl.program_id(0)] - w * window, 0, window)
        seen = per * w
        first = i * bq  # the block's first query, in its window
        live = first < real

        @pl.when(live & (j == 0))
        def _start():
            qs_ref[...] = q_ref[...].T
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def sweep(k_ref, v_ref, counts=None):
            """One block [D, rows] / [rows, D] into the running softmax;
            ``counts`` (queries, rows) -> bool where not every score
            does. A block that is swept holds a row every query sees
            first (bq divides bk), so a maximum is a real score."""
            s = lax.dot_general(qs_ref[...], k_ref[...],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=f32) * scale
            if counts is not None:
                s = lax.select(
                    counts(*(lax.broadcasted_iota(jnp.int32, s.shape, a)
                             for a in (0, 1))),
                    s, lax.full_like(s, NEG_INF))
            softmax_block(s, v_ref, slice(None), m_ref, l_ref, acc_ref)

        if ahead:  # the closed windows' summaries first
            pk_ref, pv_ref = refs[:2]
            pl.when(live & ((j + 1) * bp <= seen))(
                lambda: sweep(pk_ref, pv_ref))
            pl.when(live & (j * bp < seen) & (seen < (j + 1) * bp))(
                lambda: sweep(pk_ref, pv_ref,
                              lambda _t, c: j * bp + c < seen))
        at = j - ahead  # then the window's own rows, up to the diagonal
        pl.when(live & (at >= 0) & (at * bk + bk <= first))(
            lambda: sweep(k_ref, v_ref))
        pl.when(live & (at * bk <= first) & (first < at * bk + bk))(
            lambda: sweep(k_ref, v_ref,
                          lambda t, r: first + t >= at * bk + r))

        @pl.when(live & (j == steps - 1))
        def _write():
            out = acc_ref[...] / l_ref[...]
            t = first + lax.broadcasted_iota(jnp.int32, out.shape, 0)
            o_ref[...] = lax.select(
                t < real, out, lax.full_like(out, 0)).astype(o_ref.dtype)

        @pl.when(jnp.logical_not(live) & (j == steps - 1))
        def _skipped():
            o_ref[...] = jnp.zeros_like(o_ref)

    def held(b, w, i, n):
        """(window, block of queries, skipped) of a step: its own, or
        where that holds no real token the last that does, so that a
        skipped step fetches nothing new."""
        g, last = w * nq + i, jnp.maximum(n[b] - 1, 0) // bq
        return jnp.minimum(g, last) // nq, jnp.minimum(g, last) % nq, g > last

    def queries(b, h, w, i, j, n):
        w, i, _ = held(b, w, i, n)
        return w * nq + i

    def rows(b, h, w, i, j, n):  # nor does a block above the diagonal
        w, i, skipped = held(b, w, i, n)
        diagonal = i * bq // bk
        return w * (window // bk) + jnp.where(
            skipped, diagonal, jnp.clip(j - ahead, 0, diagonal))

    def summaries(b, h, w, i, j, n):  # nor one past the window's count
        w, _, skipped = held(b, w, i, n)
        last = jnp.maximum(per * w - 1, 0) // bp
        return jnp.where(skipped, last, jnp.minimum(j, last))

    def columns(index):  # a block of [D, tokens]
        return lambda *a: (a[0], a[1], 0, index(*a))

    def lines(index):  # a block of [tokens, D]
        return lambda *a: (a[0], a[1], index(*a), 0)

    operands = [jnp.pad(x, ((0, 0), (0, n * window - x.shape[1]), (0, 0),
                            (0, 0))).transpose(0, 2, *order)
                for x, order in ((q, (3, 1)), (k, (3, 1)), (v, (1, 3)))]
    in_specs = [pl.BlockSpec((None, None, D, bq), columns(queries)),
                pl.BlockSpec((None, None, D, bk), columns(rows)),
                pl.BlockSpec((None, None, bk, D), lines(rows))]
    if ahead:
        tail = ((0, 0), (0, ahead * bp - ks.shape[1]), (0, 0), (0, 0))
        operands += [jnp.pad(ks, tail).transpose(0, 2, 3, 1),
                     jnp.pad(vs, tail).transpose(0, 2, 1, 3)]
        in_specs += [pl.BlockSpec((None, None, D, bp), columns(summaries)),
                     pl.BlockSpec((None, None, bp, D), lines(summaries))]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, n * window, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, n, nq, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, None, bq, D),
                lambda b, h, w, i, j, n: (b, h, w * nq + i, 0)),
            scratch_shapes=[pltpu.VMEM((bq, D), q.dtype),
                            pltpu.VMEM((bq, 1), f32),
                            pltpu.VMEM((bq, 1), f32),
                            pltpu.VMEM((bq, D), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "parallel", "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM),
        interpret=jax.default_backend() != "tpu",
        name="eva_attention",
    )(length, *operands)
    return out.transpose(0, 2, 1, 3)[:, :S]


def eva_block_pairs(s: int, length, *, window: int, chunk: int,
                    block: int = 1024):
    """The (block of queries, block of rows or of summaries) pairs that
    ``eva_attention`` computes a head for ``length`` real tokens (an int,
    or a traced scalar) in a sequence of ``s``, and the pairs of every
    block of queries with its whole window and every summary of the
    sequence's whole windows (the grid's steps a head: what a form that
    skipped nothing would compute)."""
    n, bq, bk, bp, ahead = _blocks(
        s, s // window * (window // chunk), window, block)
    w = jnp.arange(n, dtype=jnp.int32)[:, None]
    first = jnp.arange(window // bq, dtype=jnp.int32) * bq
    computed = jnp.where(
        first < jnp.clip(length - w * window, 0, window),
        first // bk + 1 + -(-(window // chunk) * w // max(bp, 1)), 0).sum()
    return computed, n * (window // bq) * (ahead + window // bk)
