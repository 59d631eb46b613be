"""Causal multi-head attention: the dense reference form, the blockwise
online-softmax primitives, and the two prefill forms of the served path.

``causal_attention`` is the all-jnp path: XLA fuses the softmax chain and
tiles the two matmuls onto the MXU. Used when the sequence axis is
unsharded; `ring_attention` (sp>1) builds on the blockwise log-sum-exp
accumulation primitives defined here. ``prefill_attention`` is a full
layer's attention over a whole prompt at an admission: ``causal_attention``
while the float32 scores fit the chip's fast memory, from there
``blocked_causal_attention`` (the same without its [S, S] scores), one
Pallas TPU kernel, forward only, with values narrower than keys, the
prompt's length a prefetched scalar and, for a block that chooses the rows
a query attends, the choice's mask an operand; the training kernel with a
backward pass is `ops/flash_attention.py`. ``window_attention`` (a window layer's
prefill) is blocked ``jnp``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (grouped-query attention)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)).reshape(
        b, s, h * n_rep, d
    )


def causal_attention(
    q: jax.Array,  # [B, Sq, H, D]
    k: jax.Array,  # [B, Sk, Hkv, D]
    v: jax.Array,  # [B, Sk, Hkv, D]
    *,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
    causal: bool = True,
) -> jax.Array:
    """Standard softmax attention with a causal mask on global positions.

    q_offset/kv_offset give the global position of element 0 of each block so
    the same function serves full sequences and ring/blockwise shards.
    """
    n_rep = q.shape[2] // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = kv_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def blockwise_update(
    scores: jax.Array,  # [B, H, Sq, Skblk] fp32, already masked
    v_blk: jax.Array,  # [B, Skblk, H, D]
    acc: jax.Array,  # [B, Sq, H, D] fp32 running numerator
    m: jax.Array,  # [B, H, Sq] running row max
    l: jax.Array,  # [B, H, Sq] running denominator
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One flash-attention accumulation step (online softmax)."""
    m_blk = jnp.max(scores, axis=-1)
    m_new = jnp.maximum(m, m_blk)
    correction = jnp.exp(m - m_new)
    p = jnp.exp(scores - m_new[..., None])
    l_new = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk.astype(jnp.float32))
    acc_new = acc * correction.transpose(0, 2, 1)[..., None] + pv
    return acc_new, m_new, l_new


def blockwise_finalize(acc: jax.Array, l: jax.Array, dtype) -> jax.Array:
    """acc [B, Sq, H, D], l [B, H, Sq] -> normalized output in `dtype`."""
    denom = jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
    return (acc / denom).astype(dtype)


def block_of(n: int, most: int) -> int:
    """The largest block of at most ``most`` that divides ``n`` when halved
    down from ``most`` (``n`` itself where it is smaller)."""
    blk = min(most, n)
    while n % blk:
        blk //= 2
    return blk


# Rows of one product of the prefill kernel: a KV head's query heads x a
# block's queries (16 heads x 128 queries where the heads are grouped, one
# head x a whole block of rows where they are not), and what the kernel
# may hold in fast memory (a v5e has 128 MiB; 2,048 x 1,024 float32 scores
# are 8 MiB, their exponentials beside them).
_PREFILL_ROWS = 2048
PREFILL_VMEM = 100 * 2 ** 20


def prefill_blocks(s: int, heads_a_kv_head: int, block: int):
    """(queries, rows) of a block of ``blocked_causal_attention``: at most
    ``block`` rows of K / V, and queries halved from there while the
    heads' queries are more than ``_PREFILL_ROWS`` rows of a product (the
    queries' block divides the rows')."""
    bk = bq = block_of(s, block)
    while heads_a_kv_head * bq > _PREFILL_ROWS and bq % 32 == 0:
        bq //= 2
    return bq, bk


def prefill_block_pairs(s: int, length, heads_a_kv_head: int,
                        block: int = 1024):
    """The (block of queries, block of rows) pairs that
    ``blocked_causal_attention`` computes a KV head for ``length`` real
    tokens (an int, or a traced scalar) in a sequence of ``s``: the blocks
    of queries that hold a real token, each against its blocks of rows up
    to the diagonal's."""
    bq, bk = prefill_blocks(s, heads_a_kv_head, block)
    first = jnp.arange(s // bq, dtype=jnp.int32) * bq  # a block's first query
    return jnp.where(first < length, first // bk + 1, 0).sum()


def softmax_block(s, v_ref, lanes, m_ref, l_ref, acc_ref, e=Ellipsis):
    """One block of rows into a running softmax, inside a kernel: ``s``
    [queries, rows] (float32, scaled, what a query must not see at
    NEG_INF or under) against the rows' values ``v_ref[:, lanes]``, into
    the running maxima ``m_ref[e]`` and sums ``l_ref[e]`` [queries, 1]
    and the accumulator ``acc_ref[e]`` [queries, Dv], all float32; the
    exponentials go into the product in the values' type. The body of
    ``blocked_causal_attention``'s sweep and of ``ops/eva``'s."""
    m = m_ref[e]
    m_new = lax.max(m, lax.reduce_max(s, (1,))[:, None])
    p = lax.exp(s - m_new)
    shrink = lax.exp(m - m_new)
    l_ref[e] = shrink * l_ref[e] + lax.reduce_sum(p, (1,))[:, None]
    acc_ref[e] = shrink * acc_ref[e] + lax.dot_general(
        p.astype(v_ref.dtype), v_ref[:, lanes],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[e] = m_new


@functools.partial(jax.jit, static_argnames="block")
def blocked_causal_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, Dv]
    length: Optional[jax.Array] = None,  # [B] or a scalar: the real tokens
    *,
    mask: Optional[jax.Array] = None,  # [S, S] or [B, S, S]: chosen rows
    block: int = 1024,
) -> jax.Array:
    """``causal_attention`` over whole sequences without its [S, S]
    scores: grouped heads share their KV head's rows unrepeated, values
    may be narrower than keys, operands in their own type, scores and the
    online softmax in float32. Returns [B, S, H, Dv] in q's type; with
    ``length`` the rows at and past it are ZEROS (a padded bucket's tail:
    never read, and never uninitialised memory either). With ``mask``
    (bool or int8, one for all sequences or one each) query t attends the
    rows the mask's row t marks and no other: a choice that is causal
    already (none past t) and marks at least one row a query.

    One Pallas TPU kernel, forward only: grid (B, KV heads, blocks of
    queries, blocks of rows), the rows' axis innermost. One KV head's
    query heads ride one fetch of a K / V block as the ROWS of one product
    (heads x queries), read from q where it lies ([S, H x D]: a KV head's
    heads are a slice of lanes) and stacked once a block of queries; the
    scores, the running maxima and sums and the accumulator stay in fast
    memory for the block's whole sweep over the rows and the output is
    written once, where it lies ([S, H x Dv]). A block of rows wholly
    above the diagonal and a block of queries wholly past ``length`` are
    neither fetched nor computed (the index maps ask for the block they
    already hold); only the block the diagonal crosses builds a mask. A
    ``mask`` is fetched in int8, the step's (queries, rows) block of it by
    an index map clamped like K's, and rules every computed block, the
    diagonal's with no compare of its own: -1e30 is ADDED to the
    scores it rules out (a score is lost in it: exactly -1e30, and no
    array of booleans to keep), and the running maximum starts above that,
    at -5e29, so their exponentials are 0 even where a query has no chosen
    row in a whole block (a select would also drop a score that is NaN:
    the rows it rules out must be finite, as V's always had to be). Without
    a mask nothing of this is traced: the kernel is what it was. K
    and V are read where they lie too ([S, Hkv x D]); where one KV head's
    keys are not whole lanes wide (192) a grid step takes the fewest KV
    heads that are, one after the other. Blocks: ``prefill_blocks``. Off
    the TPU the kernel runs in the Pallas interpreter. Jitted, so that a
    program's call sites of one shape trace and lower the body once (every
    admission program does both before the compile cache is asked: the
    body is kept short)."""
    B, S, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    R = H // G
    f32 = jnp.float32
    scale = D ** -0.5
    bq, bk = prefill_blocks(S, R, block)
    nq, nk, rows = S // bq, S // bk, R * bq
    # KV heads a grid step: the fewest whose keys and values are whole
    # lanes wide where they lie (192-wide keys go two heads a step); all of
    # them where no count is (the tests' narrow heads)
    hs = next((n for n in range(1, G) if G % n == 0
               and n * D % 128 == 0 and n * Dv % 128 == 0), G)
    length = jnp.broadcast_to(
        jnp.asarray(S if length is None else length, jnp.int32), (B,))
    masked = mask is not None

    def kernel(len_ref, q_ref, k_ref, v_ref, *refs):
        mask_ref = refs[0] if masked else None
        o_ref, qs_ref, m_ref, l_ref, acc_ref = refs[-5:]
        i, j = pl.program_id(2), pl.program_id(3)
        n = len_ref[pl.program_id(0)]
        first = i * bq  # the block's first query
        here = lax.div(first, jnp.int32(bk))  # the rows the diagonal crosses
        live = first < n

        def query_of(shape, axis):  # a row of the product: (head, query)
            return first + lax.rem(
                lax.broadcasted_iota(jnp.int32, shape, axis), jnp.int32(bq))

        @pl.when(live & (j == 0))
        def _start():
            for e in range(hs * R):  # head e's queries under head e - 1's
                qs_ref[e // R, (e % R) * bq:(e % R + 1) * bq, :] = (
                    q_ref[:, e * D:(e + 1) * D])
            # under a choice above what rules a score out: see the sweep
            m_ref[...] = jnp.full_like(m_ref,
                                       NEG_INF / 2 if masked else NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        def sweep(diagonal: bool):
            if masked:  # causal already: the diagonal's block like the rest
                unseen = (jnp.concatenate([mask_ref[...]] * R).astype(f32)
                          - 1.0) * -NEG_INF  # 0 a chosen row, else NEG_INF
            elif diagonal:
                seen = query_of((rows, bk), 0) >= j * bk + lax.broadcasted_iota(
                    jnp.int32, (rows, bk), 1)
            for e in range(hs):
                s = lax.dot_general(
                    qs_ref[e], k_ref[:, e * D:(e + 1) * D],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=f32) * scale  # [rows, bk]
                if masked:
                    # NEG_INF itself (a score is lost in it), under every
                    # running maximum: a query with no chosen row in the
                    # blocks so far has exponentials of 0 all the same
                    s = s + unseen
                elif diagonal:
                    s = lax.select(seen, s, lax.full_like(s, NEG_INF))
                # causal alone, every query sees the block's first row (bq
                # divides bk): the maximum is a real score and a masked
                # exponential is 0
                softmax_block(s, v_ref, slice(e * Dv, (e + 1) * Dv),
                              m_ref, l_ref, acc_ref, e)

        if masked:
            pl.when(live & (j <= here))(lambda: sweep(False))
        else:
            @pl.when(live & (j < here))
            def _below():
                sweep(False)

            @pl.when(live & (j == here))
            def _diagonal():
                sweep(True)

        @pl.when(live & (j == nk - 1))
        def _write():
            out = acc_ref[...] / l_ref[...]
            out = lax.select(query_of(out.shape, 1) < n, out,
                             lax.full_like(out, 0)).astype(o_ref.dtype)
            for e in range(hs * R):
                o_ref[:, e * Dv:(e + 1) * Dv] = (
                    out[e // R, (e % R) * bq:(e % R + 1) * bq])

        @pl.when(jnp.logical_not(live) & (j == nk - 1))
        def _skipped():
            o_ref[...] = jnp.zeros_like(o_ref)

    def queries(b, g, i, j, n):  # a skipped block fetches nothing new
        return b, jnp.minimum(i, jnp.maximum(n[b] - 1, 0) // bq), g

    def keys(b, g, i, j, n):  # nor does a block above the diagonal
        return b, jnp.minimum(j, queries(b, g, i, j, n)[1] * bq // bk), g

    def chosen(b, g, i, j, n):  # the mask's block of a step, clamped alike
        return (b if mask.shape[0] > 1 else 0,
                queries(b, g, i, j, n)[1], keys(b, g, i, j, n)[1])

    operands = (q.reshape(B, S, H * D), k.reshape(B, S, G * D),
                v.reshape(B, S, G * Dv))
    in_specs = [pl.BlockSpec((None, bq, hs * R * D), queries),
                pl.BlockSpec((None, bk, hs * D), keys),
                pl.BlockSpec((None, bk, hs * Dv), keys)]
    if masked:
        mask = mask.astype(jnp.int8).reshape(-1, S, S)
        operands += (mask,)
        in_specs.append(pl.BlockSpec((None, bq, bk), chosen))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, S, H * Dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G // hs, nq, nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, bq, hs * R * Dv),
                                   lambda b, g, i, j, n: (b, i, g)),
            scratch_shapes=[pltpu.VMEM((hs, rows, D), q.dtype),
                            pltpu.VMEM((hs, rows, 1), f32),
                            pltpu.VMEM((hs, rows, 1), f32),
                            pltpu.VMEM((hs, rows, Dv), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=PREFILL_VMEM),
        interpret=jax.default_backend() != "tpu",
        name="prefill_attention",
    )(length, *operands)
    return out.reshape(B, S, H, Dv)


# What one product's float32 scores [H, S, S] may take: two thirds of the
# chip's fast memory (a v5e has 128 MiB). Under it the compiler keeps them
# there between the product, the softmax and the second product, beside the
# ~40 MiB of weights and activations a layer holds there anyway, and they
# never cross HBM; the kernel, at the same time a call, then only adds the
# copies that lay its operands out. Measured inside each family's admission
# program on a v5e, product -> kernel, ms a program, readings repeat to
# 0.003 ms (``tools/admission_profile.py``; PERF.md, section 6: PR 58's
# table, timed again by PR 59 and repeated to 0.2 ms): 16 heads x 1,024
# (64 MiB) 75.70 -> 80.61, 32 x 768 (72 MiB) 17.88 -> 17.85, 20 x 1,024
# (80 MiB) 21.72 -> 22.17; 40 x 768 (90 MiB) 26.92 -> 26.43, 32 x 1,024
# (128 MiB) 49.32 -> 48.49 and 21.25 -> 20.99, 20 x 2,048 (320 MiB) 36.32
# -> 32.37.
PREFILL_SCORE_BYTES = 2 * (128 * 2 ** 20) // 3


def prefill_by_kernel(heads: int, s: int) -> bool:
    """Whether ``prefill_attention`` takes the kernel for ``heads`` query
    heads over ``s`` tokens: where their float32 scores at once would pass
    ``PREFILL_SCORE_BYTES``."""
    return 4 * heads * s * s > PREFILL_SCORE_BYTES


def prefill_attention(q, k, v, length):
    """A full layer's attention over one padded prompt alone, q [1,S,H,D]
    against its own k / v [1,S,Hkv,D], ``length`` real tokens: ONE product
    (``causal_attention``: a real token attends nothing past itself, so
    the padding needs no mask of its own) while the scores stay in fast
    memory, ``blocked_causal_attention`` above. The form follows from the
    shapes alone (``prefill_by_kernel``)."""
    if prefill_by_kernel(q.shape[2], q.shape[1]):
        return blocked_causal_attention(q, k, v, length)
    return causal_attention(q, k, v)


def window_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, Dv]
    sink: Optional[jax.Array] = None,  # [H] logits
    *,
    window: int,
    block: int = 256,
) -> jax.Array:
    """Attention of query t over the rows t - window < s <= t alone
    (``window`` rows, itself among them). With ``sink`` a logit a head
    joins the softmax's denominator and carries no value: p[t,s] =
    exp(a[t,s]) / (exp(sink) + sum_s' exp(a[t,s'])). A block of queries is
    scored against the ONE stretch of rows its windows reach (the block's
    own rows and the ``window`` before them): the rows a window never
    sees are not read, masked or multiplied, so the work grows with S x
    (block + window) and not with S squared, and no [S, S] array exists.
    Grouped heads share their KV head's rows unrepeated; float32 scores.
    Returns [B, S, H, Dv] in q's type."""
    B, S, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    R = H // G
    blk = block_of(S, block)
    span = blk + window
    f32 = jnp.float32
    scale = D ** -0.5
    lead = ((0, 0), (window, 0), (0, 0), (0, 0))  # rows before the first
    kp, vp = jnp.pad(k, lead), jnp.pad(v, lead)
    qg = q.reshape(B, S, G, R, D)
    b = None if sink is None else sink.astype(f32).reshape(G, R)[:, :, None]

    def queries(i):
        qb = lax.dynamic_slice_in_dim(qg, i * blk, blk, 1)
        kb = lax.dynamic_slice_in_dim(kp, i * blk, span, 1)  # [B,span,G,D]
        vb = lax.dynamic_slice_in_dim(vp, i * blk, span, 1)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                       preferred_element_type=f32) * scale
        t = (i * blk + jnp.arange(blk))[:, None]
        row = (i * blk - window + jnp.arange(span))[None, :]
        seen = (row >= 0) & (row <= t) & (row > t - window)
        s = jnp.where(seen, s, NEG_INF)
        m = s.max(-1)  # [B,G,R,blk]
        if b is not None:
            m = jnp.maximum(m, b)
        p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
        total = p.sum(-1)
        if b is not None:
            total = total + jnp.exp(b - m)
        out = jnp.einsum("bgrqk,bkgd->bqgrd", p.astype(q.dtype), vb,
                         preferred_element_type=f32)
        return (out / total.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)

    out = lax.map(queries, jnp.arange(S // blk))  # [S/blk,B,blk,G,R,Dv]
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, H, Dv)
