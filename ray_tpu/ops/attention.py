"""Dense causal multi-head attention (reference implementation).

The all-jnp path: XLA fuses the softmax chain and tiles the two matmuls onto
the MXU. Used when the sequence axis is unsharded; `ring_attention` (sp>1) and
the Pallas flash kernel (long single-device sequences) build on the same
blockwise log-sum-exp accumulation primitives defined here.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

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


def blocked_causal_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,  # [B, S, Hkv, Dv]
    *,
    block: int = 1024,
) -> jax.Array:
    """``causal_attention`` over whole sequences without its [S, S]
    scores: one KV head's queries at a time (no key or value is repeated
    for the heads that share it), tile by tile with an online softmax in
    float32 (operands in their own type), the rows' tiles only up to the
    queries' own. Memory grows with S, not with S squared; values may be
    narrower than keys. Returns [B, S, H, Dv] in q's type."""
    B, S, H, D = q.shape
    G, Dv = k.shape[2], v.shape[-1]
    R = H // G
    blk = block_of(S, block)
    f32 = jnp.float32
    scale = D ** -0.5
    at = jnp.arange(blk)

    def group(g):
        def of(x):
            return lax.dynamic_index_in_dim(x, g, 2, keepdims=False)

        qh, kh, vh = of(q.reshape(B, S, G, R, D)), of(k), of(v)

        def queries(i):
            qb = lax.dynamic_slice_in_dim(qh, i * blk, blk, 1)  # [B,blk,R,D]

            def rows(j, state):
                m, l, acc = state  # [B,R,blk,1] twice, [B,R,blk,Dv]
                kb = lax.dynamic_slice_in_dim(kh, j * blk, blk, 1)
                vb = lax.dynamic_slice_in_dim(vh, j * blk, blk, 1)
                s = jnp.einsum("bqrd,bkd->brqk", qb, kb,
                               preferred_element_type=f32) * scale
                seen = (i * blk + at)[:, None] >= (j * blk + at)[None, :]
                s = jnp.where(seen, s, NEG_INF)
                m_new = jnp.maximum(m, s.max(-1, keepdims=True))
                p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
                shrink = jnp.exp(m - m_new)
                l = shrink * l + p.sum(-1, keepdims=True)
                acc = shrink * acc + jnp.einsum(
                    "brqk,bkd->brqd", p.astype(q.dtype), vb,
                    preferred_element_type=f32)
                return m_new, l, acc

            m0 = jnp.full((B, R, blk, 1), NEG_INF, f32)
            _, l, acc = lax.fori_loop(
                0, i + 1, rows,
                (m0, jnp.zeros_like(m0), jnp.zeros((B, R, blk, Dv), f32)))
            return (acc / l).transpose(0, 2, 1, 3).astype(q.dtype)

        out = lax.map(queries, jnp.arange(S // blk))  # [S/blk,B,blk,R,Dv]
        return out.transpose(1, 0, 2, 3, 4).reshape(B, S, R, Dv)

    out = lax.map(group, jnp.arange(G))  # [G,B,S,R,Dv]
    return out.transpose(1, 2, 0, 3, 4).reshape(B, S, H, Dv)


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
