"""EVA attention (EvaByte; "Efficient Attention via Control Variates",
arXiv:2302.04542, as the released model runs it): pooled chunk summaries
and the attention of whole sequences over them.

Token ``t`` lies in window ``t // window``; a chunk holds ``chunk``
tokens. ``eva_pool`` folds every chunk's keys and values into ONE row
each under two learned vectors a head; ``eva_attention`` lets a query
attend, in one softmax, its own window's rows up to itself and the
summaries of every chunk of every window before its own (never its own
window's: an open window has no visible summary). Plain ``jnp``, blocked
over the queries so that no [S, S] array exists: a block of queries is
scored against the ONE window it lies in and against all summaries. The
serving paths' decode step reads the same rows through
``ops/decode_attention`` (``generation._decode_eva``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import NEG_INF, block_of


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


def eva_attention(q, k, v, ks, vs, *, window: int, chunk: int,
                  block: int = 256):
    """q [B, S, H, D] (S a multiple of ``window`` or shorter than two)
    against k, v [B, <= S, H, D] and the summaries ks, vs [B, NS, H, D]
    of the chunks from the sequence's start on (``eva_pool``; at least
    those of the windows before the last query's): query t attends the
    rows ``window * (t // window) <= j <= t`` and the summaries ``c <
    (window / chunk) * (t // window)`` in ONE softmax, scores x 1 /
    sqrt(D) in float32. Returns [B, S, H, D] in q's type. A block of
    queries (``block``, halved until it divides the window) lies in one
    window: it is scored against that window's ``window`` rows (those
    past the queries masked) and all ``NS`` summaries (those of its own
    and later windows masked)."""
    B, S, H, D = q.shape
    f32 = jnp.float32
    scale = D ** -0.5
    blk = block_of(math.gcd(S, window), block)
    per = window // chunk
    n_sum = ks.shape[1]
    tail = ((0, 0), (0, -k.shape[1] % window), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, tail), jnp.pad(v, tail)

    def queries(i):
        first = i * blk
        w = first // window
        qb = lax.dynamic_slice_in_dim(q, first, blk, 1)
        kb = lax.dynamic_slice_in_dim(kp, w * window, window, 1)
        vb = lax.dynamic_slice_in_dim(vp, w * window, window, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                       preferred_element_type=f32) * scale
        t = (first + jnp.arange(blk))[:, None]
        seen = w * window + jnp.arange(window)[None, :] <= t
        s = jnp.where(seen, s, NEG_INF)
        m = s.max(-1)  # [B,H,blk]
        if n_sum:
            ss = jnp.einsum("bqhd,bchd->bhqc", qb, ks,
                            preferred_element_type=f32) * scale
            closed = jnp.arange(n_sum) < per * w
            ss = jnp.where(closed, ss, NEG_INF)
            m = jnp.maximum(m, ss.max(-1))
        p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
        total = p.sum(-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vb,
                         preferred_element_type=f32)
        if n_sum:
            ps = jnp.where(closed, jnp.exp(ss - m[..., None]), 0.0)
            total = total + ps.sum(-1)
            out = out + jnp.einsum("bhqc,bchd->bqhd", ps.astype(q.dtype),
                                   vs, preferred_element_type=f32)
        return (out / total.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    out = lax.map(queries, jnp.arange(S // blk))  # [S/blk,B,blk,H,D]
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)
