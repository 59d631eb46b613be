"""Ring attention: exact causal attention with the sequence sharded over the
``sp`` mesh axis.

Long-context is first-class here (the reference has NO sequence/context
parallelism — SURVEY.md §5.7). Each device holds a contiguous sequence block
of q/k/v. K/V blocks rotate around the ``sp`` ring via ``lax.ppermute``
(neighbour hops over ICI) while every device accumulates its q-block's
attention with the online-softmax (flash) update, so the full S×S score
matrix never materializes and per-device memory stays O(S/sp · S/sp).

Ref: Liu et al., "Ring Attention with Blockwise Transformers" (PAPERS.md).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import (
    NEG_INF,
    blockwise_finalize,
    blockwise_update,
    repeat_kv,
)


def _ring_body(q, k, v, *, axis_name: str, seq_len_per_shard: int):
    """Runs on one device inside shard_map; q/k/v are local blocks [B,Sl,H,D]."""
    sp = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    n_rep = h // k.shape[2]
    scale = d ** -0.5

    q32 = q.astype(jnp.float32)
    q_pos = my_idx * seq_len_per_shard + jnp.arange(sl)

    acc0 = jnp.zeros((b, sl, h, d), jnp.float32)
    m0 = jnp.full((b, h, sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sl), jnp.float32)

    def step(t, carry):
        acc, m, l, k_cur, v_cur = carry
        # At step t this device holds the kv block originally on (my_idx - t).
        kv_idx = (my_idx - t) % sp
        k_rep = repeat_kv(k_cur, n_rep).astype(jnp.float32)
        v_rep = repeat_kv(v_cur, n_rep)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k_rep) * scale
        k_pos = kv_idx * seq_len_per_shard + jnp.arange(sl)
        mask = q_pos[:, None] >= k_pos[None, :]
        scores = jnp.where(mask[None, None], scores, NEG_INF)
        # Whole block in the future (kv_idx > my_idx): mask is all-False and
        # the update is a no-op because exp(NEG_INF - m) underflows to 0.
        acc, m, l = blockwise_update(scores, v_rep, acc, m, l)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    acc, m, l, _, _ = lax.fori_loop(0, sp, step, (acc0, m0, l0, k, v))
    return blockwise_finalize(acc, l, q.dtype)


def ring_attention(
    q: jax.Array,  # [B, S, H, D] global, sequence sharded over `axis_name`
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    dp_axis=("dp", "ep"),
    tp_axis: str = "tp",
) -> jax.Array:
    """Causal attention with sequence parallelism. Call inside jit; shard_map
    partitions [batch→dp, seq→sp, heads→tp] and runs the ring locally."""
    P = jax.sharding.PartitionSpec
    spec = P(dp_axis, axis_name, tp_axis, None)
    sp = mesh.shape[axis_name]
    if q.shape[1] % sp:
        raise ValueError(f"seq len {q.shape[1]} not divisible by sp={sp}")
    body = partial(
        _ring_body, axis_name=axis_name, seq_len_per_shard=q.shape[1] // sp
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
