"""Ulysses-style sequence parallelism: all-to-all head-sharded attention.

The second first-class long-context strategy next to ring attention
(SURVEY §5.7 — the reference has neither): with the sequence sharded over
``sp``, two ``all_to_all``s re-layout [B, S/sp, H, D] -> [B, S, H/sp, D]
so every device computes FULL-sequence attention for its head subset
(any local kernel — here the Pallas flash kernel or dense), then the
inverse all-to-all restores sequence sharding. Communication is O(S·H·D /
sp) per device per direction — constant in sp hops (vs ring's sp
neighbour steps), which is the better trade when heads are plentiful and
ICI all-to-all bandwidth is good.

Ref: DeepSpeed-Ulysses (Jacobs et al.) — see PAPERS.md.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax

from ray_tpu.ops.attention import causal_attention, repeat_kv


def _ulysses_body(q, k, v, *, axis_name: str, local_attn):
    """Runs per-device inside shard_map; q/k/v local [B, S/sp, H, D]."""
    sp = lax.axis_size(axis_name)
    n_rep = q.shape[2] // k.shape[2]
    if k.shape[2] % sp:
        # too few kv heads to split: replicate them up to the q head count
        k = repeat_kv(k, n_rep)
        v = repeat_kv(v, n_rep)

    def seq_to_heads(x):
        # [B, S/sp, H, D] -> [B, S, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    q = seq_to_heads(q)
    k = seq_to_heads(k)
    v = seq_to_heads(v)
    o = local_attn(q, k, v)  # full-sequence attention on H/sp heads
    # [B, S, H/sp, D] -> [B, S/sp, H, D]
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention(
    q: jax.Array,  # [B, S, H, D] global, sequence sharded over `axis_name`
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    axis_name: str = "sp",
    dp_axis=("dp", "ep"),
    tp_axis: str = "tp",
    attn_impl: str = "dense",  # local kernel: dense | flash
) -> jax.Array:
    """Causal attention with Ulysses sequence parallelism. Call inside jit;
    shard_map partitions [batch->dp, seq->sp, heads->tp]."""
    P = jax.sharding.PartitionSpec
    sp = mesh.shape[axis_name]
    if q.shape[1] % sp:
        raise ValueError(f"seq len {q.shape[1]} not divisible by sp={sp}")
    heads_per_dev = q.shape[2] // max(mesh.shape[tp_axis], 1)
    if heads_per_dev % sp:
        raise ValueError(
            f"heads-per-device ({heads_per_dev}) must be divisible by "
            f"sp={sp} for Ulysses (use ring attention otherwise)"
        )
    if attn_impl == "flash":
        from ray_tpu.ops.flash_attention import flash_attention

        local_attn = flash_attention
    else:
        local_attn = causal_attention
    spec = P(dp_axis, axis_name, tp_axis, None)
    return jax.shard_map(
        partial(_ulysses_body, axis_name=axis_name, local_attn=local_attn),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
