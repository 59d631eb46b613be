"""Pallas TPU flash attention (forward + backward kernels).

Causal multi-head attention that never materializes the S x S score matrix:
the grid walks (batch*heads, q-block, kv-block) with the kv axis innermost so
the online-softmax accumulator lives in VMEM scratch across the kv sweep and
is flushed to HBM once per q-block. Backward recomputes scores blockwise from
the saved logsumexp (two kernels: dq with kv innermost, dk/dv with q
innermost), the standard FlashAttention-2 decomposition.

TPU mapping: the two matmuls per block (q@k^T and p@v) hit the MXU; masks and
the exp/max/sum chain run on the VPU; fp32 accumulation throughout with bf16
block inputs. Causal blocks strictly above the diagonal are skipped via
@pl.when, halving the work.

This is the single-device kernel; sequence parallelism composes *around* it
(ring attention over the `sp` mesh axis uses the same online-softmax math in
`ray_tpu/ops/attention.py`). The reference has no TPU attention kernel at all
(SURVEY.md §5.7 — long-context is a deliberate gap this framework fills).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF, repeat_kv

# Lane width: scratch row-stat buffers (m, l) are replicated across 128 lanes.
_LANES = 128


def _fit_block(requested: int, s: int) -> int:
    """Largest block <= requested that divides s (halving search; a block
    equal to s itself is always legal for Pallas)."""
    b = min(requested, s)
    while b > 128 and s % b:
        b //= 2
    return b if s % b == 0 else s


def _block_scores(q, k, qi, kj, *, scale, block_q, block_kv, causal):
    """Masked fp32 score block s = scale * q @ k^T for tile (qi, kj)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [bq, bkv]
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = kj * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref,  # [1, bq, D], [1, bkv, D], [1, bkv, D]
                o_ref, lse_ref,       # [1, bq, D], [1, bq]
                acc_ref, m_ref, l_ref,  # VMEM scratch
                *, scale: float, block_q: int, block_kv: int, causal: bool):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: the whole block is masked iff its first kv pos > last q pos.
    run = (not causal) or (kj * block_kv <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]  # [bq, D]
        k = k_ref[0]  # [bkv, D]
        v = v_ref[0]
        s = _block_scores(q, k, qi, kj, scale=scale, block_q=block_q,
                          block_kv=block_kv, causal=causal)

        m_prev = m_ref[:, 0]                      # [bq]
        m_cur = jnp.max(s, axis=-1)               # [bq]
        m_new = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_new)            # [bq]
        p = jnp.exp(s - m_new[:, None])           # [bq, bkv] f32
        l_ref[...] = (l_ref[...] * corr[:, None]
                      + jnp.sum(p, axis=-1)[:, None] * jnp.ones((1, _LANES),
                                                               jnp.float32))
        m_ref[...] = m_new[:, None] * jnp.ones((1, _LANES), jnp.float32)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, D]
        acc_ref[...] = acc_ref[...] * corr[:, None] + pv

    @pl.when(kj == n_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        denom = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[:, 0] + jnp.log(denom)


def _fwd(q, k, v, *, scale, block_q, block_kv, causal, interpret):
    """q/k/v: [BH, S, D] -> (o [BH, S, D], lse [BH, S])."""
    bh, s, d = q.shape
    grid = (bh, s // block_q, s // block_kv)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_kv=block_kv,
        causal=causal,
    )
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, 0, :]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, block_q, block_kv, causal):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_kv = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (not causal) or (kj * block_kv <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]     # [bq]
        delta = delta_ref[0, 0]  # [bq]
        s = _block_scores(q, k, qi, kj, scale=scale, block_q=block_q,
                          block_kv=block_kv, causal=causal)
        p = jnp.exp(s - lse[:, None])  # [bq, bkv] — already normalized probs
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bkv]
        ds = p * (dp - delta[:, None]) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, block_q, block_kv, causal):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    run = (not causal) or (qi * block_q + block_q - 1 >= kj * block_kv)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = _block_scores(q, k, qi, kj, scale=scale, block_q=block_q,
                          block_kv=block_kv, causal=causal)
        p = jnp.exp(s - lse[:, None])  # [bq, bkv]
        # dv += p^T @ do
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta[:, None]) * scale  # [bq, bkv]
        # dk += ds^T @ q
        dk_acc[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd(scale, block_q, block_kv, causal, interpret, res, do):
    q, k, v, o, lse = res
    bh, s, d = q.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse3 = lse[:, None, :]
    delta3 = delta[:, None, :]

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q,
            block_kv=block_kv, causal=causal,
        ),
        grid=(bh, s // block_q, s // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)

    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q,
            block_kv=block_kv, causal=causal,
        ),
        grid=(bh, s // block_kv, s // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, d), jnp.float32),
            pltpu.VMEM((block_kv, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse3, delta3)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper on [BH, S, D]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, block_q, block_kv, causal, interpret):
    o, _ = _fwd(q, k, v, scale=scale, block_q=block_q, block_kv=block_kv,
                causal=causal, interpret=interpret)
    return o


def _flash_fwd(q, k, v, scale, block_q, block_kv, causal, interpret):
    o, lse = _fwd(q, k, v, scale=scale, block_q=block_q, block_kv=block_kv,
                  causal=causal, interpret=interpret)
    return o, (q, k, v, o, lse)


_flash.defvjp(_flash_fwd, _bwd)


def flash_attention(
    q: jax.Array,  # [B, S, H, D]
    k: jax.Array,  # [B, S, Hkv, D]
    v: jax.Array,
    *,
    causal: bool = True,
    # 1024x1024 tiles measured fastest on v5e for the 400M train step
    # (+3.7 MFU points over 512x512); VMEM still fits f32 scratch + blocks.
    block_q: int = 1024,
    block_kv: int = 1024,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Flash attention on one device (or one shard under shard_map).

    Always the Pallas kernel: ``interpret`` defaults to the interpreter on
    the CPU backend (tests) and to the compiled kernel everywhere else.
    """
    b, s, h, d = q.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # Snap blocks to divisors of the sequence: a seq divisible by 512 but
    # not 1024 uses 512 tiles, and one that no block divides is one tile.
    block_q = _fit_block(block_q, s)
    block_kv = _fit_block(block_kv, s)
    n_rep = h // k.shape[2]
    k = repeat_kv(k, n_rep)
    v = repeat_kv(v, n_rep)
    scale = d ** -0.5
    # [B, S, H, D] -> [B*H, S, D]
    qt = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kt = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vt = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    o = _flash(qt, kt, vt, scale, block_q, block_kv, causal, interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def flash_attention_sharded(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    dp_axis=("dp", "ep"),
    tp_axis: str = "tp",
    **kw,
) -> jax.Array:
    """GSPMD-compatible wrapper: shard_map over [batch->dp, heads->tp].

    pallas_call is opaque to the XLA partitioner, so unlike the dense path we
    place it under shard_map explicitly. Requires sp=1 (sequence-parallel
    long context uses ring attention instead).
    """
    if mesh.shape.get("sp", 1) != 1:
        raise ValueError("flash attention requires sp=1; use attn_impl='ring'")
    tp = mesh.shape.get(tp_axis, 1)
    if k.shape[2] % tp:
        raise ValueError(
            f"kv heads ({k.shape[2]}) must divide over tp={tp} for the flash "
            f"kernel; use more kv heads or a smaller tp axis"
        )
    spec = jax.sharding.PartitionSpec(dp_axis, None, tp_axis, None)
    kv_spec = spec
    return jax.shard_map(
        functools.partial(flash_attention, **kw),
        mesh=mesh,
        in_specs=(spec, kv_spec, kv_spec),
        out_specs=spec,
        check_vma=False,
    )(q, k, v)
