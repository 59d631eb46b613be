"""Pipeline parallelism over the ``pp`` mesh axis: GPipe and 1F1B.

The transformer's layer stack is partitioned into ``pp`` contiguous stages
(the stacked layer params are sharded on their leading L dim by the
``layers -> pp`` rule, so each device holds L/pp layers). Inside
``shard_map`` every stage runs the same SPMD program; activation blocks
rotate between neighbour stages via ``lax.ppermute`` (one ICI hop).

Two schedules:

- **GPipe** (``pipeline_loss_fn``): all-forward then all-backward, the
  backward derived by AD through the schedule scan. Simple, but live
  activation state grows with the microbatch count M.
- **1F1B** (``pipeline_grads_1f1b``): a hand-written interleaved schedule —
  each tick runs one forward AND one backward microbatch per stage, with
  the backward realized by ``jax.vjp`` over a RECOMPUTED stage forward
  from a ring buffer of stage inputs. In-flight state per stage is
  bounded by the ring (~2·pp slots) instead of M, so activation memory is
  O(pp), not O(M) — the memory-aware schedule for long microbatch trains.

Tensor parallelism COMPOSES with both: the shard_map is manual only over
``(dp, pp)`` (``axis_names=``), leaving ``tp`` to GSPMD inside each stage
program — stage matmuls are tp-sharded exactly as in the non-pipelined
path. sp/ep must still be 1 inside the pipelined region. Reference ships
NO pipeline parallelism (SURVEY.md §2.5 — Alpa release tests only); this
is the native TPU design.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.models.transformer import (
    TransformerConfig,
    _rms_norm,
    apply_layer,
    param_logical_axes,
    remat_wrap,
)
from ray_tpu.ops.attention import causal_attention
from ray_tpu.parallel.mesh import AxisRules, DEFAULT_RULES, logical_to_spec
from ray_tpu.parallel.train_step import TrainState


def _param_specs(config: TransformerConfig, rules: AxisRules):
    return jax.tree.map(
        lambda axes: logical_to_spec(rules, axes),
        param_logical_axes(config),
        is_leaf=lambda x: isinstance(x, tuple)
        and all(a is None or isinstance(a, str) for a in x),
    )


_MANUAL_AXES = frozenset({"dp", "pp"})


@jax.custom_vjp
def _pmax_pp_sg(x):
    """pmax over pp with a zero gradient: the logsumexp max-shift is
    AD-inert, and lax.pmax has no differentiation rule at all (even a
    stop_gradient around it still traces the primitive under vjp)."""
    return lax.pmax(x, "pp")


def _pmax_pp_sg_fwd(x):
    return _pmax_pp_sg(x), None


def _pmax_pp_sg_bwd(_res, g):
    return (jnp.zeros_like(g),)


_pmax_pp_sg.defvjp(_pmax_pp_sg_fwd, _pmax_pp_sg_bwd)


def _restrict_spec(spec: P) -> P:
    """Keep only the MANUAL (dp/pp) axes of a PartitionSpec: the pipeline's
    shard_map is manual over (dp, pp) only, with tp left to GSPMD inside
    the stage program (``axis_names``) — tp partitioning rides the arrays'
    own shardings, not the shard_map specs."""
    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in _MANUAL_AXES)
            return kept if kept else None
        return entry if entry in _MANUAL_AXES else None

    return P(*(keep(e) for e in spec))


def _pipeline_specs(config: TransformerConfig, rules: AxisRules,
                    vocab_parallel_head: bool = False):
    pspecs = jax.tree.map(_restrict_spec, _param_specs(config, rules),
                          is_leaf=lambda x: isinstance(x, P))
    if vocab_parallel_head and "lm_head" in pspecs:
        # vocab-parallel scoring (1F1B): each stage receives its OWN
        # [d, V/pp] head block from the shard_map — a static local slice,
        # 1/pp of the head memory per stage, and no dynamic vocab
        # indexing for GSPMD to partition
        pspecs["lm_head"] = P(None, "pp")
    data_spec = _restrict_spec(logical_to_spec(rules, ("batch", None)))
    return pspecs, data_spec


def pipeline_loss_fn(
    params: Dict,
    batch: Dict[str, jax.Array],
    config: TransformerConfig,
    mesh: Mesh,
    num_microbatches: int,
    rules: AxisRules = DEFAULT_RULES,
) -> jax.Array:
    """Drop-in replacement for ``models.transformer.loss_fn`` that runs the
    layer stack as a pp-stage pipeline. Call inside jit."""
    c = config
    pp = mesh.shape["pp"]
    for ax in ("sp", "ep"):
        if mesh.shape[ax] != 1:
            raise ValueError(
                f"pipeline_loss_fn requires {ax}=1 (got {mesh.shape[ax]}); "
                "sp/ep compose via the GSPMD (non-pipelined) path"
            )
    if c.n_layers % pp:
        raise ValueError(
            f"pp={pp} must divide n_layers={c.n_layers} (equal stages)"
        )
    if c.attn_impl != "dense":
        raise ValueError("pipeline stages use dense attention (sp=1)")
    M = num_microbatches

    def body(params, tokens, targets, mask):
        p = lax.axis_index("pp")
        b, S = tokens.shape  # dp-local batch
        if b % M:
            raise ValueError(f"local batch {b} not divisible by {M} microbatches")
        mb = b // M
        positions = jnp.arange(S)
        embed = params["embed"].astype(c.dtype)
        head = (
            params["embed"].T if c.tie_embeddings else params["lm_head"]
        ).astype(c.dtype)
        final_scale = params["final_ln"]["scale"]
        layers_local = params["layers"]  # leading dim = n_layers / pp

        toks = tokens.reshape(M, mb, S)
        tgts = targets.reshape(M, mb, S)
        msks = mask.reshape(M, mb, S)

        def stage_layers(x):
            def lyr(carry, lp):
                y, a, _ = apply_layer(
                    carry, lp, c, positions, causal_attention, mesh=None
                )
                return y, a

            lyr = remat_wrap(lyr, c)
            x, auxs = lax.scan(lyr, x, layers_local)
            return x, jnp.sum(auxs)

        def tick(carry, t):
            state, outs, aux_sum = carry
            mb_idx = t - p  # which microbatch this stage handles at tick t
            active = (mb_idx >= 0) & (mb_idx < M)
            # stage 0 ingests microbatch t from the embedding
            tok_mb = lax.dynamic_index_in_dim(
                toks, jnp.clip(t, 0, M - 1), 0, keepdims=False
            )
            x_in = jnp.where(p == 0, embed[tok_mb], state)
            x_out, aux = stage_layers(x_in)
            # stash the finished microbatch's activations; scoring happens
            # ONCE after the schedule (the vocab projection would otherwise
            # run on every stage at every tick)
            idx = jnp.clip(mb_idx, 0, M - 1)
            use = active & (p == pp - 1)
            cur = lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
            outs = lax.dynamic_update_index_in_dim(
                outs, jnp.where(use, x_out, cur), idx, 0
            )
            aux_sum = aux_sum + jnp.where(active, aux, 0.0)
            # rotate activations one stage forward (ICI neighbour hop)
            state = lax.ppermute(
                x_out, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            return (state, outs, aux_sum), None

        d = c.d_model
        init = (
            jnp.zeros((mb, S, d), c.dtype),
            jnp.zeros((M, mb, S, d), c.dtype),
            jnp.zeros((), jnp.float32),
        )
        (_, outs, aux_sum), _ = lax.scan(
            tick, init, jnp.arange(M + pp - 1)
        )
        # Score all microbatches in one projection. Only the last stage's
        # buffer holds real outputs; other stages' contributions are masked.
        xl = _rms_norm(outs.reshape(b, S, d), final_scale)
        logits = jnp.einsum("bsd,dv->bsv", xl, head).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(
            logp, tgts.reshape(b, S)[..., None], axis=-1
        )[..., 0]
        flat_mask = msks.reshape(b, S)
        is_last = (p == pp - 1).astype(jnp.float32)
        loss_sum = lax.psum(
            -(ll * flat_mask).sum() * is_last, ("dp", "pp")
        )
        count = lax.psum(flat_mask.sum() * is_last, ("dp", "pp"))
        ce = loss_sum / jnp.maximum(count, 1.0)
        if c.moe_experts:
            aux = lax.psum(aux_sum, ("dp", "pp"))
            den = c.n_layers * M * mesh.shape["dp"]
            ce = ce + c.moe_aux_weight * aux / den
        return ce

    pspecs, data_spec = _pipeline_specs(c, rules)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape, jnp.float32)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, data_spec, data_spec, data_spec),
        out_specs=P(),
        axis_names=_MANUAL_AXES,  # tp stays GSPMD-auto inside stages
        check_vma=False,
    )(params, batch["tokens"], batch["targets"], mask)


def pipeline_grads_1f1b(
    params: Dict,
    batch: Dict[str, jax.Array],
    config: TransformerConfig,
    mesh: Mesh,
    num_microbatches: int,
    rules: AxisRules = DEFAULT_RULES,
) -> Tuple[jax.Array, Dict]:
    """Interleaved (1F1B-style) pipeline: returns ``(loss, grads)`` with a
    HAND-WRITTEN backward — each schedule tick runs one forward microbatch
    and one backward microbatch per stage. The backward recomputes the
    stage forward from a ring buffer of stage INPUTS (``jax.vjp`` at the
    backward tick), so live activation state is the ring (~2·pp blocks of
    [mb, S, d]) regardless of the microbatch count — the GPipe-through-AD
    path's activation state grows with M instead.

    Schedule (uniform SPMD, stage p at tick t):
      forward microbatch  f = t - p
      backward microbatch b = t - (2·(pp-1) - p)
    so the last stage backs up a microbatch immediately after forwarding
    it, and gradients ripple to stage 0 over pp-1 reverse hops.

    Scoring is VOCAB-PARALLEL over the pp axis (round 4, the fix for the
    masked-projection MFU tax DESIGN.md named): the last stage's output
    for a microbatch is psum-broadcast to every stage, and each stage
    projects only its V/pp vocab shard with a global-logsumexp
    cross-entropy (Megatron-style parallel CE, here over the PIPELINE
    axis). Per backward tick every stage does V/pp of the projection —
    summed across stages that is exactly ONE projection's FLOPs, so the
    uniform-SPMD program wastes nothing, for the price of two [mb,S,d]
    psums per tick (<< the (pp-1)/pp · 2·T·d·V FLOPs it replaces).
    """
    c = config
    pp = mesh.shape["pp"]
    for ax in ("sp", "ep"):
        if mesh.shape[ax] != 1:
            raise ValueError(f"1F1B pipeline requires {ax}=1")
    if c.n_layers % pp:
        raise ValueError(f"pp={pp} must divide n_layers={c.n_layers}")
    if c.vocab_size % pp:
        raise ValueError(
            f"pp={pp} must divide vocab_size={c.vocab_size} "
            "(vocab-parallel scoring)"
        )
    if c.attn_impl != "dense":
        raise ValueError("pipeline stages use dense attention (sp=1)")
    if c.moe_experts:
        raise ValueError("1F1B pipeline does not support MoE aux losses")
    if c.tie_embeddings:
        raise ValueError(
            "1F1B vocab-parallel scoring needs an untied lm_head "
            "(the embedding must stay whole for stage-0 ingestion); "
            "use the GPipe schedule for tied-embedding models"
        )
    M = num_microbatches
    W = 2 * pp  # ring slots: max input lifetime is 2*(pp-1) ticks
    Vp = c.vocab_size // pp

    def body(params, tokens, targets, mask):
        p = lax.axis_index("pp")
        b, S = tokens.shape  # dp-local batch
        if b % M:
            raise ValueError(
                f"local batch {b} not divisible by {M} microbatches"
            )
        mb = b // M
        d = c.d_model
        positions = jnp.arange(S)
        toks = tokens.reshape(M, mb, S)
        tgts = targets.reshape(M, mb, S)
        msks = mask.reshape(M, mb, S)
        is_last = (p == pp - 1)

        def stage_fn(prm, x_act, idx):
            """One stage's forward for microbatch ``idx``: ingestion on
            stage 0 + the local layer shard. No scoring here — the
            projection lives in score_fn, vocab-sharded across stages."""
            tok = lax.dynamic_index_in_dim(toks, idx, 0, keepdims=False)
            embed = prm["embed"].astype(c.dtype)
            x_in = jnp.where(p == 0, embed[tok], x_act)

            def lyr(carry, lp):
                y, a, _ = apply_layer(
                    carry, lp, c, positions, causal_attention, mesh=None
                )
                return y, a

            lyr = remat_wrap(lyr, c)
            x_out, _aux = lax.scan(lyr, x_in, prm["layers"])
            return x_out

        def score_fn(prm, x_fin, idx):
            """Vocab-parallel CE for microbatch ``idx`` on x_fin (the
            last stage's output, replicated across pp): THIS stage's
            [d, V/pp] head block (delivered pp-sharded by the shard_map —
            no dynamic slicing) + psum-combined logsumexp/target pieces.
            Returns the GLOBAL (replicated) loss sum + count."""
            xl = _rms_norm(x_fin, prm["final_ln"]["scale"])
            hs = prm["lm_head"].astype(c.dtype)  # [d, V/pp] local block
            logits = jnp.einsum("msd,dv->msv", xl, hs).astype(jnp.float32)
            gmax = _pmax_pp_sg(jnp.max(logits, axis=-1))  # [mb,S]
            denom = lax.psum(
                jnp.exp(logits - gmax[..., None]).sum(-1), "pp"
            )
            tgt = lax.dynamic_index_in_dim(tgts, idx, 0, keepdims=False)
            mk = lax.dynamic_index_in_dim(msks, idx, 0, keepdims=False)
            loc = tgt - p * Vp
            inrange = (loc >= 0) & (loc < Vp)
            pick = jnp.take_along_axis(
                logits, jnp.clip(loc, 0, Vp - 1)[..., None], axis=-1
            )[..., 0]
            tgt_logit = lax.psum(jnp.where(inrange, pick, 0.0), "pp")
            ll = tgt_logit - (gmax + jnp.log(denom))
            return -(ll * mk).sum(), mk.sum()

        T = M + 2 * pp - 2

        def tick(carry, t):
            act_in, g_in, ring, grads, loss_sum, count = carry
            # ---- forward slot ----
            f = t - p
            f_act = (f >= 0) & (f < M)
            fidx = jnp.clip(f, 0, M - 1)
            x_out = stage_fn(params, act_in, fidx)
            slot = fidx % W
            cur = lax.dynamic_index_in_dim(ring, slot, 0, keepdims=False)
            ring = lax.dynamic_update_index_in_dim(
                ring, jnp.where(f_act, act_in, cur), slot, 0
            )
            # ---- score slot: SYNCHRONIZED across stages ----
            # The cross-stage psums inside score_fn require every stage to
            # be scoring the SAME microbatch, so scoring is its own slot
            # (not part of the staggered backward): all stages score
            # s = t-(pp-1), the microbatch whose final-stage output was
            # just produced — which is also exactly the last stage's
            # backward microbatch this tick, so dL/dx_final hands off to
            # the backward slot below with no buffering.
            s = t - (pp - 1)
            s_act = (s >= 0) & (s < M)
            sidx = jnp.clip(s, 0, M - 1)
            xf = lax.all_gather(x_out, "pp")[pp - 1]
            # seed 1/pp: psum's transpose SUMS the replicated cotangents
            # across pp, so a unit seed on every stage would inflate the
            # score grads by pp (verified against dense AD)
            seed = jnp.where(s_act, 1.0 / pp, 0.0)
            (lsum, cnt), score_vjp = jax.vjp(
                lambda pr, xf_: score_fn(pr, xf_, sidx), params, xf
            )
            # the loss is replicated across pp: accumulate on ONE stage
            gate_last = jnp.where(s_act & is_last, 1.0, 0.0)
            loss_sum = loss_sum + lsum * gate_last
            count = count + cnt * gate_last
            gp_score, dxf_p = score_vjp(
                (seed.astype(jnp.float32), jnp.zeros((), jnp.float32))
            )
            # total dL/dx_final combines every stage's shard path
            dxf = lax.psum(dxf_p.astype(jnp.float32), "pp").astype(c.dtype)
            # ---- backward slot ----
            bmb = t - (2 * (pp - 1) - p)
            b_act = (bmb >= 0) & (bmb < M)
            bidx = jnp.clip(bmb, 0, M - 1)
            rx = lax.dynamic_index_in_dim(
                ring, bidx % W, 0, keepdims=False
            )
            # cotangent: dL/dx_final on the last stage (whose backward
            # microbatch IS the score slot's), rippled grad elsewhere
            _, stage_vjp = jax.vjp(
                lambda pr, xa: stage_fn(pr, xa, bidx), params, rx
            )
            cot = jnp.where(b_act, 1.0, 0.0) * jnp.where(
                is_last, dxf, g_in
            )
            gp_stage, gx = stage_vjp(cot.astype(c.dtype))
            grads = jax.tree.map(
                lambda a, g1, g2: a + g1.astype(a.dtype) + g2.astype(
                    a.dtype
                ),
                grads, gp_stage, gp_score,
            )
            # ---- rotate: activations forward, grads backward ----
            act_next = lax.ppermute(
                x_out, "pp", [(i, (i + 1) % pp) for i in range(pp)]
            )
            g_next = lax.ppermute(
                gx.astype(c.dtype), "pp",
                [(i, (i - 1) % pp) for i in range(pp)],
            )
            return (
                act_next, g_next, ring, grads, loss_sum, count,
            ), None

        init = (
            jnp.zeros((mb, S, d), c.dtype),
            jnp.zeros((mb, S, d), c.dtype),
            jnp.zeros((W, mb, S, d), c.dtype),
            jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params),
            jnp.zeros((), jnp.float32),
            jnp.zeros((), jnp.float32),
        )
        (_, _, _, grads, loss_sum, count), _ = lax.scan(
            tick, init, jnp.arange(T)
        )
        total = lax.psum(loss_sum, ("dp", "pp"))
        n = jnp.maximum(lax.psum(count, ("dp", "pp")), 1.0)
        ce = total / n
        # grad of mean = accumulated sum-grads / token count; layer shards
        # are pp-local (each stage owns its slice), everything else is
        # replicated across pp and needs the pp-reduction too
        def finalize(path, g):
            g = g / n
            g = lax.psum(g, "dp")
            # layers AND the vocab-parallel head are pp-LOCAL shards
            # (each stage owns its slice); everything else is replicated
            # across pp and needs the pp-reduction
            if not (path and getattr(path[0], "key", None) in
                    ("layers", "lm_head")):
                g = lax.psum(g, "pp")
            return g

        grads = jax.tree_util.tree_map_with_path(finalize, grads)
        return ce, grads

    pspecs, data_spec = _pipeline_specs(c, rules, vocab_parallel_head=True)
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones(batch["tokens"].shape, jnp.float32)
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(pspecs, data_spec, data_spec, data_spec),
        out_specs=(P(), pspecs),
        axis_names=_MANUAL_AXES,
        check_vma=False,
    )(params, batch["tokens"], batch["targets"], mask)


def make_pipeline_train_step(
    config: TransformerConfig,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    state_shardings: Any,
    num_microbatches: int,
    rules: AxisRules = DEFAULT_RULES,
    schedule: str = "gpipe",
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """Pipelined twin of ``train_step.make_train_step``: same step contract.
    ``schedule="gpipe"`` differentiates the forward schedule by AD;
    ``schedule="1f1b"`` uses the interleaved hand-written backward
    (bounded activation memory — see pipeline_grads_1f1b)."""
    from ray_tpu.parallel.train_step import make_train_step

    if schedule == "gpipe":
        return make_train_step(
            config,
            mesh,
            optimizer,
            state_shardings,
            rules=rules,
            loss=partial(pipeline_loss_fn,
                         num_microbatches=num_microbatches, rules=rules),
        )
    if schedule != "1f1b":
        raise ValueError(f"unknown pipeline schedule {schedule!r}")
    return make_train_step(
        config,
        mesh,
        optimizer,
        state_shardings,
        rules=rules,
        grads_fn=lambda params, batch: pipeline_grads_1f1b(
            params, batch, config, mesh, num_microbatches, rules
        ),
    )
