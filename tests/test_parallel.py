"""Pipeline (pp) and expert (ep) parallelism tests — 8 virtual CPU devices.

These cover the two parallelism axes the reference lacks entirely
(SURVEY.md §2.5): a GPipe schedule over ``pp`` via shard_map/ppermute, and
GShard-style MoE with experts sharded over ``ep``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_fn,
)
from ray_tpu.ops.moe import moe_ffn
from ray_tpu.parallel.mesh import MeshConfig, build_mesh
from ray_tpu.parallel.pipeline import (
    make_pipeline_train_step,
    pipeline_loss_fn,
)
from ray_tpu.parallel.train_step import (
    batch_sharding,
    default_optimizer,
    make_sharded_state,
    make_train_step,
)


def _f32_tiny(**kw):
    cfg = TransformerConfig.tiny(**kw)
    return dataclasses.replace(cfg, dtype=jnp.float32)


# ---------------------------------------------------------------------------
# Pipeline parallelism
# ---------------------------------------------------------------------------

def test_pipeline_matches_dense_loss_and_grads():
    cfg = _f32_tiny(max_seq_len=32, n_layers=4)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mask": jnp.ones((8, 32), jnp.float32)}
    mesh = build_mesh(MeshConfig(dp=2, pp=4))

    ref = float(loss_fn(params, batch, cfg))
    pl = float(
        jax.jit(
            lambda p, b: pipeline_loss_fn(p, b, cfg, mesh, num_microbatches=2)
        )(params, batch)
    )
    assert abs(ref - pl) < 1e-5, (ref, pl)

    gd = jax.grad(lambda p: loss_fn(p, batch, cfg))(params)
    gp = jax.jit(
        jax.grad(
            lambda p: pipeline_loss_fn(p, batch, cfg, mesh, num_microbatches=2)
        )
    )(params)
    errs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), gd, gp)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs


def test_pipeline_train_step_loss_decreases():
    cfg = _f32_tiny(max_seq_len=32, n_layers=4)
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    opt = default_optimizer(lr=1e-2)
    state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
    # layer stack is genuinely partitioned over pp
    assert state.params["layers"]["mlp"]["wi"].sharding.spec[0] == "pp"
    step = make_pipeline_train_step(cfg, mesh, opt, state_sh,
                                    num_microbatches=2)
    tokens = jnp.ones((8, 32), jnp.int32)
    batch = {
        "tokens": jax.device_put(tokens, batch_sharding(mesh)),
        "targets": jax.device_put(tokens, batch_sharding(mesh)),
        "mask": jax.device_put(jnp.ones((8, 32), jnp.float32),
                               batch_sharding(mesh)),
    }
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# Expert parallelism / MoE
# ---------------------------------------------------------------------------

def test_moe_matches_brute_force():
    G, N, D, F, E, K = 2, 16, 8, 16, 4, 2
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (G, N, D), jnp.float32)
    rw = jax.random.normal(ks[1], (D, E)) * 0.5
    wi = jax.random.normal(ks[2], (E, D, F)) * 0.2
    wo = jax.random.normal(ks[3], (E, F, D)) * 0.2
    # capacity_factor = E => nothing can be dropped => exact
    out, aux = moe_ffn(x, rw, wi, wo, top_k=K, capacity_factor=float(E))

    probs = np.asarray(jax.nn.softmax(x @ rw, -1))
    ref = np.zeros((G, N, D), np.float32)
    for g in range(G):
        for n in range(N):
            chosen = np.argsort(-probs[g, n])[:K]
            gsum = probs[g, n][chosen].sum()
            for e in chosen:
                h = np.asarray(jax.nn.gelu(x[g, n] @ wi[e]))
                ref[g, n] += (probs[g, n, e] / gsum) * (h @ wo[e])
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-5)
    assert float(aux) > 0.0


def test_moe_capacity_drops_tokens():
    """With capacity 1 and a router forcing everyone to expert 0, all but
    one token per group must be dropped (combine weight 0 -> output 0)."""
    G, N, D, F, E = 1, 8, 4, 8, 2
    x = jnp.ones((G, N, D), jnp.float32)
    rw = jnp.zeros((D, E)).at[:, 0].set(10.0)  # everyone -> expert 0
    wi = jnp.ones((E, D, F)) * 0.1
    wo = jnp.ones((E, F, D)) * 0.1
    out, _ = moe_ffn(x, rw, wi, wo, top_k=1, capacity_factor=E / N)
    # capacity = max(1, int(1*8*(2/8)/2)) = 1 -> only the first token served
    norms = jnp.linalg.norm(out[0], axis=-1)
    assert float(norms[0]) > 0.0
    np.testing.assert_allclose(np.asarray(norms[1:]), 0.0, atol=1e-6)


def test_moe_ep_sharded_matches_unsharded():
    G, N, D, F, E, K = 4, 16, 8, 16, 4, 2
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (G, N, D), jnp.float32)
    rw = jax.random.normal(ks[1], (D, E)) * 0.5
    wi = jax.random.normal(ks[2], (E, D, F)) * 0.2
    wo = jax.random.normal(ks[3], (E, F, D)) * 0.2
    out, _ = moe_ffn(x, rw, wi, wo, top_k=K, capacity_factor=float(E))

    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = build_mesh(MeshConfig(dp=2, ep=2, tp=2))
    xs = jax.device_put(x, NamedSharding(mesh, P(("dp", "ep"))))
    out_sh = jax.jit(
        lambda x: moe_ffn(x, rw, wi, wo, top_k=K,
                          capacity_factor=float(E), mesh=mesh)[0]
    )(xs)
    np.testing.assert_allclose(np.asarray(out_sh), np.asarray(out), atol=1e-5)


def test_moe_transformer_train_step_ep():
    """Full MoE transformer trains on a dp=2/ep=2/tp=2 mesh; experts are
    genuinely sharded over ep and the loss decreases."""
    cfg = _f32_tiny(max_seq_len=32)
    cfg = dataclasses.replace(cfg, moe_experts=4, moe_top_k=2,
                              moe_capacity_factor=2.0)
    mesh = build_mesh(MeshConfig(dp=2, ep=2, tp=2))
    opt = default_optimizer(lr=1e-2)
    state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
    assert state.params["layers"]["moe"]["wi"].sharding.spec[1] == "ep"
    step = make_train_step(cfg, mesh, opt, state_sh)
    tokens = jnp.ones((8, 32), jnp.int32)
    sh = batch_sharding(mesh)
    batch = {
        "tokens": jax.device_put(tokens, sh),
        "targets": jax.device_put(tokens, sh),
        "mask": jax.device_put(jnp.ones((8, 32), jnp.float32), sh),
    }
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


# ---------------------------------------------------------------------------
# Ulysses sequence parallelism + collective API
# ---------------------------------------------------------------------------

def test_ulysses_attention_matches_dense():
    from ray_tpu.ops.ulysses_attention import ulysses_attention
    from ray_tpu.ops.attention import causal_attention

    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    b, s, h, d = 2, 32, 4, 8
    q = jax.random.normal(jax.random.key(0), (b, s, h, d), jnp.float32)
    k = jax.random.normal(jax.random.key(1), (b, s, h, d), jnp.float32)
    v = jax.random.normal(jax.random.key(2), (b, s, h, d), jnp.float32)
    dense = causal_attention(q, k, v)
    uly = ulysses_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(dense), atol=2e-5)


def test_ulysses_attention_gqa():
    from ray_tpu.ops.ulysses_attention import ulysses_attention
    from ray_tpu.ops.attention import causal_attention

    mesh = build_mesh(MeshConfig(dp=4, sp=2, tp=1))
    b, s, h, hkv, d = 4, 16, 4, 1, 8  # kv heads < sp: replicated inside
    q = jax.random.normal(jax.random.key(0), (b, s, h, d))
    k = jax.random.normal(jax.random.key(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.key(2), (b, s, hkv, d))
    dense = causal_attention(q, k, v)
    uly = ulysses_attention(q, k, v, mesh=mesh)
    np.testing.assert_allclose(np.asarray(uly), np.asarray(dense), atol=2e-5)


def test_ulysses_transformer_train_step():
    cfg = _f32_tiny(max_seq_len=32)
    cfg = dataclasses.replace(cfg, attn_impl="ulysses")
    mesh = build_mesh(MeshConfig(dp=2, sp=2, tp=2))
    opt = default_optimizer(lr=1e-2)
    state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
    step = make_train_step(cfg, mesh, opt, state_sh)
    tokens = jnp.ones((8, 32), jnp.int32)
    sh = batch_sharding(mesh)
    batch = {
        "tokens": jax.device_put(tokens, sh),
        "targets": jax.device_put(tokens, sh),
        "mask": jax.device_put(jnp.ones((8, 32), jnp.float32), sh),
    }
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses


def test_in_graph_collective_verbs():
    from ray_tpu.util.collective import in_graph

    mesh = build_mesh(MeshConfig(dp=8))
    from jax.sharding import NamedSharding, PartitionSpec as P

    x = jnp.arange(16.0).reshape(8, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P("dp")))

    def body(x):
        total = in_graph.allreduce(x.sum(), "dp")
        gathered = in_graph.allgather(x, "dp")
        return total, gathered

    total, gathered = jax.shard_map(
        body, mesh=mesh, in_specs=P("dp"),
        out_specs=(P(), P("dp", None)), check_vma=False,
    )(xs)
    assert float(total) == float(x.sum())


def test_pipeline_composes_with_tp():
    """pp x tp: the stage program is tp-sharded by GSPMD inside the manual
    (dp, pp) shard_map — loss and grads still match dense exactly."""
    cfg = _f32_tiny(max_seq_len=32, n_layers=4)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mask": jnp.ones((8, 32), jnp.float32)}
    mesh = build_mesh(MeshConfig(dp=2, pp=2, tp=2))

    ref = float(loss_fn(params, batch, cfg))
    pl = float(
        jax.jit(
            lambda p, b: pipeline_loss_fn(p, b, cfg, mesh, num_microbatches=2)
        )(params, batch)
    )
    assert abs(ref - pl) < 1e-5, (ref, pl)
    gd = jax.grad(lambda p: loss_fn(p, batch, cfg))(params)
    gp = jax.jit(
        jax.grad(
            lambda p: pipeline_loss_fn(p, batch, cfg, mesh, num_microbatches=2)
        )
    )(params)
    errs = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), gd, gp)
    assert max(jax.tree.leaves(errs)) < 1e-5, errs


def test_1f1b_grads_match_dense():
    """The hand-written interleaved backward reproduces dense grads."""
    from ray_tpu.parallel.pipeline import pipeline_grads_1f1b

    cfg = _f32_tiny(max_seq_len=32, n_layers=4)
    params = init_params(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens,
             "mask": jnp.ones((8, 32), jnp.float32)}
    for mesh_cfg, M in ((MeshConfig(dp=2, pp=4), 2),
                        (MeshConfig(dp=2, pp=4), 4),
                        (MeshConfig(dp=2, pp=2, tp=2), 2)):
        mesh = build_mesh(mesh_cfg)
        ref_l = float(loss_fn(params, batch, cfg))
        gd = jax.grad(lambda p: loss_fn(p, batch, cfg))(params)
        l, g = jax.jit(
            lambda p, b: pipeline_grads_1f1b(p, b, cfg, mesh,
                                             num_microbatches=M)
        )(params, batch)
        assert abs(ref_l - float(l)) < 1e-5, (mesh_cfg, ref_l, float(l))
        errs = jax.tree.map(
            lambda a, b: float(jnp.abs(a - b).max()), gd, g
        )
        assert max(jax.tree.leaves(errs)) < 1e-4, (mesh_cfg, errs)


def test_1f1b_train_step_and_memory_vs_gpipe():
    """1F1B trains (loss decreases) and its compiled activation footprint
    beats GPipe's at many microbatches (the schedule exists to bound
    in-flight activations by ~pp instead of M)."""
    from ray_tpu.parallel.pipeline import make_pipeline_train_step

    cfg = _f32_tiny(max_seq_len=64, n_layers=4, d_model=128, d_ff=512)
    mesh = build_mesh(MeshConfig(dp=2, pp=4))
    opt = default_optimizer(lr=1e-2)
    state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
    M = 8
    tokens = jnp.ones((16, 64), jnp.int32)
    batch = {
        "tokens": jax.device_put(tokens, batch_sharding(mesh)),
        "targets": jax.device_put(tokens, batch_sharding(mesh)),
        "mask": jax.device_put(jnp.ones((16, 64), jnp.float32),
                               batch_sharding(mesh)),
    }
    step_1f1b = make_pipeline_train_step(
        cfg, mesh, opt, state_sh, num_microbatches=M, schedule="1f1b"
    )
    step_gpipe = make_pipeline_train_step(
        cfg, mesh, opt, state_sh, num_microbatches=M, schedule="gpipe"
    )
    mem = {}
    for name, step in (("1f1b", step_1f1b), ("gpipe", step_gpipe)):
        lowered = step.lower(state, batch)
        ana = lowered.compile().memory_analysis()
        mem[name] = int(getattr(ana, "temp_size_in_bytes", 0))
    assert mem["1f1b"] < mem["gpipe"], mem

    losses = []
    for _ in range(5):
        state, m = step_1f1b(state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0], losses
