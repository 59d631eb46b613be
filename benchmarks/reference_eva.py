"""Plain reference of EvaByte's block (``model_type`` evabyte,
``attention_class`` eva; EVA, "Efficient Attention via Control Variates",
arXiv:2302.04542, as the released byte-level model runs it: every layer
attends its own window of 2,048 bytes exactly and every earlier window
through pooled 16-byte chunk summaries): the benchmark's copy, which
decides ``correct`` of the cells of kind ``serve_eva``
(``benchmarks/runners/serve_eva.py``). Kept under ``benchmarks/`` so that
no later PR that claims a gain can change what "correct" means.

``ray_tpu/models/reference_eva.py`` is the repository's copy, for the
tier-1 tests; below the marker line the two files are identical, byte for
byte, and a test holds them to it.
"""
# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# blocking, no batching, and none of the program's code. One sequence;
# every score of a head materialised ([S, chunks + S], one head at a
# time); the summaries of EVERY whole chunk of the sequence computed and
# masked, not kept by window. Callers wrap calls in
# ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]); W = window, C =
# chunk; token t lies in window t // W, chunk c holds tokens C c .. C c +
# C - 1, a window holds W / C chunks; s = 1 / sqrt(d_head):
#   x = E[t]                                       (float32 from here on)
#   each layer:  x = x + EVA(norm1(x)) W_o;
#                x = x + W_down(silu(h W_gate) * (h W_up)), h = norm2(x)
#   norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)  (the unit offset)
#   EVA, a head: q, k, v = h W_q, h W_k, h W_v (no bias); rotary on all
#        d_head dims of q and k (rotate-half pairs (i, i + d_head / 2),
#        base theta, absolute position t).
#        Chunk summaries from the ROTATED keys under the head's learned
#        phi, mu [d_head]:
#          k~_c = sum_j softmax_j(s k_j . phi) k_j,
#          v~_c = sum_j softmax_j(s k_j . mu) v_j,   j over the chunk.
#        Query t attends, in ONE softmax at scale s, the summaries of the
#        chunks of every window BEFORE its own (c < (W / C) (t // W)) and
#        the tokens of its own window up to itself (W (t // W) <= j <= t).
#        A window's own chunks are never seen as summaries by that
#        window's queries.
#   logits = norm(x) W_head, reshaped [n_pred_heads, V]: head i scores
#        token t + 1 + i.
#
# Assumed (``config.json`` carries no modeling file; each is in the
# configuration's ``assumed``): the pooling logits' scale s and that they
# carry no -|k_j|^2 / 2 term; nothing is added to k~_c; pooling follows
# the rotary; rotate-half pairing.
#
# Departures from the published model, all shared with the program:
# - W_q, W_k, W_v, W_o keep heads as an axis of their own; W_gate and
#   W_up are two matrices ("wg", "wi"): the same numbers;
# - the prediction heads lie side by side in ONE matrix [d, n_pred_heads
#   x V];
# - weights arrive as the program lays them out, stacked over layers
#   under "eva_layers".
#
# ``hp``: n_heads, d_head, eps, theta, window, chunk, n_pred_heads.
# ``ablate`` computes the model WRONG in one way, for the comparisons that
# must fail:
#   pool_15_of_16     a summary pooled from all of its chunk's tokens but
#                     the last;
#   swap_phi_mu       the keys pooled under mu and the values under phi;
#   open_summaries    a query also sees, as summaries, the whole chunks of
#                     its OWN window that end before it (beside their
#                     tokens);
#   residual_bf16     the residual stream rounded to bf16 after every
#                     addition;
#   pool_unrotated    summaries pooled from the keys before their rotary;
#   pool_unscaled     pooling logits k_j . phi without the scale s;
#   no_summaries      no summary is seen at all (plain window attention);
#   fp8_weights       every matrix rounded through float8_e4m3: the
#                     nearest precision below the bf16 the weights are
#                     served in.

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _weights(ablate):
    """How a matrix is read: float32, or rounded through float8 first."""
    if ablate.get("fp8_weights"):
        return lambda w: w.astype(jnp.float8_e4m3fn).astype(F32)
    return lambda w: w.astype(F32)


def rms_norm(x, g, hp):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + hp["eps"])
    return x * (1.0 + g.astype(F32))


def rotary(x, theta):
    """x [S, H, D]: rotate all D dims, pairs (i, i + D / 2), position =
    row."""
    s, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def summaries(k, v, phi, mu, hp, ablate, k_raw=None):
    """One head: k, v [S, D] (k rotated) -> (k~, v~) [S // C, D], the
    summaries of the sequence's whole chunks."""
    C, d = hp["chunk"], k.shape[-1]
    n = k.shape[0] // C
    if ablate.get("pool_unrotated"):
        k = k_raw
    if ablate.get("swap_phi_mu"):
        phi, mu = mu, phi
    kc, vc = k[:n * C].reshape(n, C, d), v[:n * C].reshape(n, C, d)
    if ablate.get("pool_15_of_16"):
        kc, vc = kc[:, :-1], vc[:, :-1]
    s = 1.0 if ablate.get("pool_unscaled") else d ** -0.5
    wk = jax.nn.softmax((kc @ phi.astype(F32)) * s, axis=-1)  # [n, C]
    wv = jax.nn.softmax((kc @ mu.astype(F32)) * s, axis=-1)
    return (wk[..., None] * kc).sum(1), (wv[..., None] * vc).sum(1)


def eva_head(q, k, v, ks, vs, hp, ablate):
    """One head's attention: q, k, v [S, D] and the whole chunks'
    summaries ks, vs [S // C, D] -> [S, D]. ONE softmax over [summaries |
    tokens]."""
    W, C = hp["window"], hp["chunk"]
    t = jnp.arange(q.shape[0])[:, None]
    j = jnp.arange(k.shape[0])[None, :]
    c = jnp.arange(ks.shape[0])[None, :]
    own = (j <= t) & (j // W == t // W)
    closed = c // (W // C) < t // W
    if ablate.get("open_summaries"):
        closed = closed | (c * C + C - 1 < t)
    if ablate.get("no_summaries"):
        closed = jnp.zeros_like(closed)
    scale = q.shape[-1] ** -0.5
    scores = jnp.concatenate([q @ ks.T, q @ k.T], -1) * scale
    scores = jnp.where(jnp.concatenate([closed, own], -1), scores, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ jnp.concatenate([vs, v], 0)


def eva(h, wp, hp, ablate):
    """The mixer over one sequence h [S, d]. Returns (out [S, d], the
    summaries (k~, v~) [S // C, H, D] of every whole chunk)."""
    w = _weights(ablate)
    q = jnp.einsum("sd,dhk->shk", h, w(wp["wq"]))
    k_raw = jnp.einsum("sd,dhk->shk", h, w(wp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, w(wp["wv"]))
    q, k = rotary(q, hp["theta"]), rotary(k_raw, hp["theta"])

    def head(at):
        q, k, v, k_raw, phi, mu = at
        ks, vs = summaries(k, v, phi, mu, hp, ablate, k_raw)
        return eva_head(q, k, v, ks, vs, hp, ablate), ks, vs

    out, ks, vs = jax.lax.map(head, (
        q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
        k_raw.swapaxes(0, 1), wp["phi"], wp["mu"]))  # [H, S, D]
    return (jnp.einsum("hsk,hkd->sd", out, w(wp["wo"])),
            (ks.swapaxes(0, 1), vs.swapaxes(0, 1)))


def _add(x, y, ablate):
    x = x + y
    if ablate.get("residual_bf16"):  # a cast there and back may be elided
        x = jax.lax.reduce_precision(x, 8, 7)
    return x


def layer(x, lp, hp, ablate):
    """One layer over x [S, d]. Returns (y [S, d], the layer's
    summaries)."""
    w = _weights(ablate)
    a, pooled = eva(rms_norm(x, lp["ln1"]["scale"], hp), lp["eva"], hp,
                    ablate)
    x = _add(x, a, ablate)
    h, m = rms_norm(x, lp["ln2"]["scale"], hp), lp["mlp"]
    ffn = (jax.nn.silu(h @ w(m["wg"])) * (h @ w(m["wi"]))) @ w(m["wo"])
    return _add(x, ffn, ablate), pooled


def embed(params, tokens, hp):
    return params["embed"][tokens].astype(F32)


def head(params, x, hp, ablate=None):
    """x [S, d] -> logits [S, n_pred_heads, V]."""
    w = _weights(ablate or {})
    x = rms_norm(x, params["final_ln"]["scale"], hp)
    logits = x @ w(params["lm_head"])
    return logits.reshape(x.shape[0], hp["n_pred_heads"], -1)


def forward_logits(params, tokens, hp, ablate=None):
    """tokens [S] -> (logits [S, n_pred_heads, V] in float32, every
    layer's summaries (k~, v~) [S // C, H, D] in running order)."""
    ablate = ablate or {}
    x = embed(params, tokens, hp)
    pooled = []
    stack = params["eva_layers"]
    for i in range(stack["ln1"]["scale"].shape[0]):
        x, kv = layer(x, jax.tree.map(lambda a: a[i], stack), hp, ablate)
        pooled.append(kv)
    return head(params, x, hp, ablate), pooled


def relative_rms(got, want):
    """An array against the reference's: the root-mean-square of the
    difference over the root-mean-square of the reference's, float32."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(want.astype(F32) ** 2))
