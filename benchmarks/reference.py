"""Plain references, kept with the benchmark: GPT-J's block as this repo
runs it (see ``configs/*.json`` "departures"), in straightforward
``jax.numpy`` and float32, with no kernel, no cache, no batching and no
code of the program under test. Callers wrap the calls in
``jax.default_matmul_precision("highest")``.

Parameters arrive as the program lays them out (stacked over layers); a
weight may be a pair ``(q, s)``: int8 values and their float32 scales, the
weight being ``q * s``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _w(x):
    """A float32 weight from a plain or an (int8, scale) leaf."""
    if isinstance(x, tuple):
        q, s = x
        return q.astype(jnp.float32) * s.astype(jnp.float32)
    return x.astype(jnp.float32)


def _rms_norm(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, rotary_dim):
    """x [S, H, D]: rotate the first ``rotary_dim`` dims, halves layout
    (first half with second half), base 10000, position = row."""
    s = x.shape[0]
    half = rotary_dim // 2
    inv = 1.0 / (10000.0 ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def forward_logits(params, tokens, rotary_dim: int):
    """tokens [S] -> logits [S, V], float32. One sequence, causal."""
    x = _w(params["embed"])[tokens]
    s = tokens.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, lp):  # lp: ONE layer's weights
        h = _rms_norm(x, _w(lp["ln1"]["scale"]))
        q = jnp.einsum("sd,dhk->shk", h, _w(lp["attn"]["wq"]))
        k = jnp.einsum("sd,dhk->shk", h, _w(lp["attn"]["wk"]))
        v = jnp.einsum("sd,dhk->shk", h, _w(lp["attn"]["wv"]))
        q, k = _rotary(q, rotary_dim), _rotary(k, rotary_dim)
        scores = jnp.einsum("qhk,thk->hqt", q, k) * (q.shape[-1] ** -0.5)
        scores = jnp.where(causal[None], scores, -jnp.inf)
        att = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v)
        a = jnp.einsum("shk,hkd->sd", att, _w(lp["attn"]["wo"]))
        m = jax.nn.gelu(h @ _w(lp["mlp"]["wi"])) @ _w(lp["mlp"]["wo"])
        return x + a + m, None  # GPT-J's parallel block: one residual add

    x, _ = jax.lax.scan(block, x, params["layers"])  # layer after layer
    x = _rms_norm(x, _w(params["final_ln"]["scale"]))
    return x @ _w(params["lm_head"])


def loss(params, tokens, targets, rotary_dim: int):
    """Mean next-token cross-entropy over [B, S] (plain float32)."""
    def one(t, y):
        logp = jax.nn.log_softmax(
            forward_logits(params, t, rotary_dim), -1)
        return -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]

    return jnp.mean(jax.vmap(one)(tokens, targets))


def loss_and_grad_norm(params, tokens, targets, rotary_dim):
    val, grads = jax.value_and_grad(loss)(
        params, tokens, targets, rotary_dim)
    sq = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    return val, jnp.sqrt(sq)


def served_token_margin(logits, served_ids):
    """Per generated position: how far the served token's logit lies under
    the reference's largest (0 where they agree on the token)."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served_ids[:, None], -1)[:, 0]
    return best - got
