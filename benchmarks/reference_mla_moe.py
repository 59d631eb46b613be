"""The benchmark's plain reference of the latent-attention /
routed-experts block (GLM-4.7-Flash, ``model_type`` glm4_moe_lite): the
forward that decides ``correct`` in the cells of kind ``serve_mla_moe``
(``runners/serve_mla_moe.py`` holds the served path's logits to it).

Kept under ``benchmarks/`` so that no later PR that claims a gain can
change what the served path is compared with. A copy of
``ray_tpu/models/reference.py``: below the marker line the two files are
identical, byte for byte (``tests/test_mla_moe_model.py`` checks it).
"""
# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# batching, no absorbed attention, and none of the program's code. One
# sequence, full causal attention with every head's keys and values
# materialised from the latents; the routed experts applied ONE AT A TIME
# to every token and masked by the routing weight, so that a layer's
# float32 copy never exists whole. Callers wrap calls in
# ``jax.default_matmul_precision("highest")``.
#
# The layer, for hidden x [S, d]:
#   h = x + MLA(norm1(x));  y = h + FFN(norm2(h))          (RMSNorm, eps)
#   MLA: c_q = norm(x W_dq); [q_nope | q_rope] = c_q W_uq  (per head)
#        [c_kv | k_r] = x W_dkv; c_kv = norm(c_kv)
#        k_nope = c_kv W_uk, v = c_kv W_uv                  (per head)
#        rotary on q_rope and on the one k_r all heads share
#        scores = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)
#        out = concat_h(softmax_causal(scores) v) W_o
#   FFN, dense layers: W_o(silu(x W_g) * x W_i)
#   FFN, expert layers: s = sigmoid(x W_r) in float32; the top_k experts
#        with the largest s + b; weights s_i / sum_chosen(s) * route_scale;
#        sum of the chosen experts' gated FFNs + the shared expert's.
#
# Departures from the published model, all shared with the program:
# - rotary pairs dim i with dim i + rope/2 (this repo's layout), not
#   adjacent dims; with random weights the two are a relabelling of W_uq's
#   and W_dkv's columns;
# - weights arrive as the program lays them out: stacked over layers, the
#   leading dense layers under "dense_layers", the rest under "layers";
#   W_ukv as two arrays (wuk, wuv); heads as an axis of their own;
# - the multi-token-prediction module is not part of the forward.
#
# ``hp`` (a plain dict) holds the sizes and constants: n_heads, qk_nope,
# qk_rope, kv_rank, top_k, route_scale, eps, theta. ``ablate`` (a dict of
# switches, all off by default) computes a deliberately WRONG model, to
# show that a comparison refuses it: "top_k": int, "no_shared", "no_scale",
# "select_without_bias", "weights_with_bias", "unrotated_k",
# "router_bf16" (the router's logits rounded to bf16), and "fp8_weights" (every matrix rounded through
# float8_e4m3: the nearest precision below the bf16 the weights are
# served in).

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rotary(x, theta):
    """x [S, H, R]: rotate all R dims, halves layout, position = row."""
    s, half = x.shape[0], x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _weights(ablate):
    """How a matrix is read: float32, or rounded through float8 first."""
    if ablate.get("fp8_weights"):
        return lambda w: w.astype(jnp.float8_e4m3fn).astype(F32)
    return lambda w: w.astype(F32)


def mla(x, wp, hp, ablate):
    """Latent attention in the plain form over one sequence x [S, d]."""
    w = _weights(ablate)
    r, nope = hp["kv_rank"], hp["qk_nope"]
    s = x.shape[0]
    c_q = _rms_norm(x @ w(wp["wdq"]), w(wp["q_norm"]), hp["eps"])
    q = jnp.einsum("sr,rhk->shk", c_q, w(wp["wuq"]))
    kv = x @ w(wp["wdkv"])
    c_kv = _rms_norm(kv[:, :r], w(wp["kv_norm"]), hp["eps"])
    k_r = kv[:, None, r:]  # [S, 1, rope]: one for all heads
    q_rope = _rotary(q[..., nope:], hp["theta"])
    if not ablate.get("unrotated_k"):
        k_r = _rotary(k_r, hp["theta"])
    k_nope = jnp.einsum("sc,chk->shk", c_kv, w(wp["wuk"]))
    v = jnp.einsum("sc,chk->shk", c_kv, w(wp["wuv"]))
    scores = (jnp.einsum("qhk,thk->hqt", q[..., :nope], k_nope)
              + jnp.einsum("qhk,tk->hqt", q_rope, k_r[:, 0]))
    scores = scores * (nope + hp["qk_rope"]) ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jnp.einsum("hqt,thk->qhk", jax.nn.softmax(scores, -1), v)
    return jnp.einsum("shk,hkd->sd", att, w(wp["wo"]))


def gated_ffn(x, wg, wi, wo):
    return (jax.nn.silu(x @ wg) * (x @ wi)) @ wo


def route(x, wp, hp, ablate):
    """Per token: the chosen experts [S, k] and their weights [S, k]."""
    router, bias = wp["router"].astype(F32), wp["bias"].astype(F32)
    logits = x @ router
    if ablate.get("router_bf16"):  # what a bf16 product would hand on
        logits = jax.lax.reduce_precision(logits, 8, 7)
    s = jax.nn.sigmoid(logits)
    biased = s + bias
    k = ablate.get("top_k", hp["top_k"])
    _, chosen = jax.lax.top_k(
        s if ablate.get("select_without_bias") else biased, k)
    src = biased if ablate.get("weights_with_bias") else s
    picked = jnp.take_along_axis(src, chosen, -1)
    scale = 1.0 if ablate.get("no_scale") else hp["route_scale"]
    return chosen, picked / picked.sum(-1, keepdims=True) * scale


def routed_experts(x, wp, hp, ablate):
    """Every expert in turn over every token; a token keeps an expert's
    output times its routing weight, which is 0 unless it chose it."""
    w = _weights(ablate)
    chosen, weights = route(x, wp, hp, ablate)
    n_experts = wp["router"].shape[-1]

    def one(y, e):
        mine = jnp.where(chosen == e, weights, 0.0).sum(-1)  # [S]
        out = gated_ffn(x, w(wp["wg"][e]), w(wp["wi"][e]), w(wp["wo"][e]))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_experts))
    if "shared" in wp and not ablate.get("no_shared"):
        sp = wp["shared"]
        y = y + gated_ffn(x, w(sp["wg"]), w(sp["wi"]), w(sp["wo"]))
    return y


def block(x, lp, hp, ablate):
    """One layer's weights ``lp`` (no leading axis) over x [S, d]."""
    w = _weights(ablate)
    h = x + mla(_rms_norm(x, w(lp["ln1"]["scale"]), hp["eps"]),
                lp["attn"], hp, ablate)
    n = _rms_norm(h, w(lp["ln2"]["scale"]), hp["eps"])
    if "moe" in lp:
        return h + routed_experts(n, lp["moe"], hp, ablate)
    m = lp["mlp"]
    return h + gated_ffn(n, w(m["wg"]), w(m["wi"]), w(m["wo"]))


def forward_logits(params, tokens, hp, last=None, ablate=None):
    """tokens [S] -> logits [S, V] in float32 (the last ``last`` positions
    only, where given: the head over a whole long prompt is large)."""
    ablate = ablate or {}
    w = _weights(ablate)
    x = params["embed"][tokens].astype(F32)
    for group in ("dense_layers", "layers"):
        if group in params:
            x, _ = jax.lax.scan(
                lambda x, lp: (block(x, lp, hp, ablate), None),
                x, params[group])  # layer after layer
    if last is not None:
        x = x[-last:]
    x = _rms_norm(x, w(params["final_ln"]["scale"]), hp["eps"])
    return x @ w(params["lm_head"])


def served_token_margin(logits, served_ids):
    """Per generated position: how far the served token's logit lies under
    the reference's largest (0 where they agree on the token)."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served_ids[:, None], -1)[:, 0]
    return best - got


def vector_distance(got, want):
    """A logit vector against the reference's: (largest absolute
    difference, root-mean-square difference), float32."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.max(jnp.abs(diff)), jnp.sqrt(jnp.mean(diff * diff))
