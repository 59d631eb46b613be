"""Plain reference of a model that mixes gated-delta-rule linear-attention
layers ("kda": a matrix state a head under a decay a channel) and
latent-attention layers with no positional term, under dropless routed
experts of which a chip holds a share, and a shared one
(Kimi-Linear-48B-A3B, ``model_type`` kimi_linear; the equations are
written down from the published ``config.json``, see ISSUE 44 and
``benchmarks/configs/kimi-linear-l8-e64-bf16-serve.json``).

The repository's own copy, for the tier-1 tests (``tests/
test_kda_moe_model.py``). ``benchmarks/reference_kda_moe.py`` is the
benchmark's copy and decides a cell's ``correct``; below the marker line
the two files are identical, byte for byte, and a test holds them to it.
The small helpers (RMSNorm, rotary, the gated FFN, the router, the two
distances) are those of the latent / routed reference beside this file.
"""
from ray_tpu.models import reference as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no chunk,
# no batching, and none of the program's code. One sequence; the delta
# rule token by token in a ``lax.scan``; the convolution as four shifted
# sums; every score of an attention head materialised ([S, S], one head at
# a time). Callers wrap calls in ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]):
#   x = E[t]
#   each layer, of kind K ("kda") or F ("attention"):
#     h = norm1(x)                       (RMSNorm with a weight, eps)
#     K: [q | k | v] = silu(conv4(h W_qkv))  (causal, depthwise, 4 taps over
#          each of the three H x D wide streams, zeros before the first
#          token, no bias); a head: q <- q / sqrt(|q|^2 + 1e-6) / sqrt(D),
#          k <- k / sqrt(|k|^2 + 1e-6)
#        g = -exp(a_log) * softplus((h W_fa) W_fb + dt_bias)   [H, D]:
#          a log-decay a channel (a_log a head); beta = sigmoid(h W_b) [H]
#        a head's state S [D, D], from zeros:
#          S <- Diag(exp g_t) S;  S <- S + beta_t k_t (v_t - S^T k_t)^T;
#          o_t = S^T q_t
#        x += W_o (RMSNorm_w(o_t) over each head's D
#                  * sigmoid((h W_ga) W_gb))
#     F: q = h W_q [H heads of nope + rope]; [c | k_r] = h W_kva;
#        c = RMSNorm_w(c); k_nope = c W_uk, v = c W_uv a head;
#        k = [k_nope | k_r], the k_r dims shared by all heads and NOT
#        rotated (no positional term); a = q . k / sqrt(nope + rope),
#        causal softmax; x += W_o (p v)
#     h2 = norm2(x)
#     FFN, the leading dense layers: x += W_o(silu(h2 W_g) * (h2 W_i))
#     FFN, the others: s = sigmoid(h2 W_r) in float32 over ALL E experts;
#        the top_k with the largest s + b; weights s_i / sum_chosen(s) x
#        route_scale; x += the sum over the chosen experts HELD here
#        (first_expert .. first_expert + held) of w_e FFN_e(h2), each
#        applied to every token in turn and masked by its weight, + the
#        shared expert's FFN (every token)
#   logits = norm(x) W_head
#
# Departures from the published model, all shared with the program:
# - W_q, W_k, W_v of a K layer are one matrix W_qkv [d, 3 H D] and the
#   three convolutions one [4, 3 H D]: the same numbers;
# - weights arrive as the program lays them out: the leading dense layers
#   (K layers here) under "dense_layers", the other K layers under
#   "kda_layers", the F layers under "layers", run in the order
#   ``hp["layer_types"]`` gives; W_ukv as two arrays; heads as an axis.
#
# ``hp``: n_heads, qk_nope, qk_rope, kv_rank, eps, theta, top_k,
# route_scale, first_expert, layer_types, n_dense_layers, kda_heads,
# kda_head_dim. ``ablate`` computes the model WRONG in one way, for the
# comparisons that must fail:
#   head_decay            one decay a head: the channels' mean of g (a
#                         gated delta rule with a scalar gate, not KDA);
#   no_delta              no correction: S += beta k v^T;
#   decay_after           the decay applied after the token's write;
#   beta_one              beta taken as 1;
#   no_l2norm             q and k not normalised (q keeps its 1/sqrt(D));
#   silu_gate             the output gate through SiLU, not a sigmoid;
#   state_bf16            the state rounded to bf16 after every token;
#   state_at_bucket_end   (prompt_len, bucket): as if the state were taken
#                         after the padding of a prefill bucket: bucket -
#                         prompt_len padding tokens (id 0) run through every
#                         layer after the prompt, attended by nobody;
#   drop_conv_tail        prompt_len: tokens from there on see zeros where
#                         the convolution's window reaches back into the
#                         prompt (the tail lost at the hand-off);
#   rotate_kr             rotary (base theta) on the k_r dims of q and k;
#   no_scale, no_shared, fp8_weights   as the latent / routed reference.

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_rms_norm = _base._rms_norm
_rotary = _base._rotary
_weights = _base._weights
gated_ffn = _base.gated_ffn
route = _base.route
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance


def kda(h, wp, hp, ablate):
    """The delta-rule mixer over one sequence h [S, d], token by token.
    Returns (out [S, d], the state after the last token [H, D, D])."""
    w = _weights(ablate)
    s, nh, dk = h.shape[0], hp["kda_heads"], hp["kda_head_dim"]
    qkv = h @ w(wp["wqkv"])
    taps = wp["conv_w"].shape[0]
    conv = jnp.zeros_like(qkv)
    rows = jnp.arange(s)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the input ``back`` tokens ago
        shifted = jnp.pad(qkv, ((back, 0), (0, 0)))[:s]
        if "drop_conv_tail" in ablate:
            cut = ablate["drop_conv_tail"]
            shifted = jnp.where(((rows >= cut) & (rows - back < cut))[:, None],
                                0.0, shifted)
        conv = conv + shifted * wp["conv_w"][j].astype(F32)
    q, k, v = (a.reshape(s, nh, dk)
               for a in jnp.split(jax.nn.silu(conv), 3, axis=-1))
    if not ablate.get("no_l2norm"):
        q = q * jax.lax.rsqrt((q * q).sum(-1, keepdims=True) + 1e-6)
        k = k * jax.lax.rsqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q = q * dk ** -0.5
    step = jax.nn.softplus((h @ w(wp["wfa"])) @ w(wp["wfb"])
                           + wp["dt_bias"].astype(F32))
    g = -jnp.exp(wp["a_log"].astype(F32))[:, None] * step.reshape(s, nh, dk)
    if ablate.get("head_decay"):
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    beta = jax.nn.sigmoid(h @ w(wp["wb"]))  # [S, H]
    if ablate.get("beta_one"):
        beta = jnp.ones_like(beta)

    def token(state, at):
        q_t, k_t, v_t, g_t, b_t = at
        keep = jnp.exp(g_t)[:, :, None]
        if not ablate.get("decay_after"):
            state = keep * state
        read = 0.0 if ablate.get("no_delta") else jnp.einsum(
            "hk,hkv->hv", k_t, state)
        state = state + k_t[:, :, None] * (
            b_t[:, None] * (v_t - read))[:, None, :]
        if ablate.get("decay_after"):
            state = keep * state
        if ablate.get("state_bf16"):  # a cast there and back may be elided
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    state, o = jax.lax.scan(token, jnp.zeros((nh, dk, dk), F32),
                            (q, k, v, g, beta))
    gate = (h @ w(wp["wga"])) @ w(wp["wgb"])
    gate = jax.nn.silu(gate) if ablate.get("silu_gate") else \
        jax.nn.sigmoid(gate)
    o = _rms_norm(o, wp["norm"].astype(F32), hp["eps"]).reshape(s, -1) * gate
    return o @ w(wp["wo"]), state


def kept_rows(h, wp, hp, ablate):
    """What an F layer keeps of every token of h [S, d]: [c | k_r] [S,
    kv_rank + qk_rope], c normed and k_r as the scores use it."""
    w = _weights(ablate)
    r = hp["kv_rank"]
    kv = h @ w(wp["wdkv"])
    k_r = kv[:, None, r:]
    if ablate.get("rotate_kr"):
        k_r = _rotary(k_r, hp["theta"])
    return jnp.concatenate(
        [_rms_norm(kv[:, :r], w(wp["kv_norm"]), hp["eps"]), k_r[:, 0]], -1)


def mla(h, wp, hp, ablate, unseen=None):
    """Latent attention with direct queries and no positional term over
    one sequence h [S, d], one head's scores at a time. ``unseen`` [S]
    bool marks rows nobody else may attend (each still attends itself)."""
    w = _weights(ablate)
    r, nope = hp["kv_rank"], hp["qk_nope"]
    s = h.shape[0]
    q = jnp.einsum("sd,dhk->shk", h, w(wp["wq"]))
    kept = kept_rows(h, wp, hp, ablate)
    c_kv, k_r = kept[:, :r], kept[:, None, r:]
    q_rope = q[..., nope:]
    if ablate.get("rotate_kr"):
        q_rope = _rotary(q_rope, hp["theta"])
    k_nope = jnp.einsum("sc,chk->shk", c_kv, w(wp["wuk"]))
    v = jnp.einsum("sc,chk->shk", c_kv, w(wp["wuv"]))
    rows = jnp.arange(s)
    mask = rows[:, None] >= rows[None, :]
    if unseen is not None:
        mask &= ~unseen[None, :] | (rows[:, None] == rows[None, :])

    def head(args):
        qn, qr, kn, v_h = args  # [S, nope], [S, rope], [S, nope], [S, v]
        a = (qn @ kn.T + qr @ k_r[:, 0].T) * (nope + hp["qk_rope"]) ** -0.5
        return jax.nn.softmax(jnp.where(mask, a, -jnp.inf), -1) @ v_h

    att = jax.lax.map(head, (
        q[..., :nope].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        k_nope.transpose(1, 0, 2), v.transpose(1, 0, 2)))  # [H, S, v]
    return jnp.einsum("hsk,hkd->sd", att, w(wp["wo"]))


def routed_experts(x, wp, hp, ablate):
    """The held experts in turn over every token; a token keeps an
    expert's output times its routing weight, which is 0 unless it chose
    it; then the shared expert. ``wp`` holds the router over all E
    experts and the weights of the experts ``hp["first_expert"]`` .. +
    held alone."""
    w = _weights(ablate)
    chosen, weights = route(x, wp, hp, ablate)
    first, n_held = hp.get("first_expert", 0), wp["wi"].shape[0]

    def one(y, e):
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(-1)  # [S]
        out = gated_ffn(x, w(wp["wg"][e]), w(wp["wi"][e]), w(wp["wo"][e]))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    if "shared" in wp and not ablate.get("no_shared"):
        sp = wp["shared"]
        y = y + gated_ffn(x, w(sp["wg"]), w(sp["wi"]), w(sp["wo"]))
    return y


def ffn(x, lp, hp, ablate):
    """The second half of a layer: x [S, d] after its mixer."""
    w = _weights(ablate)
    h = _rms_norm(x, w(lp["ln2"]["scale"]), hp["eps"])
    if "moe" in lp:
        return x + routed_experts(h, lp["moe"], hp, ablate)
    m = lp["mlp"]
    return x + gated_ffn(h, w(m["wg"]), w(m["wi"]), w(m["wo"]))


def mix(x, lp, hp, ablate, unseen=None):
    """The first half of a layer (a K layer's weights hold "kda").
    Returns (x [S, d], the layer's state after the last token or None)."""
    w = _weights(ablate)
    h = _rms_norm(x, w(lp["ln1"]["scale"]), hp["eps"])
    if "kda" in lp:
        a, state = kda(h, lp["kda"], hp, ablate)
    else:
        a, state = mla(h, lp["attn"], hp, ablate, unseen), None
    return x + a, state


def layer(x, lp, hp, ablate, unseen=None):
    """One layer's weights ``lp`` (no leading axis) over x [S, d]."""
    x, state = mix(x, lp, hp, ablate, unseen)
    return ffn(x, lp, hp, ablate), state


def with_padding(tokens, ablate):
    """The sequence a forward runs over, and which of its rows are real
    (a numpy mask: the lengths are static): the tokens themselves, or
    under ``state_at_bucket_end`` the prompt, its bucket's padding (id 0,
    attended by nobody), then the rest."""
    if "state_at_bucket_end" not in ablate:
        return tokens, np.ones(tokens.shape, bool)
    cut, bucket = ablate["state_at_bucket_end"]
    pad = jnp.zeros((bucket - cut,), tokens.dtype)
    real = np.concatenate([np.ones(cut, bool), np.zeros(bucket - cut, bool),
                           np.ones(tokens.shape[0] - cut, bool)])
    return jnp.concatenate([tokens[:cut], pad, tokens[cut:]]), real


def layers_in_order(params, hp):
    """(stack name, index in that stack) of every layer, in running
    order."""
    out, seen = [], {"dense_layers": 0, "layers": 0, "kda_layers": 0}
    for i, kind in enumerate(hp["layer_types"]):
        name = ("dense_layers" if i < hp["n_dense_layers"] else
                "kda_layers" if kind == "kda" else "layers")
        out.append((name, seen[name]))
        seen[name] += 1
    return out


def embed(params, tokens):
    return params["embed"][tokens].astype(F32)


def head(params, x, hp, ablate=None):
    w = _weights(ablate or {})
    x = _rms_norm(x, w(params["final_ln"]["scale"]), hp["eps"])
    return x @ w(params["lm_head"])


def forward_logits(params, tokens, hp, ablate=None):
    """tokens [S] -> (logits [S, V] in float32, the state of every K layer
    after the last token, in running order)."""
    ablate = ablate or {}
    seq, real = with_padding(tokens, ablate)
    x = embed(params, seq)
    states = []
    for name, i in layers_in_order(params, hp):
        lp = jax.tree.map(lambda a: a[i], params[name])
        x, state = layer(x, lp, hp, ablate, jnp.asarray(~real))
        if state is not None:
            states.append(state)
    return head(params, x[np.flatnonzero(real)], hp, ablate), states


def state_distance(got, want):
    """A state against the reference's: the root-mean-square of the
    difference over the root-mean-square of the reference's, float32."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(want.astype(F32) ** 2))
