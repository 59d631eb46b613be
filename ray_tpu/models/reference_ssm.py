"""Plain reference of a model that mixes state-space (Mamba-2) layers and
attention layers (granite-4.0-h-micro, ``model_type`` granitemoehybrid;
the equations are written down from the published ``config.json``, see
ISSUE 35 and ``benchmarks/configs/granite4-h-micro-bf16-serve.json``).

The repository's own copy, for the tier-1 tests (``tests/
test_ssm_hybrid_model.py``). ``benchmarks/reference_ssm.py`` is the
benchmark's copy and decides a cell's ``correct``; below the marker line
the two files are identical, byte for byte, and a test holds them to it.
The small helpers (RMSNorm, the gated FFN, the two distances) are those of
the latent / routed reference beside this file.
"""
from ray_tpu.models import reference as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# chunking, no batching, and none of the program's code. One sequence; the
# state-space recurrence token by token in a ``lax.scan``; the convolution
# as four shifted sums; full causal attention with every score
# materialised. Callers wrap calls in
# ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]):
#   x = embed_scale * E[t]
#   each layer:  x = x + residual_scale * Mixer(norm1(x))
#                x = x + residual_scale * W_o(silu(h W_g) * (h W_i)),
#                                                   h = norm2(x)
#   logits = logit_scale * norm(x) E^T          (RMSNorm with a weight, eps)
#   Mixer, a layer of kind "attention": q = h W_q [H heads], k = h W_k,
#        v = h W_v [Hkv heads, H / Hkv queries a KV head]; NO positional
#        term; scores q . k * attn_scale; causal softmax; W_o.
#   Mixer, a layer of kind "ssm" (heads H_s of width P, G groups, state N):
#        z = h W_z [H_s P]; xBC = h W_xbc [H_s P + 2 G N]; dt = h W_dt [H_s]
#        xBC_t = silu(bias + sum_k w[k] * xBC_{t-3+k})   (4 taps, zeros
#                                                before the first token)
#        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(a_log)
#        per head:  H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T   [P, N]
#                   y_t = H_t C_t + D x_t
#        out = W_out RMSNorm_w(y * silu(z))       (over all H_s P, one group)
#
# Departures from the published model, all shared with the program:
# - W_in is three matrices (W_z, W_xbc, W_dt): the same numbers;
# - weights arrive as the program lays them out: the attention layers
#   stacked under "layers", the state-space layers under "ssm_layers",
#   run in the order ``hp["layer_types"]`` gives.
#
# ``hp``: n_heads, n_kv_heads, d_head, eps, embed_scale, residual_scale,
# logit_scale, attn_scale, layer_types, ssm_heads, ssm_head_dim, ssm_state,
# ssm_groups. ``ablate`` computes the model WRONG in one way, for the
# comparisons that must fail:
#   state_bf16            the state rounded to bf16 after every token;
#   state_at_bucket_end   (prompt_len, bucket): as if the state were taken
#                         after the padding of a prefill bucket: bucket -
#                         prompt_len padding tokens (id 0) run through every
#                         layer after the prompt, attended by nobody;
#   drop_conv_tail        prompt_len: tokens from there on see zeros where
#                         the convolution's window reaches back into the
#                         prompt (the tail lost at the hand-off);
#   residual_one          residual_scale taken as 1;
#   usual_attn_scale      scores scaled by 1/sqrt(d_head).

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_rms_norm = _base._rms_norm
gated_ffn = _base.gated_ffn
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance


def _w(a):
    return a.astype(F32)


def attention(h, wp, hp, ablate, unseen=None):
    """Causal attention over one sequence h [S, d] with no positional
    term. ``unseen`` [S] bool marks rows nobody else may attend (each row
    still attends itself)."""
    s = h.shape[0]
    rep = hp["n_heads"] // hp["n_kv_heads"]
    q = jnp.einsum("sd,dhk->shk", h, _w(wp["wq"]))
    k = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _w(wp["wk"])), rep, axis=1)
    v = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _w(wp["wv"])), rep, axis=1)
    scale = (hp["d_head"] ** -0.5 if ablate.get("usual_attn_scale")
             else hp["attn_scale"])
    scores = jnp.einsum("thk,shk->hts", q, k) * scale
    rows = jnp.arange(s)
    mask = rows[:, None] >= rows[None, :]
    if unseen is not None:
        mask &= ~unseen[None, :] | (rows[:, None] == rows[None, :])
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shk->thk", probs, v)
    return jnp.einsum("thk,hkd->td", out, _w(wp["wo"]))


def ssm(h, wp, hp, ablate):
    """The state-space mixer over one sequence h [S, d], token by token.
    Returns (out [S, d], the state after the last token [H_s, P, N])."""
    s = h.shape[0]
    nh, p, n, g = (hp["ssm_heads"], hp["ssm_head_dim"], hp["ssm_state"],
                   hp["ssm_groups"])
    z = h @ _w(wp["wz"])
    xbc = h @ _w(wp["wxbc"])
    dt = jax.nn.softplus(h @ _w(wp["wdt"]) + _w(wp["dt_bias"]))
    taps = wp["conv_w"].shape[0]
    conv = jnp.zeros_like(xbc) + _w(wp["conv_b"])
    rows = jnp.arange(s)
    for k in range(taps):
        back = taps - 1 - k  # tap k reads the input ``back`` tokens ago
        shifted = jnp.pad(xbc, ((back, 0), (0, 0)))[:s]
        if "drop_conv_tail" in ablate:
            cut = ablate["drop_conv_tail"]
            shifted = jnp.where(((rows >= cut) & (rows - back < cut))[:, None],
                                0.0, shifted)
        conv = conv + shifted * _w(wp["conv_w"])[k]
    conv = jax.nn.silu(conv)
    x = conv[:, :nh * p].reshape(s, nh, p)
    B = jnp.repeat(conv[:, nh * p:nh * p + g * n].reshape(s, g, n),
                   nh // g, axis=1)
    C = jnp.repeat(conv[:, nh * p + g * n:].reshape(s, g, n), nh // g, axis=1)
    A = -jnp.exp(_w(wp["a_log"]))

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if ablate.get("state_bf16"):  # a cast there and back may be elided
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hpn,hn->hp", state, c_t)

    state, y = jax.lax.scan(token, jnp.zeros((nh, p, n), F32), (x, B, C, dt))
    y = (y + x * _w(wp["d"])[:, None]).reshape(s, nh * p)
    y = _rms_norm(y * jax.nn.silu(z), _w(wp["norm"]), hp["eps"])
    return y @ _w(wp["wo"]), state


def layer(x, lp, hp, ablate, unseen=None):
    """One layer of either kind (an "ssm" layer's weights hold "ssm").
    Returns (y [S, d], the layer's state after the last token or None)."""
    scale = 1.0 if ablate.get("residual_one") else hp["residual_scale"]
    h = _rms_norm(x, _w(lp["ln1"]["scale"]), hp["eps"])
    if "ssm" in lp:
        a, state = ssm(h, lp["ssm"], hp, ablate)
    else:
        a, state = attention(h, lp["attn"], hp, ablate, unseen), None
    x = x + scale * a
    h = _rms_norm(x, _w(lp["ln2"]["scale"]), hp["eps"])
    m = lp["mlp"]
    return x + scale * gated_ffn(h, _w(m["wg"]), _w(m["wi"]),
                                 _w(m["wo"])), state


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


def embed(params, tokens, hp):
    return hp["embed_scale"] * params["embed"][tokens].astype(F32)


def head(params, x, hp):
    x = _rms_norm(x, _w(params["final_ln"]["scale"]), hp["eps"])
    return hp["logit_scale"] * (x @ _w(params["embed"]).T)


def layers_in_order(params, hp):
    """(stack name, index in that stack) of every layer, in running
    order."""
    seen = {"attention": 0, "ssm": 0}
    names = {"attention": "layers", "ssm": "ssm_layers"}
    out = []
    for kind in hp["layer_types"]:
        out.append((names[kind], seen[kind]))
        seen[kind] += 1
    return out


def forward_logits(params, tokens, hp, ablate=None):
    """tokens [S] -> (logits [S, V] in float32, the state of every "ssm"
    layer after the last token, in running order)."""
    ablate = ablate or {}
    seq, real = with_padding(tokens, ablate)
    x = embed(params, seq, hp)
    states = []
    for name, i in layers_in_order(params, hp):
        lp = jax.tree.map(lambda a: a[i], params[name])
        x, state = layer(x, lp, hp, ablate, jnp.asarray(~real))
        if state is not None:
            states.append(state)
    return head(params, x[np.flatnonzero(real)], hp), states


def state_distance(got, want):
    """A state against the reference's: the root-mean-square of the
    difference over the root-mean-square of the reference's, float32."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(want.astype(F32) ** 2))
