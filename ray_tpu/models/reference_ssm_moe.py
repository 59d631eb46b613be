"""Plain reference of a model whose layers are ONE branch each: a
state-space (Mamba-2) mixer, an attention, or a routed FFN whose experts
live in a latent (NVIDIA-Nemotron-3-Super-120B-A12B, ``model_type``
nemotron_h; the equations are written down from the published
``config.json``, see ISSUE 60 and ``benchmarks/configs/
nemotron3-super-l11-e128-bf16-serve.json``).

The repository's own copy, for the tier-1 tests (``tests/
test_served_models.py``, ``tests/test_nemotron_model.py``).
``benchmarks/reference_ssm_moe.py`` is the benchmark's copy and decides a
cell's ``correct``; below the marker line the two files are identical,
byte for byte, and a test holds them to it. The small helpers (RMSNorm,
the two distances) are those of the latent / routed reference beside this
file.
"""
from ray_tpu.models import reference as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# chunking, no batching, and none of the program's code. One sequence; the
# state-space recurrence token by token in a ``lax.scan``; the convolution
# as four shifted sums; full causal attention with every score
# materialised; the routed experts applied ONE AT A TIME to every token
# and weighted by the routing weight (0 where the token did not choose the
# expert), so that a layer's float32 copy never exists whole. Callers wrap
# calls in ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]):
#   x = E[t];  each layer:  x = x + Branch(norm(x))   (RMSNorm, a weight, eps)
#   logits = norm(x) W_head                                    (untied head)
#   Branch, a layer of kind "attention": q = h W_q [H heads], k = h W_k,
#        v = h W_v [Hkv heads, H / Hkv queries a KV head]; NO positional
#        term; scores q . k / sqrt(d_head); causal softmax; W_o.
#   Branch, a layer of kind "ssm" (heads H_s of width P, G groups, state N):
#        z = h W_z [H_s P]; xBC = h W_xbc [H_s P + 2 G N]; dt = h W_dt [H_s]
#        xBC_t = silu(bias + sum_k w[k] * xBC_{t-3+k})   (4 taps, zeros
#                                                before the first token)
#        [x | B | C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(a_log)
#        head j uses group j // (H_s / G)'s B and C:
#                   H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T   [P, N]
#                   y_t = H_t C_t + D x_t
#        out = W_out (w * GroupRMSNorm(y * silu(z))): the gate BEFORE the
#        norm, the mean square over each of ``norm_groups`` groups of
#        H_s P / norm_groups channels, one scale w [H_s P]
#   Branch, a layer of kind "experts" (E routed experts in a latent of
#        width l, ``top_k`` a token, one shared expert on the full width):
#        s = sigmoid(h W_r) [E]; the top_k largest of s + b chosen;
#        g_i = route_scale * s_i / sum_chosen s;  u = h W_dn [l]
#        E_i(u) = W2_i relu(W1_i u)^2                  (no gate matrix)
#        out = (sum_{i chosen} g_i E_i(u)) W_up + W2_s relu(W1_s h)^2
#
# A SHARE of the experts: where W1 holds fewer experts than the router has
# outputs, they are the experts ``first_expert ..``; the router and the
# weights g (normalised over ALL the chosen) stay whole and what an absent
# expert would have added is left out.
#
# Departures from the published model, all shared with the program:
# - W_in is three matrices (W_z, W_xbc, W_dt): the same numbers;
# - weights arrive as the program lays them out: the attention layers
#   stacked under "layers", the state-space layers under "ssm_layers", the
#   routed layers under "expert_layers", run in the order
#   ``hp["layer_types"]`` gives;
# - the multi-token-prediction module is not part of the forward.
#
# ``hp``: n_heads, n_kv_heads, d_head, eps, layer_types, ssm_heads,
# ssm_head_dim, ssm_state, ssm_groups, norm_groups, top_k, route_scale,
# first_expert. ``ablate`` computes the model WRONG in one way, for the
# comparisons that must fail:
#   state_bf16            the state rounded to bf16 after every token;
#   state_at_bucket_end   (prompt_len, bucket): as if the state were taken
#                         after the padding of a prefill bucket: bucket -
#                         prompt_len padding tokens (id 0) run through every
#                         layer after the prompt, attended by nobody;
#   drop_conv_tail        prompt_len: tokens from there on see zeros where
#                         the convolution's window reaches back into the
#                         prompt (the tail lost at the hand-off);
#   one_norm_group        the gate's norm over all channels as one group;
#   route_scale_one       route_scale taken as 1;
#   relu                  relu in place of relu squared, every expert;
#   top_k                 int: that many experts a token;
#   no_shared             the shared expert left out.

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
_rms_norm = _base._rms_norm
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance


def _w(a):
    return a.astype(F32)


def attention(h, wp, hp, unseen=None):
    """Causal attention over one sequence h [S, d] with no positional
    term. ``unseen`` [S] bool marks rows nobody else may attend (each row
    still attends itself)."""
    s = h.shape[0]
    rep = hp["n_heads"] // hp["n_kv_heads"]
    q = jnp.einsum("sd,dhk->shk", h, _w(wp["wq"]))
    k = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _w(wp["wk"])), rep, axis=1)
    v = jnp.repeat(jnp.einsum("sd,dhk->shk", h, _w(wp["wv"])), rep, axis=1)
    scores = jnp.einsum("thk,shk->hts", q, k) * hp["d_head"] ** -0.5
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
    y = (y + x * _w(wp["d"])[:, None]).reshape(s, nh * p) * jax.nn.silu(z)
    groups = 1 if ablate.get("one_norm_group") else hp["norm_groups"]
    y = y.reshape(s, groups, -1)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + hp["eps"])
    return (y.reshape(s, nh * p) * _w(wp["norm"])) @ _w(wp["wo"]), state


def route(h, wp, hp, ablate):
    """The routing weight of every (token, expert) pair, [S, E]: g_i for
    the chosen, 0 for the others."""
    top_k = ablate.get("top_k", hp["top_k"])
    s = jax.nn.sigmoid(h @ _w(wp["router"]))
    _, idx = jax.lax.top_k(s + _w(wp["bias"]), top_k)
    chosen = jnp.zeros_like(s).at[jnp.arange(s.shape[0])[:, None], idx].set(1)
    g = s * chosen
    g = g / g.sum(-1, keepdims=True)
    return g if ablate.get("route_scale_one") else g * hp["route_scale"]


def _act(m, ablate):
    m = jax.nn.relu(m)
    return m if ablate.get("relu") else m * m


def experts(h, wp, hp, ablate):
    """A routed layer's branch over one sequence h [S, d]: the experts
    this stack holds, one at a time, in the latent; the shared expert on
    the full width."""
    held = wp["wi"].shape[0]
    g = route(h, wp, hp, ablate)[:, hp["first_expert"]:][:, :held]
    u = h @ _w(wp["latent_in"])

    def one(acc, at):
        w1, w2, g_e = at
        return acc + g_e[:, None] * (_act(u @ _w(w1), ablate) @ _w(w2)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u), (wp["wi"], wp["wo"],
                                                      g.T))
    out = routed @ _w(wp["latent_out"])
    if not ablate.get("no_shared"):
        sp = wp["shared"]
        out = out + _act(h @ _w(sp["wi"]), ablate) @ _w(sp["wo"])
    return out


def layer(x, lp, hp, ablate, unseen=None):
    """One layer of any of the three kinds (its weights hold "ssm", "attn"
    or "moe"). Returns (y [S, d], the layer's state after the last token
    or None)."""
    h = _rms_norm(x, _w(lp["ln1"]["scale"]), hp["eps"])
    state = None
    if "ssm" in lp:
        a, state = ssm(h, lp["ssm"], hp, ablate)
    elif "attn" in lp:
        a = attention(h, lp["attn"], hp, unseen)
    else:
        a = experts(h, lp["moe"], hp, ablate)
    return x + a, state


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
    return params["embed"][tokens].astype(F32)


def head(params, x, hp):
    x = _rms_norm(x, _w(params["final_ln"]["scale"]), hp["eps"])
    return x @ _w(params["lm_head"])


_STACKS = {"attention": "layers", "ssm": "ssm_layers",
           "experts": "expert_layers"}


def layers_in_order(params, hp):
    """(stack name, index in that stack) of every layer, in running
    order."""
    seen = dict.fromkeys(_STACKS, 0)
    out = []
    for kind in hp["layer_types"]:
        out.append((_STACKS[kind], seen[kind]))
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


def state_head_distances(got, want):
    """The same a head, [H_s]: where the whole state's distance is carried
    by the heads that forget fastest (theirs are the largest states), a
    head's own tells a slow head's state rounded at every token, which
    loses what a token adds to it."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.sqrt(jnp.mean(diff * diff, (-2, -1))
                    / jnp.mean(want.astype(F32) ** 2, (-2, -1)))
