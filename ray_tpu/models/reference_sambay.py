"""Plain reference of a decoder-hybrid-decoder (Phi-4-mini-flash-reasoning,
``model_type`` phi4flash, arXiv:2507.06607: Mamba-1 and window
differential-attention layers below ONE full differential-attention layer,
and above it layers that keep nothing of their own, "cross" layers
attending that layer's rows and gated memory units gated by the last
Mamba layer's output; the equations are written down from the published
``config.json`` and the paper, see ISSUE 49 and
``benchmarks/configs/phi4-mini-flash-bf16-serve.json``).

The repository's own copy, for the tier-1 tests (``tests/
test_sambay_model.py``). ``benchmarks/reference_sambay.py`` is the
benchmark's copy and decides a cell's ``correct``; below the marker line
the two files are identical, byte for byte, and a test holds them to it.
The small helpers (RMSNorm, the gated FFN, the two distances) are those of
the latent / routed reference beside this file.
"""
from ray_tpu.models import reference as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# chunking, no batching, and none of the program's code. One sequence; the
# Mamba-1 recurrence token by token in a ``lax.scan``; the convolution as
# shifted sums; differential attention as FOUR softmax attentions a pair
# of heads and a subtraction (not the padded grouped-query reading the
# program takes), every score materialised; the upper layers over EVERY
# token. Callers wrap calls in ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]), N = 32 layers:
#   x = E[t]
#   each layer:  x = x + Mixer(LN1(x));  x = x + W_o(silu(h W_g) * (h W_i)),
#                h = LN2(x)     (LayerNorm: mean subtracted, scale AND bias)
#   logits = LN(x) E^T                              (tied, no logit bias)
#   No positional term anywhere.
#   "mamba" (even layers up to N/2): [x | z] = h W; x = silu(conv(x)) (4
#        taps, causal, depthwise, with bias); [dt_low | B | C] = x W_x;
#        dt = softplus(dt_low W_dt + b_dt) a channel; A = -exp(a_log)
#        [state, channel]; h_t[n, c] = exp(dt_t[c] A[n, c]) h_{t-1}[n, c]
#        + dt_t[c] x_t[c] B_t[n]; y_t[c] = sum_n h_t[n, c] C_t[n] + D[c]
#        x_t[c]; out = (y * silu(z)) W_out. The layer hands ``y`` on.
#   "window" (odd layers below N/2) and "attention" (layer N/2 + 1):
#        q = h W_q + b [H heads], k, v = h W_k + b, h W_v + b [Hkv heads];
#        differential attention (below) over the rows t - window < s <= t,
#        or every row s <= t; W_o with a bias. The "attention" layer hands
#        its k and v on.
#   "gmu" (even layers above): out = (m * silu(h W_1)) W_2, m the ``y`` of
#        the last "mamba" layer below, for the same token.
#   "cross" (odd layers above): q = h W_q + b against the k and v the
#        "attention" layer handed on, rows s <= t; W_o with a bias.
#   Differential attention: query heads (2i, 2i + 1) and KV heads (2j, 2j
#        + 1), j = i // (H / Hkv); A(q, k, V) causal softmax attention at
#        scale 1/sqrt(d_head), V_j = [v_2j | v_2j+1]:
#        o_i = A(q_2i, k_2j, V_j) - lambda A(q_2i+1, k_2j+1, V_j),
#        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
#        lambda_init = 0.8 - 0.6 exp(-0.3 depth) by the layer's index;
#        then RMSNorm_w(o_i) over its 2 d_head channels, x (1 -
#        lambda_init); the H / 2 outputs feed W_o.
#
# Departures from the published model, all shared with the program:
# - W_in is two matrices (W_x, W_z), W_qkv three with heads as an axis,
#   W_gate_up two (W_g, W_i): the same numbers;
# - A is kept [state, channel], the transpose of the published [channel,
#   state];
# - weights arrive as the program lays them out, a stack a kind ("layers"
#   the one attention layer, "mamba_layers", "window_layers", "gmu_layers",
#   "cross_layers"), run in the order ``hp["layer_types"]`` gives.
#
# ``hp``: n_heads, n_kv_heads, d_head, eps, window, layer_types,
# mamba_state, mamba_dt_rank. ``ablate`` computes the model WRONG in one
# way, for the comparisons that must fail:
#   lambda_zero           lambda taken as 0 (plain attention of the pair's
#                         first heads);
#   m_after_gate          the "gmu" layers gated by y * silu(z), the mamba
#                         layer's output AFTER its own gate;
#   state_bf16            the state rounded to bf16 after every token;
#   keep_lambda_init      the factor (1 - lambda_init) dropped;
#   cross_strict          the "cross" layers attend the rows s < t alone,
#                         without the token's own (the first token: itself);
#   window                int: another window;
#   rms_norm              every LayerNorm without its mean and its bias;
#   state_at_bucket_end   (prompt_len, bucket): as if the state were taken
#                         after the padding of a prefill bucket (padding
#                         tokens of id 0 run through every layer after the
#                         prompt, attended by nobody, no position counted);
#   drop_conv_tail        prompt_len: tokens from there on see zeros where
#                         the convolution's window reaches back into the
#                         prompt (the tail lost at the hand-off).

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
gated_ffn = _base.gated_ffn
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance

STACKS = {"attention": "layers", "window": "window_layers",
          "mamba": "mamba_layers", "gmu": "gmu_layers",
          "cross": "cross_layers"}


def _w(a):
    return a.astype(F32)


def layer_norm(x, p, hp, ablate):
    if ablate.get("rms_norm"):
        return _base._rms_norm(x, _w(p["scale"]), hp["eps"])
    x = x - jnp.mean(x, -1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + hp["eps"])
    return x * _w(p["scale"]) + _w(p["bias"])


def softmax_attention(q, k, v, mask, scale):
    """q [T, D], k [S, D], v [S, Dv], mask [T, S] -> [T, Dv]."""
    scores = jnp.where(mask, (q @ k.T) * scale, -jnp.inf)
    return jax.nn.softmax(scores, axis=-1) @ v


def differential(q, k, v, wp, hp, ablate, depth, mask):
    """q [T, H, D] against k, v [S, Hkv, D] under ``mask`` [T, S]: the
    H / 2 differential heads' outputs, normed, [T, H / 2 x 2 D]."""
    n_h, d = q.shape[1], q.shape[2]
    rep = n_h // k.shape[1]
    scale = d ** -0.5
    init = 0.8 - 0.6 * jnp.exp(-0.3 * depth)
    lam = _w(wp["lambda"])
    full = (jnp.exp(lam[0] @ lam[1]) - jnp.exp(lam[2] @ lam[3]) + init)
    if ablate.get("lambda_zero"):
        full = 0.0
    outs = []
    for i in range(n_h // 2):
        j = i // rep
        pair = jnp.concatenate([v[:, 2 * j], v[:, 2 * j + 1]], -1)
        o = (softmax_attention(q[:, 2 * i], k[:, 2 * j], pair, mask, scale)
             - full * softmax_attention(q[:, 2 * i + 1], k[:, 2 * j + 1],
                                        pair, mask, scale))
        o = _base._rms_norm(o, _w(wp["subln"]), hp["eps"])
        outs.append(o if ablate.get("keep_lambda_init") else o * (1 - init))
    return jnp.concatenate(outs, -1)


def _project(h, w, b):
    return jnp.einsum("sd,dhk->shk", h, _w(w)) + _w(b)


def _out(o, wp):
    wo = _w(wp["wo"])
    return o @ wo.reshape(-1, wo.shape[-1]) + _w(wp["bo"])


def seen(at, unseen, window=None, strict=False):
    """The mask [T, S] of the rows each token attends: the real tokens'
    positions ``at`` [S] (a padding row has its predecessor's), rows
    ``unseen`` attended by themselves alone; within ``window`` rows;
    ``strict``: without the token's own row (the first token: itself)."""
    rows = jnp.arange(at.shape[0])
    own = rows[:, None] == rows[None, :]
    mask = (rows[:, None] >= rows[None, :]) & (~unseen[None, :] | own)
    if window is not None:
        mask &= at[:, None] - at[None, :] < window
    if strict:
        mask = (mask & ~own) | (own & (rows == 0)[:, None])
    return mask


def attention(h, wp, hp, ablate, depth, at, unseen, window=None):
    """A "window" or "attention" layer's mixer over one sequence h [S,
    d]. Returns (out [S, d], its keys and values [S, Hkv, D] each)."""
    q = _project(h, wp["wq"], wp["bq"])
    k = _project(h, wp["wk"], wp["bk"])
    v = _project(h, wp["wv"], wp["bv"])
    o = differential(q, k, v, wp, hp, ablate, depth,
                     seen(at, unseen, window))
    return _out(o, wp), (k, v)


def cross(h, wp, hp, ablate, depth, at, unseen, kv):
    q = _project(h, wp["wq"], wp["bq"])
    o = differential(q, kv[0], kv[1], wp, hp, ablate, depth,
                     seen(at, unseen, strict=bool(ablate.get("cross_strict"))))
    return _out(o, wp)


def mamba(h, wp, hp, ablate):
    """The Mamba-1 mixer over one sequence h [S, d], token by token.
    Returns (out [S, d], the state after the last token [N, C], what the
    layer hands on [S, C])."""
    s = h.shape[0]
    n, r = hp["mamba_state"], hp["mamba_dt_rank"]
    x, z = h @ _w(wp["wx"]), h @ _w(wp["wz"])
    taps = wp["conv_w"].shape[0]
    conv = jnp.zeros_like(x) + _w(wp["conv_b"])
    rows = jnp.arange(s)
    for k in range(taps):
        back = taps - 1 - k  # tap k reads the input ``back`` tokens ago
        shifted = jnp.pad(x, ((back, 0), (0, 0)))[:s]
        if "drop_conv_tail" in ablate:
            cut = ablate["drop_conv_tail"]
            shifted = jnp.where(((rows >= cut) & (rows - back < cut))[:, None],
                                0.0, shifted)
        conv = conv + shifted * _w(wp["conv_w"])[k]
    x = jax.nn.silu(conv)
    low = x @ _w(wp["wxp"])
    dt = jax.nn.softplus(low[:, :r] @ _w(wp["wdt"]) + _w(wp["dt_bias"]))
    B, C = low[:, r:r + n], low[:, r + n:]
    A = -jnp.exp(_w(wp["a_log"]))  # [N, C]

    def token(state, at):
        x_t, b_t, c_t, dt_t = at
        state = (jnp.exp(dt_t[None, :] * A) * state
                 + (dt_t * x_t)[None, :] * b_t[:, None])
        if ablate.get("state_bf16"):  # a cast there and back may be elided
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, c_t @ state

    state, y = jax.lax.scan(token, jnp.zeros(A.shape, F32), (x, B, C, dt))
    y = y + x * _w(wp["d"])
    gated = y * jax.nn.silu(z)
    return (gated @ _w(wp["wo"]), state,
            gated if ablate.get("m_after_gate") else y)


def layer(x, lp, hp, ablate, depth, handed, real=None):
    """One layer of any kind (told by the key its mixer's weights sit
    under), the model's ``depth``-th; ``handed`` holds what the layers
    below handed on ("m", "kv"); ``real`` [S] bool (a numpy mask; None:
    all) the rows that are tokens. Returns (y [S, d], the layer's state
    after the last token or None, ``handed``)."""
    real = np.ones(x.shape[0], bool) if real is None else real
    at, unseen = jnp.asarray(np.cumsum(real) - 1), jnp.asarray(~real)
    h = layer_norm(x, lp["ln1"], hp, ablate)
    state = None
    if "mamba" in lp:
        a, state, m = mamba(h, lp["mamba"], hp, ablate)
        handed = {**handed, "m": m}
    elif "swa" in lp:
        a, _kv = attention(h, lp["swa"], hp, ablate, depth, at, unseen,
                           ablate.get("window", hp["window"]))
    elif "attn" in lp:
        a, kv = attention(h, lp["attn"], hp, ablate, depth, at, unseen)
        handed = {**handed, "kv": kv}
    elif "gmu" in lp:
        g = lp["gmu"]
        a = (handed["m"] * jax.nn.silu(h @ _w(g["wi"]))) @ _w(g["wo"])
    else:
        a = cross(h, lp["cross"], hp, ablate, depth, at, unseen,
                  handed["kv"])
    x = x + a
    h = layer_norm(x, lp["ln2"], hp, ablate)
    m = lp["mlp"]
    return x + gated_ffn(h, _w(m["wg"]), _w(m["wi"]), _w(m["wo"])), \
        state, handed


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


def head(params, x, hp, ablate=None):
    x = layer_norm(x, params["final_ln"], hp, ablate or {})
    return x @ _w(params["embed"]).T


def layers_in_order(params, hp):
    """(stack name, index in that stack) of every layer, in running
    order."""
    seen_so_far = dict.fromkeys(STACKS, 0)
    out = []
    for kind in hp["layer_types"]:
        out.append((STACKS[kind], seen_so_far[kind]))
        seen_so_far[kind] += 1
    return out


def forward_logits(params, tokens, hp, ablate=None):
    """tokens [S] -> (logits [S, V] in float32, the state of every
    "mamba" layer after the last token, in running order)."""
    ablate = ablate or {}
    seq, real = with_padding(tokens, ablate)
    x = embed(params, seq, hp)
    states, handed = [], {}
    for depth, (name, i) in enumerate(layers_in_order(params, hp)):
        lp = jax.tree.map(lambda a: a[i], params[name])
        x, state, handed = layer(x, lp, hp, ablate, depth, handed, real)
        if state is not None:
            states.append(state)
    return head(params, x[np.flatnonzero(real)], hp, ablate), states


def state_distance(got, want):
    """A state against the reference's: the root-mean-square of the
    difference over the root-mean-square of the reference's, float32."""
    diff = got.astype(F32) - want.astype(F32)
    return jnp.sqrt(jnp.mean(diff * diff) / jnp.mean(want.astype(F32) ** 2))
