"""Plain reference of a model that mixes two kinds of attention layer,
full layers that attend every row and window layers that attend their
last rows and a learned sink, under dropless routed experts of which a
chip holds a share (MiMo-V2-Flash, ``model_type`` mimo_v2_flash; the
equations are written down from the published ``config.json``, see ISSUE
39 and ``benchmarks/configs/mimo-v2-flash-l7-e16-bf16-serve.json``).

The repository's own copy, for the tier-1 tests (``tests/
test_swa_moe_model.py``). ``benchmarks/reference_swa_moe.py`` is the
benchmark's copy and decides a cell's ``correct``; below the marker line
the two files are identical, byte for byte, and a test holds them to it.
The small helpers (RMSNorm, rotary, the gated FFN, the router, the two
distances) are those of the latent / routed reference beside this file.
"""
from ray_tpu.models import reference as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no ring,
# no blocking over rows, no batching, and none of the program's code. One
# sequence; every score of a head materialised ([S, S], one head at a
# time); a window is a mask over them. Callers wrap calls in
# ``jax.default_matmul_precision("highest")``.
#
# The model, for token ids t [S] (E the embedding [V, d]):
#   x = E[t]
#   each layer, of kind F (full) or W (window):
#     h = norm1(x)                       (RMSNorm with a weight, eps)
#     q = h W_q [H heads of D]; k = h W_k [Hkv heads of D];
#     v = value_scale * (h W_v) [Hkv heads of Dv]; Hkv and the rotary
#     base theta are the kind's own
#     rotary on the first rotary_dim dims of q and k, pairs (i, i + R/2)
#     a[t,s] = q_t . k_s / sqrt(D); query head g reads KV head
#     g // (H / Hkv)
#     F: s <= t, p = softmax(a)
#     W: t - window < s <= t, p[t,s] = exp(a[t,s]) / (exp(b_g) +
#        sum_s' exp(a[t,s'])): the sink b_g a head takes mass, gives no
#        value
#     x = x + (sum_s p[t,s] v_s over heads, [H Dv]) W_o
#     h2 = norm2(x)
#     a dense layer: x = x + W_o(silu(h2 W_g) * (h2 W_i))
#     a routed layer: s = sigmoid(h2 W_r) over ALL E experts (float32),
#        the top_k largest of s + bias chosen, weight s_e / sum_chosen s
#        * route_scale; x = x + sum over the chosen experts THAT ARE HELD
#        (first_expert .. + held) of w_e FFN_e(h2); no shared expert
#   logits = norm(x) W_head
#
# Weights arrive as the program lays them out: the leading dense layers
# (kind F) stacked under "dense_layers", the other F layers under
# "layers" (mixer "attn"), the W layers under "window_layers" (mixer
# "swa", with "sink" [H]), run in the order ``hp["layer_types"]`` gives
# ("attention" = F, "window" = W).
#
# ``hp``: n_heads, kv_heads {kind: n}, theta {kind: base}, d_head,
# rotary_dim, window, value_scale, eps, top_k, route_scale, first_expert,
# layer_types, n_dense_layers. ``ablate`` computes the model WRONG in one
# way, for the comparisons that must fail:
#   window            n: the window layers attend n rows (127, 129);
#   no_sink           the window layers' softmax without its sink;
#   sink_on_full      the full layers take a sink too: the first window
#                     layer's logits + ln(S / window), about the weight of
#                     the rows a full layer's last query attends, as the
#                     window layers' are drawn about the weight of theirs;
#   no_value_scale    value_scale taken as 1;
#   swap_theta        the two kinds' rotary bases exchanged;
#   rotary_all        rotary over all D dims;
#   window_grouping   a window layer's query head g reads KV head
#                     g // (H / Hkv_F): the full layers' grouping;
#   window_attends_all  a window layer attends every row s <= t;
#   fp8_weights       every matrix rounded through float8_e4m3.

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_rms_norm = _base._rms_norm
_rotary = _base._rotary
_weights = _base._weights
gated_ffn = _base.gated_ffn
route = _base.route
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance

KINDS = {"attention": "F", "window": "W"}


def attention(h, wp, hp, ablate, kind, full_sink=None):
    """One layer's attention over its normed input h [S, d]; ``kind`` "F"
    or "W". ``full_sink`` [H]: what ``sink_on_full`` gives a full layer."""
    w = _weights(ablate)
    s = h.shape[0]
    n_heads, window = hp["n_heads"], hp["window"]
    other = {"F": "W", "W": "F"}[kind]
    theta = hp["theta"][other if ablate.get("swap_theta") else kind]
    rot = hp["d_head"] if ablate.get("rotary_all") else hp["rotary_dim"]
    q = jnp.einsum("sd,dhk->shk", h, w(wp["wq"]))
    k = jnp.einsum("sd,dhk->shk", h, w(wp["wk"]))
    v = jnp.einsum("sd,dhk->shk", h, w(wp["wv"]))
    if not ablate.get("no_value_scale"):
        v = hp["value_scale"] * v

    def rotated(x):
        return jnp.concatenate([_rotary(x[..., :rot], theta), x[..., rot:]],
                               -1)

    q, k = rotated(q), rotated(k)
    rep = n_heads // k.shape[1]
    if kind == "W" and ablate.get("window_grouping"):
        rep = n_heads // hp["kv_heads"]["F"]
    of = jnp.arange(n_heads) // rep  # the KV head a query head reads
    rows = jnp.arange(s)
    mask = rows[:, None] >= rows[None, :]
    sink = None
    if kind == "W":
        if not ablate.get("window_attends_all"):
            mask &= rows[None, :] > rows[:, None] - ablate.get(
                "window", window)
        if not ablate.get("no_sink"):
            sink = wp["sink"].astype(F32)
    elif ablate.get("sink_on_full"):
        sink = full_sink + math.log(s / window)

    def head(args):
        q_h, g, b = args  # [S, D], the KV head, the sink's logit
        a = (q_h @ k[:, g].T) * hp["d_head"] ** -0.5
        a = jnp.where(mask, a, -jnp.inf)
        m = jnp.maximum(a.max(-1, keepdims=True), b)
        e = jnp.exp(a - m)
        return (e / (e.sum(-1, keepdims=True) + jnp.exp(b - m))) @ v[:, g]

    b = jnp.full((n_heads,), -jnp.inf, F32) if sink is None else sink
    att = jax.lax.map(head, (q.transpose(1, 0, 2), of, b))  # [H, S, Dv]
    return jnp.einsum("hsk,hkd->sd", att, w(wp["wo"]))


def routed_experts(x, wp, hp, ablate):
    """The held experts in turn over every token; a token keeps an
    expert's output times its routing weight, which is 0 unless it chose
    it. ``wp`` holds the router over all E experts and the weights of the
    experts ``hp["first_expert"]`` .. + held alone."""
    w = _weights(ablate)
    chosen, weights = route(x, wp, hp, ablate)
    first, n_held = hp.get("first_expert", 0), wp["wi"].shape[0]

    def one(y, e):
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(-1)  # [S]
        out = gated_ffn(x, w(wp["wg"][e]), w(wp["wi"][e]), w(wp["wo"][e]))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    return y


def ffn(x, lp, hp, ablate):
    """The second half of a layer: x [S, d] after its attention."""
    w = _weights(ablate)
    h = _rms_norm(x, w(lp["ln2"]["scale"]), hp["eps"])
    if "moe" in lp:
        return x + routed_experts(h, lp["moe"], hp, ablate)
    m = lp["mlp"]
    return x + gated_ffn(h, w(m["wg"]), w(m["wi"]), w(m["wo"]))


def attend(x, lp, hp, ablate, full_sink=None):
    """The first half of a layer (a window layer's weights hold "swa")."""
    w = _weights(ablate)
    h = _rms_norm(x, w(lp["ln1"]["scale"]), hp["eps"])
    if "swa" in lp:
        return x + attention(h, lp["swa"], hp, ablate, "W")
    return x + attention(h, lp["attn"], hp, ablate, "F", full_sink)


def layer(x, lp, hp, ablate, full_sink=None):
    """One layer's weights ``lp`` (no leading axis) over x [S, d]."""
    return ffn(attend(x, lp, hp, ablate, full_sink), lp, hp, ablate)


def layers_in_order(params, hp):
    """(stack name, index in that stack) of every layer, in running
    order."""
    out, seen = [], {"dense_layers": 0, "layers": 0, "window_layers": 0}
    for i, kind in enumerate(hp["layer_types"]):
        name = ("window_layers" if kind == "window" else
                "dense_layers" if i < hp["n_dense_layers"] else "layers")
        out.append((name, seen[name]))
        seen[name] += 1
    return out


def first_window_sink(params):
    """What ``sink_on_full`` starts from: the first window layer's logits."""
    return params["window_layers"]["swa"]["sink"][0].astype(F32)


def embed(params, tokens):
    return params["embed"][tokens].astype(F32)


def head(params, x, hp, ablate=None):
    w = _weights(ablate or {})
    x = _rms_norm(x, w(params["final_ln"]["scale"]), hp["eps"])
    return x @ w(params["lm_head"])


def forward_logits(params, tokens, hp, last=None, ablate=None):
    """tokens [S] -> logits [S, V] in float32 (the last ``last`` positions
    only, where given)."""
    ablate = ablate or {}
    x = embed(params, tokens)
    full_sink = first_window_sink(params)
    for name, i in layers_in_order(params, hp):
        lp = jax.tree.map(lambda a: a[i], params[name])
        x = layer(x, lp, hp, ablate, full_sink)
    if last is not None:
        x = x[-last:]
    return head(params, x, hp, ablate)
