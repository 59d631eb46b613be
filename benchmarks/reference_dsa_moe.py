"""Plain reference of the latent-attention block with a learned selection
of cache rows and a chip's share of the routed experts (GLM-5.2,
``model_type`` glm_moe_dsa): the benchmark's copy, which decides
``correct`` of the cells of kind ``serve_dsa_moe``
(``benchmarks/runners/serve_dsa_moe.py``). Kept under ``benchmarks/`` so
that no later PR that claims a gain can change what "correct" means.

``ray_tpu/models/reference_dsa.py`` is the repository's copy, for the
tier-1 tests; below the marker line the two files are identical, byte for
byte, and a test holds them to it. The small helpers (RMSNorm, rotary, the
gated FFN, the router, the two distances) are those of the benchmark's
latent / routed reference beside this file.
"""
from benchmarks import reference_mla_moe as _base

# ---- below this line the two copies are identical ----

# Straightforward ``jax.numpy`` in float32: no kernel, no cache, no
# batching, no absorbed attention, no blocks, and none of the program's
# code. One sequence; every pair's index score (one index head at a time);
# an explicit top-k per query; a softmax over the chosen rows alone (one
# head at a time, so that a [S, S] map exists once and not per head); the
# held experts applied ONE AT A TIME to every token. Callers wrap calls in
# ``jax.default_matmul_precision("highest")``.
#
# The layer, for hidden x [S, d] (everything not said is the latent /
# routed reference's block, ``reference.py``, with this config's numbers):
#   h = x + MLA(norm1(x));  y = h + FFN(norm2(h))          (RMSNorm, eps)
#   MLA: c_q = norm(x W_dq); [q_nope | q_rope] = c_q W_uq  (per head)
#        [c_kv | k_r] = x W_dkv; c_kv = norm(c_kv)
#        k_nope = c_kv W_uk, v = c_kv W_uv; rotary on q_rope and k_r
#        scores = (q_nope . k_nope + q_rope . k_r) / sqrt(nope + rope)
#   The selection, on a layer whose kind is "full" (x its normed input):
#        qI[t, j] = rot(c_q[t] W_Iq)[j]            j < n_I, each d_I wide
#        kI[s]    = rot(LayerNorm(x[s] W_Ik))      ONE key for all heads
#        w[t, j]  = (x[t] W_Iw)[j] / sqrt(n_I d_I)
#        I[t, s]  = sum_j w[t, j] relu(qI[t, j] . kI[s])        s <= t
#        (rot: rotary on the first ``qk_rope`` of the d_I dims)
#        T(t) = the min(t + 1, index_topk) rows s <= t with the largest
#        I[t, s], ties to the lower s (``lax.top_k``'s rule);
#        out = concat_h(softmax over T(t) of scores . v) W_o
#   A layer whose kind is "shared" has no indexer and attends T(t) of the
#   nearest "full" layer below it.
#   FFN, dense layers: W_o(silu(x W_g) * x W_i)
#   FFN, expert layers: s = sigmoid(x W_r) in float32 over ALL E experts;
#        the top_k with the largest s + b; weights s_i / sum_chosen(s) *
#        route_scale, the sum over all the chosen; of the chosen, the
#        experts HELD here (``first_expert`` ..) add their gated FFNs, the
#        others' part is left out; plus the shared expert's.
#
# Departures from the published model, all shared with the program:
# - rotary pairs dim i with dim i + rope/2 (this repo's layout) on the
#   attention's and on the indexer's rope dims, not adjacent dims;
# - the indexer in the weights' own precision with float32 scores (the
#   published serving stack keeps 8-bit index keys behind a Hadamard
#   rotation, which is orthogonal and changes no score);
# - weights arrive as the program lays them out: stacked over layers, the
#   leading dense layers under "dense_layers", the rest under "layers",
#   each stack's indexers under attn["indexer"] with one entry for each
#   "full" layer of the stack;
# - the multi-token-prediction module is not part of the forward.
#
# ``hp`` (a plain dict): n_heads, qk_nope, qk_rope, kv_rank, top_k,
# route_scale, eps, theta, index_topk, indexer_types (one "full" |
# "shared" a layer, dense layers first), first_expert. ``ablate`` (a dict
# of switches, all off by default) computes a deliberately WRONG model, to
# show that a comparison refuses it: "no_selection" (every row s <= t is
# attended), "index_topk": int, "shared_chooses_afresh" (a shared layer
# chooses for itself with the indexer of the full layer below),
# "no_relu", "unrotated_index_k", "no_index_layernorm",
# "weights_over_held" (a chosen expert's weight normalised over the HELD
# chosen experts only), and "fp8_weights" (every matrix rounded through
# float8_e4m3: the nearest precision below the bf16 the weights are served
# in).

import jax
import jax.numpy as jnp

F32 = jnp.float32
_rms_norm, _rotary, _weights = _base._rms_norm, _base._rotary, _base._weights
gated_ffn, route = _base.gated_ffn, _base.route
served_token_margin = _base.served_token_margin
vector_distance = _base.vector_distance


def _layer_norm(x, scale, bias, eps=1e-6):
    x = x - x.mean(-1, keepdims=True)
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) \
        * scale + bias


def query_latent(x, wp, hp, ablate):
    """c_q [S, r_q] of the layer's normed input x [S, d]."""
    w = _weights(ablate)
    return _rms_norm(x @ w(wp["wdq"]), w(wp["q_norm"]), hp["eps"])


def index_scores(x, c_q, ip, hp, ablate, queries=None):
    """I[t, s] for the queries t (every position where None), [T, S] in
    float32, -inf where s > t. ``x`` is the layer's normed input, ``ip``
    one indexer's weights."""
    w = _weights(ablate)
    rope, theta = hp["qk_rope"], hp["theta"]
    s = x.shape[0]

    def rot(y):  # rotary on the first rope dims of [S, H, d_I]
        return jnp.concatenate(
            [_rotary(y[..., :rope], theta), y[..., rope:]], -1)

    q = rot(jnp.einsum("sr,rjk->sjk", c_q, w(ip["wq"])))
    k = x @ w(ip["wk"])
    if not ablate.get("no_index_layernorm"):
        k = _layer_norm(k, w(ip["k_norm"]["scale"]), w(ip["k_norm"]["bias"]))
    if not ablate.get("unrotated_index_k"):
        k = rot(k[:, None])[:, 0]
    n_i, d_i = q.shape[1:]
    weight = (x @ w(ip["ww"])) * (n_i * d_i) ** -0.5
    t = jnp.arange(s) if queries is None else jnp.asarray(queries)

    def head(scores, args):  # one index head at a time: a [T, S] map each
        q_j, w_j = args  # [T, d_I], [T]
        dots = q_j @ k.T
        if not ablate.get("no_relu"):
            dots = jax.nn.relu(dots)
        return scores + w_j[:, None] * dots, None

    scores, _ = jax.lax.scan(
        head, jnp.zeros((t.shape[0], s), F32),
        (q[t].transpose(1, 0, 2), weight[t].T))
    return jnp.where(jnp.arange(s)[None] <= t[:, None], scores, -jnp.inf)


def chosen_rows(scores, topk):
    """The mask [T, S] of T(t): an explicit top-k of each row of
    ``scores`` (-inf where a row may not be attended); every allowed row
    where there are at most ``topk``. The k-th value is ``lax.top_k``'s;
    rows that tie with it go in from the lower index, as ``lax.top_k``
    would take them."""
    k = min(topk, scores.shape[-1])
    kth = jax.lax.top_k(scores, k)[0][:, -1:]
    above = scores > kth
    level = (scores == kth) & (scores > -jnp.inf)
    room = k - above.sum(-1, keepdims=True)
    return above | (level & (jnp.cumsum(level, -1) <= room))


def mla(x, c_q, wp, hp, ablate, mask):
    """Latent attention in the plain form over one sequence, each query
    attending the rows of ``mask`` [S, S] alone; one head at a time."""
    w = _weights(ablate)
    r, nope = hp["kv_rank"], hp["qk_nope"]
    q = jnp.einsum("sr,rhk->shk", c_q, w(wp["wuq"]))
    kv = x @ w(wp["wdkv"])
    c_kv = _rms_norm(kv[:, :r], w(wp["kv_norm"]), hp["eps"])
    k_r = _rotary(kv[:, None, r:], hp["theta"])[:, 0]  # one for all heads
    q_rope = _rotary(q[..., nope:], hp["theta"])
    scale = (nope + hp["qk_rope"]) ** -0.5

    def head(args):
        q_n, q_r, wuk, wuv = args  # [S, nope], [S, rope], [r, nope], [r, v]
        scores = (q_n @ (c_kv @ wuk).T + q_r @ k_r.T) * scale
        scores = jnp.where(mask, scores, -jnp.inf)
        return jax.nn.softmax(scores, -1) @ (c_kv @ wuv)

    att = jax.lax.map(head, (
        q[..., :nope].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        w(wp["wuk"]).transpose(1, 0, 2), w(wp["wuv"]).transpose(1, 0, 2)))
    return jnp.einsum("hsk,hkd->sd", att, w(wp["wo"]))


def routed_experts(x, wp, hp, ablate):
    """The held experts in turn over every token; a token keeps an
    expert's output times its routing weight, which is 0 unless it chose
    it. ``wp`` holds the router over all E experts and the weights of the
    experts ``hp["first_expert"]`` .. + H alone."""
    w = _weights(ablate)
    chosen, weights = route(x, wp, hp, ablate)
    first, n_held = hp.get("first_expert", 0), wp["wi"].shape[0]
    if ablate.get("weights_over_held"):
        held = (chosen >= first) & (chosen < first + n_held)
        total = weights.sum(-1, keepdims=True)
        kept = jnp.where(held, weights, 0.0).sum(-1, keepdims=True)
        weights = weights * total / jnp.maximum(kept, 1e-30)

    def one(y, e):
        mine = jnp.where(chosen == first + e, weights, 0.0).sum(-1)  # [S]
        out = gated_ffn(x, w(wp["wg"][e]), w(wp["wi"][e]), w(wp["wo"][e]))
        return y + mine[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(n_held))
    if "shared" in wp and not ablate.get("no_shared"):
        sp = wp["shared"]
        y = y + gated_ffn(x, w(sp["wg"]), w(sp["wi"]), w(sp["wo"]))
    return y


def attention(x, lp, ip, hp, ablate, mask=None):
    """One layer's attention over its normed input x [S, d]: (output
    [S, d], the mask [S, S] it attended). ``ip`` is the indexer that
    chooses (None: ``mask`` is attended as given)."""
    c_q = query_latent(x, lp["attn"], hp, ablate)
    if ip is not None:
        mask = chosen_rows(
            index_scores(x, c_q, ip, hp, ablate),
            ablate.get("index_topk", hp["index_topk"]))
    if ablate.get("no_selection"):
        mask = jnp.tril(jnp.ones((x.shape[0],) * 2, bool))
    return mla(x, c_q, lp["attn"], hp, ablate, mask), mask


def block(x, lp, ip, hp, ablate, mask):
    """One layer's weights ``lp`` (no leading axis) over x [S, d];
    returns (y, the mask the layer attended)."""
    w = _weights(ablate)
    a, mask = attention(_rms_norm(x, w(lp["ln1"]["scale"]), hp["eps"]),
                        lp, ip, hp, ablate, mask)
    h = x + a
    n = _rms_norm(h, w(lp["ln2"]["scale"]), hp["eps"])
    if "moe" in lp:
        return h + routed_experts(n, lp["moe"], hp, ablate), mask
    m = lp["mlp"]
    return h + gated_ffn(n, w(m["wg"]), w(m["wi"]), w(m["wo"])), mask


def layers_of(params, hp):
    """(one layer's weights, the indexer that chooses for it or None, its
    kind) for every layer in the order they run."""
    out, kinds = [], list(hp["indexer_types"])
    for group in ("dense_layers", "layers"):
        if group not in params:
            continue
        stack = dict(params[group])
        attn = dict(stack["attn"])
        indexers, own = attn.pop("indexer", None), 0
        stack["attn"] = attn
        for i in range(stack["ln1"]["scale"].shape[0]):
            kind, ip = kinds[len(out)], None
            if kind == "full":
                ip = jax.tree.map(lambda a: a[own], indexers)
                own += 1
            out.append((jax.tree.map(lambda a: a[i], stack), ip, kind))
    return out


def forward_logits(params, tokens, hp, last=None, ablate=None):
    """tokens [S] -> logits [S, V] in float32 (the last ``last`` positions
    only, where given: the head over a whole long prompt is large)."""
    ablate = ablate or {}
    w = _weights(ablate)
    x = params["embed"][tokens].astype(F32)
    mask = below = None
    for lp, ip, kind in layers_of(params, hp):
        if kind == "full":
            below = ip
        elif ablate.get("shared_chooses_afresh"):
            ip = below
        x, mask = block(x, lp, ip, hp, ablate, mask)
    if last is not None:
        x = x[-last:]
    x = _rms_norm(x, w(params["final_ln"]["scale"]), hp["eps"])
    return x @ w(params["lm_head"])
