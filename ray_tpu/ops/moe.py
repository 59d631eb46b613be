"""Mixture-of-experts FFN with GSPMD expert parallelism.

GShard/Switch-style top-k routing with per-expert capacity: tokens are
dispatched to [E, G, C, D] expert buffers via one-hot dispatch/combine
tensors, the expert FFN runs with the E axis sharded over the ``ep`` mesh
axis, and ``with_sharding_constraint`` re-layouts make XLA insert the
dispatch/return all-to-alls over ICI. No hand-written collectives — the
partitioner derives them, which is the TPU-native shape of expert
parallelism (the reference ships NO EP/MoE at all — SURVEY.md §2.5).

Refs: GShard (Lepikhin et al.), Switch Transformers (Fedus et al.) — see
PAPERS.md.

``routed_ffn`` below is the other kind of routed layer: dropless (no
capacity, no dispatch tensor), for models whose mathematics has no dropped
token. Its experts run as two calls of the Pallas grouped product in
``ops/grouped_matmul.py`` (gate and up in one pass, then down), which
streams the touched experts' weights out of the stacked array in pieces
of megabytes. It may hold a chip's share of the layer's experts (the
router stays whole, what an absent expert would have added is left out);
it has no exchange between chips and no backward pass: those are open
(ROADMAP R2).
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops.grouped_matmul import group_schedule, grouped_matmul


def _top_k_mask(probs: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """probs [G,N,E] -> (gates [G,N,E] zeroed outside top-k, masks [k,G,N,E]
    one-hot per choice slot)."""
    masks = []
    remaining = probs
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, probs.shape[-1], dtype=probs.dtype)
        masks.append(m)
        remaining = remaining * (1.0 - m)
    mask = jnp.stack(masks)  # [k, G, N, E]
    gates = probs * mask.sum(0)
    # renormalize the kept gates so they sum to 1 per token
    denom = jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates / denom, mask


def moe_ffn(
    x: jax.Array,  # [G, N, D] tokens (G = batch rows, sharded dp/ep)
    router_w: jax.Array,  # [D, E]
    wi: jax.Array,  # [E, D, F]
    wo: jax.Array,  # [E, F, D]
    *,
    top_k: int = 2,
    capacity_factor: float = 2.0,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (out [G, N, D], aux_loss scalar).

    Capacity C = ceil(top_k * N / E * capacity_factor); tokens routed beyond
    an expert's capacity are dropped (their combine weight is zero) — the
    standard GShard contract. aux_loss is the Switch load-balancing term.
    """
    import math

    g, n, d = x.shape
    e = router_w.shape[-1]
    capacity = max(1, math.ceil(top_k * n * capacity_factor / e))

    x32 = x.astype(jnp.float32)
    logits = jnp.einsum("gnd,de->gne", x32, router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, masks = _top_k_mask(probs, top_k)  # [G,N,E], [k,G,N,E]

    # Position of each token within its chosen expert's buffer, per slot.
    # Slot order: all slot-0 picks first, then slot-1 (GShard convention).
    dispatch = jnp.zeros((g, n, e, capacity), jnp.float32)
    combine = jnp.zeros((g, n, e, capacity), jnp.float32)
    prev_count = jnp.zeros((g, 1, e), jnp.float32)
    for s in range(masks.shape[0]):
        m = masks[s]  # [G,N,E] one-hot
        pos = jnp.cumsum(m, axis=1) - m + prev_count  # [G,N,E]
        keep = m * (pos < capacity)
        # position of each token within its chosen expert's buffer; value is
        # only meaningful where keep=1 (dropped tokens are masked out below)
        pos_idx = (pos * m).sum(-1).astype(jnp.int32)  # [G,N]
        pos_oh = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32)
        disp_s = keep[..., None] * pos_oh[:, :, None, :]  # [G,N,E,C]
        dispatch = dispatch + disp_s
        combine = combine + disp_s * (gates * m).sum(-1)[..., None, None]
        prev_count = prev_count + m.sum(1, keepdims=True)

    # Dispatch: [G,N,E,C] x [G,N,D] -> [E,G,C,D]; re-layout E onto `ep`
    # (XLA inserts the all-to-all between the dp/ep token sharding and the
    # ep expert sharding).
    def constrain(arr, spec):
        if mesh is None:
            return arr
        return jax.lax.with_sharding_constraint(
            arr, jax.sharding.NamedSharding(mesh, spec)
        )

    expert_in = jnp.einsum("gnec,gnd->egcd", dispatch.astype(x.dtype), x)
    expert_in = constrain(expert_in, P("ep", ("dp",), None, None))
    h = jnp.einsum("egcd,edf->egcf", expert_in, wi.astype(x.dtype))
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("egcf,efd->egcd", h, wo.astype(x.dtype))
    expert_out = constrain(expert_out, P("ep", ("dp",), None, None))
    out = jnp.einsum("gnec,egcd->gnd", combine.astype(x.dtype), expert_out)
    out = constrain(out, P(("dp", "ep"), None, None))

    # Switch load-balancing aux: E * sum_e mean_tokens_frac_e * mean_prob_e
    frac = masks[0].mean(axis=(0, 1))  # fraction routed (slot 0) per expert
    mean_prob = probs.mean(axis=(0, 1))
    aux = (frac * mean_prob).sum() * e
    return out, aux


# Tokens one pass of ``routed_ffn`` sorts and gathers: a longer prompt goes
# through in pieces of this many, so that the [tokens x top_k, D] rows of
# the sorted pairs stay a few hundred megabytes (a 24,576-token prompt at
# 8 experts a token and 6,144 wide would be 2.4 GB, four times over).
ROUTED_TOKENS_A_PASS = 4096

# Sorted-pair rows one turn of a share's loop gathers, computes and adds
# back (``routed_ffn``): four of the kernel's 128-row tiles. From a
# micro-run of one layer at the served widths on a v5e (PERF.md section 6,
# PR 43): 384-512 rows read fastest at every prompt length (XLA's
# scatter-add costs more a row above 512), and half a tile of dead rows a
# pass is what the rounding leaves.
ROUTED_ROWS_A_TILE = 512

# A share of at least one expert in this many brings a turn's rows back to
# their tokens as a product with the turn's 0/1 matrix of (token, row); a
# smaller share by XLA's scatter-add. Two ways, because neither serves
# both (my chip runs, PR 44, PERF.md section 6): at a quarter share (64 of
# 256, 8 a token, rows 2,304 wide) the scatter-add did not return on a v5e
# for some prompts, whichever turn it was given alone, where the loop
# without it and the product do; at a sixteenth (rows 4,096 and 6,144
# wide), where the scatter-add has run every prefill since PR 43, the
# product reads and writes all of the [tokens, D] output a turn and costs a
# layer -7 % at 1,024 tokens, nothing at 2,048, +46 % and +13 % at 4,096.
# Why the scatter-add hangs is open, so the share is a stand-in for the
# cause; one way for both needs a kernel that adds rows in place.
ROUTED_SHARE_BY_PRODUCT = 8


def _rows_to_tokens(token: jax.Array, rows: jax.Array, n: int,
                    exact: bool) -> jax.Array:
    """[n, d] float32 with row r of ``rows`` [R, d] float32 added into row
    ``token[r]``, as one product with the 0/1 matrix [n, R]: the float32
    rows split into two bf16 halves, one MXU pass each with float32
    accumulation (what is lost is under 2^-16 of a row), or, ``exact``,
    a true float32 product (a model that computes in float32). It costs
    n x R x d twice and a pass over all n rows of the output, whatever
    the rows (``ROUTED_SHARE_BY_PRODUCT``)."""
    f32 = jnp.float32
    hit = jnp.arange(n)[:, None] == token[None, :]
    if exact:
        return jnp.dot(hit.astype(f32), rows,
                       precision=jax.lax.Precision.HIGHEST)
    hi = rows.astype(jnp.bfloat16)
    lo = (rows - hi.astype(f32)).astype(jnp.bfloat16)
    hit = hit.astype(jnp.bfloat16)
    return (jnp.dot(hit, hi, preferred_element_type=f32)
            + jnp.dot(hit, lo, preferred_element_type=f32))


def routed_ffn(
    x: jax.Array,  # [..., D]
    wp,  # router [D,E], bias [E], wi/wo (+ wg) [H,...], optional "shared"
    *,
    top_k: int,
    route_scale: float = 1.0,
    act=jax.nn.silu,
    token_mask: Optional[jax.Array] = None,  # x.shape[:-1], bool
    first_expert: int = 0,
):
    """Dropless routed experts with bias-corrected selection, one
    implementation for a prompt's S tokens and a decode step's B.

    Scores are ``s = sigmoid(x W_r)`` in float32 (a true float32 product:
    a near-tie between the k-th and the next expert is decided here). The
    ``top_k`` experts with the largest ``s + bias`` are chosen; a chosen
    expert's weight is its ``s`` over the chosen ones' sum, times
    ``route_scale``. Every chosen (token, expert) pair is computed: the
    pairs are sorted by expert and the experts' FFNs run as grouped
    products (``grouped_matmul``: bf16 operands, float32 accumulation;
    gate, up and the activation in one kernel, down in a second) over the
    sorted rows, so an expert that no token chose is not read, and there
    is no capacity and no [tokens, E, C] tensor. ``wp["shared"]`` is an
    FFN every token takes, gated where IT holds a ``wg``.

    Experts in a LATENT: where ``wp`` holds ``latent_in`` [D, l] and
    ``latent_out`` [l, D], the router still reads ``x`` but the experts'
    rows are ``x @ latent_in`` (their matrices are [l, f] and [f, l]) and
    the weighted sum over a token's choices goes through ``latent_out``
    (bf16 operands, float32 accumulation) before the shared expert's
    part, which works on the full width, is added. One projection each
    way whatever the experts chosen; a share's part stays a part, the
    projection out being linear.

    Tokens outside ``token_mask`` (a parked lane, a prompt's padding) are
    sent to no expert: their pairs sort behind the last group, and their
    routed output is zero.

    A SHARE of the experts: where the weights hold fewer experts than the
    router has outputs (``wi`` [H, ...], H < E), they are the experts
    ``first_expert .. first_expert + H`` of the layer, as one of the chips
    that divide it would hold them. The router keeps its E outputs and its
    ``top_k`` a token, and a chosen expert's weight is normalised over ALL
    the chosen, held or not; a pair that goes to an absent expert sorts
    behind the last group like a parked lane's, and what it would have
    added is left out: the result is this share's part of the layer's
    (the shares' parts, with the shared expert counted once, add up to
    the whole layer's). Nothing stands in for the other chips.

    With ``wp["layer"]`` (an index) the experts' weights are those of a
    whole stack of layers, [layers, E, ...]: the kernel's block index is
    (layer, expert, ...), so it reads this layer's touched experts where
    they lie and nothing slices (copies) a layer's experts out of the
    stack (``transformer.scan_stack``).

    Such a share moves only the pairs it computes. Of ``tokens x top_k``
    sorted pairs, the live ones (``held / E`` of them: 1 in 16 where a
    chip holds 16 of 256 experts) sort in front, and a loop walks their
    tiles of ``ROUTED_ROWS_A_TILE`` rows, ``ceil(live / tile)`` turns, a
    device value: a turn gathers its rows of ``x``, runs the two grouped
    products over them (the groups cut at the tile's edges) and adds
    ``weight x result`` to its tokens' rows of the float32 output
    (scatter-add, so a token's choices are summed in the experts' order:
    float32 rounding of at most ``top_k`` terms apart from the other
    form; for a share of an eighth of the layer or more a product with
    the turn's 0/1 matrix of (token, row) instead:
    ``ROUTED_SHARE_BY_PRODUCT``). No array of all the pairs is gathered,
    selected or brought back to token order, there is no cap and nothing
    is dropped: with
    every pick held the loop runs over all the pairs. The rule, from
    shapes alone: the loop is taken where the weights hold a share AND
    the pairs outnumber one tile; a whole layer (every pair is live) and
    a decode step's few hundred pairs (one tile) go as one fused pass,
    the programs they always were.

    More than ``ROUTED_TOKENS_A_PASS`` tokens (a long prompt) go through
    in passes of that many, each sorted and computed on its own.

    Returns (y like x, stats): int32 scalars ``moe_assignments`` (pairs
    computed), ``moe_experts_touched`` (experts with at least one),
    ``moe_experts_capacity`` (the experts a step could read: those HELD
    here, E for a whole layer), ``moe_max_load`` (the fullest expert's
    pairs), ``moe_weight_visits`` (the (expert, 128-row tile) pairs the
    kernel's schedule visits: ``moe_experts_touched`` where every
    expert's rows sit in one tile, as a decode step's 128 rows do; an
    expert cut by a turn's edge is visited, and read, in both turns),
    ``moe_pair_rows`` (the sorted-pair rows gathered, selected and brought
    back around the kernel: ``tokens x top_k`` in one pass, the live pairs
    rounded up to whole tiles in the loop)."""
    f32 = jnp.float32
    d, E = x.shape[-1], wp["wi"].shape[-3]  # E: the experts held here
    whole = E == wp["router"].shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    gated = "wg" in wp
    latent = "latent_in" in wp
    if n > ROUTED_TOKENS_A_PASS and n % ROUTED_TOKENS_A_PASS == 0:
        mask = (jnp.ones(n, bool) if token_mask is None
                else token_mask.reshape(-1))

        def one_pass(args):
            return routed_ffn(
                args[0], wp, top_k=top_k, route_scale=route_scale, act=act,
                token_mask=args[1], first_expert=first_expert)

        y, stats = jax.lax.map(one_pass, (
            x2.reshape(-1, ROUTED_TOKENS_A_PASS, d),
            mask.reshape(-1, ROUTED_TOKENS_A_PASS)))
        # per pass; an expert touched in two passes was read twice
        stats = {k: v.max() if k in ("moe_max_load", "moe_experts_capacity")
                 else v.sum() for k, v in stats.items()}
        return y.reshape(x.shape), stats
    with jax.named_scope("raytpu.moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x2.astype(f32), wp["router"].astype(f32),
            precision=jax.lax.Precision.HIGHEST))
        _, idx = jax.lax.top_k(s + wp["bias"].astype(f32), top_k)  # [n,k]
        w = jnp.take_along_axis(s, idx, axis=-1)
        w = w / w.sum(-1, keepdims=True) * route_scale
        expert = idx.reshape(-1)  # pair p = token p // k, choice p % k
        if not whole:  # a share: an absent expert's pair is left out
            expert = expert - first_expert
            expert = jnp.where((expert >= 0) & (expert < E), expert, E)
        if token_mask is not None:
            live = jnp.repeat(token_mask.reshape(-1), top_k)
            expert = jnp.where(live, expert, E)  # behind every group
            w = w * token_mask.reshape(-1, 1)
        order = jnp.argsort(expert, stable=True)
        edges = jnp.searchsorted(
            expert[order], jnp.arange(E + 1), side="left",
            method="compare_all")
        sizes = jnp.diff(edges).astype(jnp.int32)  # pairs per expert
    rows = n * top_k
    # every pair in one tile, or every pair live: one fused pass over them
    looped = not whole and rows > ROUTED_ROWS_A_TILE
    u = x2  # the experts' rows: the tokens', or their latents
    if latent:
        with jax.named_scope("raytpu.moe.latent"):
            u = x2 @ wp["latent_in"].astype(x.dtype)
            d = u.shape[-1]
    with jax.named_scope("raytpu.moe.experts"):
        def experts(rows, schedule, *names, act=None):
            stacks = [wp[k].astype(x.dtype).reshape((-1, E) + wp[k].shape[-2:])
                      for k in names]  # [E, k, n]: a stack of one layer
            return grouped_matmul(rows, stacks, schedule,
                                  layer=wp.get("layer", 0), act=act)

        gate_up = ("wg", "wi") if gated else ("wi",)
        if not looped:
            schedule = group_schedule(sizes, rows)
            xs = u[order // top_k]  # [n*k, D], sorted by expert
            ys = experts(experts(xs, schedule, *gate_up, act=act),
                         schedule, "wo")
            # rows behind the last group hold nothing defined: select, then
            # back to token order and the weighted sum over a token's choices
            ys = jnp.where((jnp.arange(rows) < edges[E])[:, None], ys, 0)
            ys = ys[jnp.argsort(order)].reshape(n, top_k, d)
            y = jnp.einsum("nkd,nk->nd", ys.astype(f32), w)
            visits, moved = schedule.visits, jnp.int32(rows)
        else:
            tile = ROUTED_ROWS_A_TILE
            by_product = (E * ROUTED_SHARE_BY_PRODUCT
                          >= wp["router"].shape[-1])
            turns = (edges[E] + tile - 1) // tile  # the live pairs' tiles
            pairs = jnp.pad(order, (0, -rows % tile))
            w_pair = w.reshape(-1)

            def turn(i, carry):
                y, visits = carry
                lo = i * tile
                pair = jax.lax.dynamic_slice(pairs, (lo,), (tile,))
                token = pair // top_k
                cut = jnp.clip(edges, lo, lo + tile)  # the groups, cut here
                schedule = group_schedule(
                    jnp.diff(cut).astype(jnp.int32), tile)
                ys = experts(experts(u[token], schedule, *gate_up, act=act),
                             schedule, "wo")
                # a row behind the last group holds nothing defined
                ys = jnp.where(
                    (lo + jnp.arange(tile) < edges[E])[:, None],
                    ys.astype(f32) * w_pair[pair][:, None], 0)
                if by_product:
                    y = y + _rows_to_tokens(token, ys, n, x.dtype == f32)
                else:
                    y = y.at[token].add(ys)
                return y, visits + schedule.visits

            y, visits = jax.lax.fori_loop(
                0, turns, turn, (jnp.zeros((n, d), f32), jnp.int32(0)))
            moved = (turns * tile).astype(jnp.int32)
    if latent:
        with jax.named_scope("raytpu.moe.latent"):
            y = jnp.dot(y.astype(x.dtype), wp["latent_out"].astype(x.dtype),
                        preferred_element_type=f32)
    if "shared" in wp:
        with jax.named_scope("raytpu.moe.shared"):
            sp = wp["shared"]
            m = x2 @ sp["wi"].astype(x.dtype)
            m = (act(x2 @ sp["wg"].astype(x.dtype)) * m if "wg" in sp
                 else act(m))
            y = y + (m @ sp["wo"].astype(x.dtype)).astype(f32)
    stats = {
        "moe_assignments": edges[E].astype(jnp.int32),
        "moe_experts_touched": (sizes > 0).sum().astype(jnp.int32),
        "moe_experts_capacity": jnp.int32(E),
        "moe_max_load": sizes.max(),
        "moe_weight_visits": visits,
        "moe_pair_rows": moved,
    }
    return y.astype(x.dtype).reshape(x.shape), stats
