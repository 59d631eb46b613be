"""Autoregressive generation with a KV cache (prefill + decode).

The inference half of the flagship model. ONE cached forward, as two
compiled programs with static shapes and no per-token Python:
``prefill_into_slot`` runs one prompt through the stack and writes its
rows into one slot of a shared batch cache (and, handed the engine's
lanes, samples the first token and fills the slot's lane entries: one
program an admission), and ``decode_block`` runs
``steps`` decode iterations for every slot at per-slot positions with
on-device sampling. The serving engine (``serve/llm.py``) schedules
requests over them; ``generate`` is their straight-line use (row ``b`` in
slot ``b``, one block). ``decode_step_multi`` is the decode body
returning logits, for tests that compare with a reference.

TPU notes: cache layout [L, B, S_max, H_kv, D] keeps the per-layer slices
contiguous for the scanned stack; GQA caches only kv_heads; latent
attention caches one latent row a token a layer (``init_kv_cache``: the
cache is a pytree that the mixer defines, and the engine never looks
inside it), and a block with an indexer the index key of each layer that
owns one. A layer whose mixer is a state-space recurrence keeps no rows
at all: its slot holds a STATE of fixed size (the recurrent state and the
convolution's last inputs, the cache's ``"state"`` subtree), which
``prefill_into_slot`` hands over as it stands after the prompt's last
real token and ``decode_block`` updates in place once a token. A WINDOW
layer (``TransformerConfig.window``) attends its last ``window`` rows
alone, so those are all its slot keeps: a ring of ``window`` rows, a STATE
leaf too, row ``pos mod window`` overwritten once a token; a prefill hands
over the prompt's last ``window`` rows, attends block by block and never
touches the rows a window does not reach (``ops/attention.
window_attention``). A "kda" layer (a gated delta rule, ``ops/kda.py``)
keeps a matrix state a head and the tail of its convolution, STATE leaves
like the state-space layer's, beside whichever rows the model's attention
layers keep. Both programs run every block the config can describe.
Decode is bound by HBM reads, and a masked cache row is read like a live
one: the mask only discards what was already streamed. So the decode
attention of both dense caches (``_attend_prefix_plus_self``,
``_attend_latent_prefix_plus_self``) is one Pallas kernel
(``ops/decode_attention``) that reads each slot in row chunks up to the
slot's OWN length, and nothing of a parked slot. A block with an indexer
attends fewer rows still: it scores the prefix's index keys, picks
``index_topk`` rows a lane, and attends those alone (``_decode_choice``,
``_attend_latent_chosen``: a masked walk up to the longest lane, see
there); its prefill attends under the mask of each query's chosen rows
through the prefill kernel, a group of heads a call (``_prefill_choice``,
``ops/attention.blocked_causal_attention`` with a ``mask``). Every other
full layer's prefill attends the prompt alone and nothing else of the slot
(``ops/attention.prefill_attention``, which takes one product or that
kernel from the heads and the bucket).

A decoder-hybrid-decoder's upper layers keep NOTHING: a "cross" layer
attends the rows the one "attention" layer below keeps (the token's own
row, which that layer wrote earlier in the same step, is handed up in the
layer scan's carry, as the last "mamba" layer's output is for the "gmu"
layers), so a prefill runs them on the prompt's LAST REAL token alone.

What differs by the KIND of a layer is in one table, ``_KINDS``, a row a
kind (``transformer.layer_kind``: "attn", "ssm", "swa", "kda", "mamba",
"gmu", "cross", "eva", and "moe", the routed FFN of a block whose layers
are one branch each; a latent block with an indexer has its own "attn"
row, a model whose layers hand things on too): what a slot keeps for the
kind's layers (``init_kv_cache`` merges the rows), how a token decodes
through one and how a prompt fills it (``_decode_forward_multi`` and
``prefill_into_slot`` look the layer's form up, once each), the kind's
counters (``block_stat_keys``) and the host's count of what its decode
attention reads (``attn_rows_read``). Nothing else here, and nothing in
the engine, asks what kind a layer is: a new kind is a row and the
functions it names.
"""

from __future__ import annotations

import math
from contextlib import nullcontext
from functools import lru_cache, partial
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.quant import QTensor
from ray_tpu.models.transformer import (
    TransformerConfig,
    _norm,
    apply_block,
    embed_tokens,
    kda_split,
    layer_groups,
    layer_kind,
    lm_logits,
    mamba_inputs,
    mla_expand,
    pred_logits,
    recalling,
    scan_stack,
    ssm_split,
)
from ray_tpu.ops.attention import (
    NEG_INF,
    block_of,
    blocked_causal_attention,
    prefill_attention,
    prefill_block_pairs,
    repeat_kv,
    window_attention,
)
from ray_tpu.ops.decode_attention import (
    chunk_rows,
    decode_attention,
    slot_schedule,
)
from ray_tpu.ops.eva import eva_attention, eva_block_pairs, eva_pool
from ray_tpu.ops.kda import kda_chunked, kda_update
from ray_tpu.ops.mamba import mamba_scan, mamba_update
from ray_tpu.ops.ssm import causal_conv, ssm_chunked, ssm_update


def _is_qtensor(x) -> bool:
    """``is_leaf`` of a walk that takes an int8 weight as one node."""
    return isinstance(x, QTensor)


def prepare_for_inference(params, config: TransformerConfig):
    """Cast training params (fp32 master copy) to the compute dtype ONCE.
    Serving streams every weight per decode step — fp32 params double that
    HBM traffic just to be cast in-kernel. Int8-quantized weights
    (models/quant.py QTensor) pass through untouched: they dequantize
    inside the consuming matmul. Returns (params, config)."""
    import dataclasses

    cast = jax.tree.map(
        lambda x: x if isinstance(x, QTensor) else x.astype(config.dtype),
        params, is_leaf=_is_qtensor)
    return cast, dataclasses.replace(config, param_dtype=config.dtype)


def decode_weight_formats(params, config: TransformerConfig, slots: int,
                          max_len: int, steps: int):
    """The ``Format`` (physical layout + sharding) in which the compiled
    ``decode_block`` reads each leaf of ``params``, as a tree like
    ``params``: ``decode_block`` for ``slots`` lanes of ``max_len`` rows is
    compiled with every weight's layout left to the compiler
    (``Layout.AUTO``) and the program's parameter layouts are read back.
    A leaf the program never reads has layout ``None``. ``params`` may be
    arrays or ``ShapeDtypeStruct`` s with a sharding; nothing runs."""
    from jax.experimental.layout import Format, Layout

    auto = jax.tree.map(lambda x: Format(Layout.AUTO, x.sharding), params)
    # shapes alone: an array that carries a layout may not be asked AUTO
    params = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), params)
    cache = jax.eval_shape(lambda: init_kv_cache(config, slots, max_len))

    def lane(dtype):
        return jax.ShapeDtypeStruct((slots,), dtype)

    compiled = jax.jit(
        decode_block, static_argnames=("config", "steps"),
        donate_argnums=(1,), in_shardings=(auto,) + (None,) * 6,
    ).lower(
        params, cache, lane(jnp.int32), lane(jnp.int32), lane(jnp.float32),
        lane(jnp.int32), lane(jnp.int32), config, steps,
    ).compile()
    return compiled.input_formats[0][0]


def lay_out_for_decode(params, config: TransformerConfig, slots: int,
                       max_len: int, steps: int):
    """Place every serving weight ONCE where the compiled ``decode_block``
    reads it: committed to its device, in the physical layout the program
    asks for (``decode_weight_formats``). A jitted program compiles for
    the layouts its arguments carry, so a weight left in another layout
    is re-laid-out INSIDE the program, once a block, while requests wait:
    on a v5e the three stacked int8 q/k/v projections of a 6B model, 470
    MB each. The decode step decides (it runs once a token);
    ``prefill_into_slot`` compiles for what it is handed, and is told what
    that is: every int8 leaf that then lies otherwise than row by row
    carries its order (``told_where_they_lie``), and the admission holds
    each layer's slice to it (``_read_where_they_lie``). Left to itself the
    compiler answers as the decode step does, [L, h, d, k] for ``wq`` /
    ``wk`` / ``wv``, up to 128 rows; from 256 rows up it wants ``wq`` and
    ``wk`` as [L, h, k, d], the contracted axis minor (their products are
    written rows-minor for the rotary step; ``wv`` it takes as it lies),
    and got that by copying 2 x 16 MB a layer twice over, 3.9-4.2 ms of a
    GPT-J admission (ISSUE 62). One algorithm steered by the compiler's
    answer for the model and shapes in front of it: where the compiler
    asks for the layout a leaf already has (every leaf, on the CPU
    backend) no byte moves and no leaf is told anything.

    A leaf that moves is DONATED, one at a time: set-up's peak rises by
    one leaf, no weight exists twice afterwards, and the arrays of the
    tree passed in that were moved are deleted (the engine owns its
    weights). A leaf that stays is committed where it lies (no copy).
    Names, logical shapes, dtypes and values are unchanged, and so is the
    tree's structure but for the ``order`` of such a ``QTensor``. Returns
    (params, leaves moved, bytes moved)."""
    leaves, treedef = jax.tree.flatten(params)
    formats = decode_weight_formats(params, config, slots, max_len, steps)
    asked = jax.tree.leaves(formats)
    moves = [f.layout is not None and f.layout != x.format.layout
             for x, f in zip(leaves, asked)]
    nbytes = sum(x.nbytes for x, move in zip(leaves, moves) if move)
    # a move waits, so that the next leaf's copy is allocated after this
    # leaf's donated buffer is free again
    placed = [
        jax.block_until_ready(jax.device_put(x, f, donate=True))
        if move else jax.device_put(x, x.sharding)
        for x, f, move in zip(leaves, asked, moves)]
    return (told_where_they_lie(treedef.unflatten(placed), formats),
            sum(moves), nbytes)


def told_where_they_lie(params, formats):
    """``params`` (arrays or shapes) with every ``QTensor`` told the order
    its ``q`` lies in (``QTensor.order``) where ``formats``, a tree like
    ``params`` (``decode_weight_formats``), lays it otherwise than row by
    row; the other nodes, and every leaf, as they are."""
    def told(w, f):
        if not isinstance(w, QTensor):
            return w
        order = f.q.layout and tuple(f.q.layout.major_to_minor)
        return QTensor(w.q, w.s, None if order == tuple(
            range(w.q.ndim)) else order)

    return jax.tree.map(told, params, formats, is_leaf=_is_qtensor)


def _read_where_they_lie(lp):
    """One layer's weights with each int8 leaf that lies otherwise than row
    by row (``QTensor.order``) PINNED to that order for the program being
    traced. A product over many rows would have its weight contracted-axis
    minor, and the compiler gets it that way at any price: it slices the
    layer out of the stack and lays it out again, 4 passes over 16 MB a
    leaf a layer, before it dequantises (``lay_out_for_decode``). Held to
    the order the stack has, the slice is a view of the stack and the
    product reads it there, dequantised on the way, as the decode step's
    does. A tree without such a leaf comes back as it is (no operation)."""
    from jax.experimental.layout import Layout, with_layout_constraint

    def pinned(w):
        if not (isinstance(w, QTensor) and w.order):
            return w
        return QTensor(with_layout_constraint(w.q, Layout(w.lies())), w.s,
                       w.order)

    return jax.tree.map(pinned, lp, is_leaf=_is_qtensor)


def _ckr_width(c: TransformerConfig) -> int:
    return -(-(c.kv_lora_rank + c.qk_rope_dim) // 128) * 128


def _ckr_rows(c_kv, k_r, width: int):
    """[c_kv | rot(k_r) | zeros] along the last axis, ``width`` wide."""
    pad = width - c_kv.shape[-1] - k_r.shape[-1]
    return jnp.concatenate(
        [c_kv, k_r, jnp.zeros(c_kv.shape[:-1] + (pad,), c_kv.dtype)], -1)


def init_kv_cache(config: TransformerConfig, batch: int,
                  max_len: int) -> Dict[str, jax.Array]:
    """The cache is a pytree that the mixers define: the merge of what
    each kind of layer the model has keeps of a slot (``_KINDS``, a row a
    kind; each row's ``keeps`` says what and why). Its leaves are of two
    kinds, told apart by where they sit (``cache_rows``, ``cache_state``):
    ROW leaves, [layers that keep it, B, S_max, ...], one row a cached
    token, at the top level; and STATE leaves, [layers that keep it, B,
    ...] with no S_max axis, under ``"state"``: what a slot keeps whatever
    its length. A slot is index ``b`` of axis 1 of every leaf."""
    cache, state = {}, {}
    for _kind, row, n in _kinds_of(config):
        rows, kept = row.keeps(config, n, batch, max_len)
        cache.update(rows)
        state.update(kept)
    return {**cache, "state": state} if state else cache


def _attn_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """What the layers that attend every row keep: rows alone. MHA/GQA:
    ``k`` and ``v`` of [L, .., Hkv, D] (``v`` as wide as the values are).
    Heads narrower than a 128-lane (``_kv_rows``) lie flat in their row,
    ``k`` and ``v`` of [L, .., Hkv x D]: the chip lays [.., Hkv, 64] out
    rows-minor, and the decode attention's kernel, which takes its
    operands row-major, was handed two copies of the whole cache a block
    (compiled for a described v5e, PR 35). Latent attention: the row
    ``[c_kv | rot(k_r)]`` a token a layer, as ``ckv`` of [L, ..,
    kv_lora_rank] (key and value at once) and ``kr`` of [L, ..,
    qk_rope_dim] (two arrays: see _attend_latent_prefix_plus_self)."""
    rows = (n, batch, max_len)
    if c.mixer == "mla":
        return {"ckv": jnp.zeros(rows + (c.kv_lora_rank,), c.dtype),
                "kr": jnp.zeros(rows + (c.qk_rope_dim,), c.dtype)}, {}
    k_row, v_row = _kv_rows(c)
    return {"k": jnp.zeros(rows + k_row, c.dtype),
            "v": jnp.zeros(rows + v_row, c.dtype)}, {}


def _chosen_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A latent block with an indexer GATHERS its rows, and a gather wants
    rows that lie whole: its slot keeps the latent row as ONE array
    ``ckr`` of [L, .., kv_lora_rank + qk_rope_dim, padded with zeros to
    whole lanes of 128] (576 -> 640; the chip lays a 64- or 576-wide array
    out rows-minor, and gathering from that costs a copy of the cache a
    layer), and ``ik`` of [layers that own an indexer, .., index_head_dim]:
    the index key a token, the rows kept for CHOOSING what the layers
    above attend (the choice itself lives one step, in the layer scan's
    carry)."""
    rows = (batch, max_len)
    return {"ckr": jnp.zeros((n,) + rows + (_ckr_width(c),), c.dtype),
            "ik": jnp.zeros((c.n_index_layers,) + rows
                            + (c.index_head_dim,), c.dtype)}, {}


def _ssm_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A state-space layer keeps no rows: ``ssm`` of [those layers, B, H,
    P, N] in float32 (rounded to bf16 at every one of thousands of steps
    it would be another result) and ``conv``, the convolution's last
    ``ssm_conv - 1`` inputs, of [those layers, B, (ssm_conv - 1) x width]
    in the compute dtype: the taps side by side in ONE minor axis, a whole
    number of 128-lanes, because [.., 3, width] would be tiled with its 3
    rows padded to 16."""
    return {}, {
        "ssm": jnp.zeros((n, batch, c.ssm_heads, c.ssm_head_dim,
                          c.ssm_state), jnp.float32),
        "conv": jnp.zeros((n, batch, (c.ssm_conv - 1) * c.ssm_conv_width),
                          c.dtype)}


def _window_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A window layer keeps the last ``window`` rows: the ring ``wk`` of
    [those layers, B, window, Hkv_w x D] and ``wv`` of [.., Hkv_w x Dv],
    the KV heads flat in their row; the row of position p is ``p mod
    window``."""
    ring, h_kv = (n, batch, c.window), c.mha_kind(True)[0]
    return {}, {"wk": jnp.zeros(ring + (h_kv * c.d_head,), c.dtype),
                "wv": jnp.zeros(ring + (h_kv * c.v_dim,), c.dtype)}


def _kda_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A "kda" layer keeps ``kda`` of [those layers, B, H, D, D] in
    float32 (a head's keys x values) and ``conv``, the last ``kda_conv -
    1`` inputs of the convolution over the three streams, [those layers,
    B, (kda_conv - 1) x 3 x H x D], flat as a state-space layer's."""
    return {}, {
        "kda": jnp.zeros((n, batch, c.kda_heads, c.kda_head_dim,
                          c.kda_head_dim), jnp.float32),
        "conv": jnp.zeros((n, batch, (c.kda_conv - 1) * 3 * c.kda_inner),
                          c.dtype)}


def _mamba_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A "mamba" layer keeps ``mamba`` of [those layers, B, state,
    channels] in float32, the 16 state dims in the sublanes and the
    channels in the lanes (the chip lays a 16-minor array out badly), and
    ``conv``, the last ``mamba_conv - 1`` inputs of its convolution, flat
    as a state-space layer's."""
    return {}, {
        "mamba": jnp.zeros((n, batch, c.mamba_state, c.mamba_inner),
                           jnp.float32),
        "conv": jnp.zeros((n, batch, (c.mamba_conv - 1) * c.mamba_inner),
                          c.dtype)}


def eva_rows(c: TransformerConfig, length: int) -> int:
    """Rows an "eva" layer's slot needs for a sequence of ``length``
    tokens: ``eva_window / eva_chunk`` summaries for each window that can
    have closed before its last token, and the open window's rows."""
    return ((length - 1) // c.eva_window * (c.eva_window // c.eva_chunk)
            + min(c.eva_window, length))


def eva_read_len(c: TransformerConfig, pos):
    """Rows of an "eva" layer's slot that hold something when the lane's
    next token is at position ``pos`` (an integer or an array): the
    summaries of the windows closed so far, then the open window's
    tokens. What a decode step's attention reads, and the row the step's
    token is written to; 0 for a parked lane."""
    return (pos // c.eva_window * (c.eva_window // c.eva_chunk)
            + pos % c.eva_window)


def _eva_keeps(c: TransformerConfig, n: int, batch: int, max_len: int):
    """An "eva" layer keeps rows, but not one a token: ``ek`` and ``ev``
    of [those layers, B, eva_rows(max_len), H, D]. With n = eva_window /
    eva_chunk (EvaByte: 2,048 / 16 = 128), rows [0, n w) hold the
    summaries of the w windows closed so far and the rows from there on
    the open window's keys (rotated) and values, one a token: ONE
    contiguous prefix of ``eva_read_len(pos)`` rows, which the decode
    attention's kernel reads as it reads any cache. When a window closes
    its eva_window rows are pooled and written over their own first n
    (``_eva_close``), and the next token's row lands after them."""
    rows = (n, batch, eva_rows(c, max_len), c.n_heads, c.d_head)
    return {"ek": jnp.zeros(rows, c.dtype),
            "ev": jnp.zeros(rows, c.dtype)}, {}


def _eva_closing(c: TransformerConfig, pos):
    """The lanes [B] whose token at ``pos`` fills its window: it is the
    window's last, and the lane is not parked."""
    return (pos > 0) & (pos % c.eva_window == c.eva_window - 1)


def _nothing_kept(c: TransformerConfig, n: int, batch: int, max_len: int):
    """A "gmu" or a "cross" layer keeps nothing: it reads what a layer
    below keeps or hands on. Nor does a "single" block's routed FFN
    ("moe"): it has no mixer at all."""
    return {}, {}


def _put_layer(leaf, rows, li):
    """``leaf`` (one slot's, [layers, 1, S, ...]) with layer ``li``'s rows
    overwritten from their start by ``rows`` [1, 1, S', ...]."""
    return lax.dynamic_update_slice(
        leaf, rows.astype(leaf.dtype), (li,) + (0,) * (leaf.ndim - 1))


def _put_token(leaf, li, b_idx, at, x):
    """``leaf`` [layers, B, S, ...] with row ``at`` [B] of layer ``li``
    of every lane overwritten by the lanes' ``x`` [B, ...], shaped as the
    leaf keeps a row."""
    return leaf.at[li, b_idx, at].set(
        x.reshape((-1,) + leaf.shape[3:]).astype(leaf.dtype))


def _kv_rows(c: TransformerConfig) -> Tuple[Tuple[int, ...], ...]:
    """The shapes of one token's key and value in a layer of the MHA/GQA
    cache: (Hkv, D) and (Hkv, Dv), or both flat, (Hkv x D,) and
    (Hkv x Dv,), where D alone is no whole number of 128-lanes (or the
    heads pair: ``transformer._diff_pairs`` reads two as one, or they are
    fewer than the 8 sublanes a tile of [Hkv, D] has: two KV heads of 128
    would lie in tiles of 8 or 16, most of each padding) and the heads
    together are."""
    if (c.d_head % 128 or c.diff_attn or c.kv_heads < 8) and (
            c.kv_heads * c.d_head) % 128 == 0:
        return (c.kv_heads * c.d_head,), (c.kv_heads * c.v_dim,)
    return (c.kv_heads, c.d_head), (c.kv_heads, c.v_dim)


def cache_rows(cache: Dict) -> Dict:
    """The cache's row leaves ([layers, B, S_max, ...]: what grows with a
    sequence), as a tree."""
    return {k: v for k, v in cache.items() if k != "state"}


def cache_state(cache: Dict) -> Dict:
    """The cache's state leaves ([layers, B, ...]: what a slot keeps
    whatever its length), as a tree; empty for most models."""
    return cache.get("state", {})


def slot_footprint(cache: Dict) -> Dict[str, int]:
    """What one slot costs, from the cache's shapes alone: ``state_bytes``
    whatever its length, ``row_bytes`` a cached token (over all layers),
    and ``state_layers``, the layers that keep a state (a decode step
    reads and writes every slot's state once a state layer)."""
    state = jax.tree.leaves(cache_state(cache))
    return {
        "state_bytes": sum(a.dtype.itemsize * math.prod(a.shape[2:])
                           * a.shape[0] for a in state),
        "row_bytes": sum(a.dtype.itemsize * math.prod(a.shape[3:])
                         * a.shape[0]
                         for a in jax.tree.leaves(cache_rows(cache))),
        "state_layers": max((a.shape[0] for a in state), default=0),
    }


# ---------------- continuous-batching primitives ----------------
# (serve/llm.py's iteration-level scheduler: per-SLOT positions so one
# compiled decode step serves sequences admitted at different times —
# the TPU-shaped analog of vLLM's iteration-level batching.)


def dense_attn_chunk(arrays) -> int:
    """Rows of one slot that one visit of the decode attention's kernel
    reads of the cache ``arrays`` ([L,B,S_max,...] each): from what a row
    weighs in all of them together (``ops/decode_attention.chunk_rows``)."""
    return chunk_rows(
        sum(math.prod(a.shape[3:]) * a.dtype.itemsize for a in arrays),
        arrays[0].shape[2])


def _visits(pos, arrays):
    """One decode step's schedule for the kernel over the cache ``arrays``
    (``ops/decode_attention.slot_schedule``)."""
    s_max = arrays[0].shape[2]
    return slot_schedule(pos, s_max, min(dense_attn_chunk(arrays), s_max))


@lru_cache(maxsize=None)
def decode_attn_chunk(config: TransformerConfig, s_max: int) -> int:
    """Rows of cache one iteration of ``config``'s decode attention reads
    of a slot of ``s_max`` rows."""
    return _row(_length_kind(config), config).chunk(config, s_max)


def _dense_chunk(config: TransformerConfig, s_max: int) -> int:
    cache = jax.eval_shape(lambda: init_kv_cache(config, 1, s_max))
    return dense_attn_chunk(jax.tree.leaves(cache_rows(cache)))


def attn_rows_walked(rows: int, s_max: int, chunk: int) -> int:
    """Rows of ONE slot's cache that one decode step reads when the slot's
    ``pos`` is ``rows``: whole chunks of ``chunk`` (``decode_attn_chunk``
    of the model) up to it, at most ``s_max``; none for a parked slot.
    Plain integers: the host-side count of what the decode attention's
    kernel reads on the device, slot by slot."""
    return min(-(-min(rows, s_max) // chunk) * chunk, s_max)


def attn_rows_read(config: TransformerConfig, rows, steps: int,
                   s_max: int) -> int:
    """Rows of cache the decode attention of one ``decode_block`` of
    ``steps`` steps reads, the slots' ``pos`` being ``rows`` (plain
    integers, 0: parked) when it starts: the engine's count. Each live
    slot's chunks up to its own length, step by step; a block with an
    indexer walks EVERY slot up to the longest lane. The chunks are
    those of the rows a position leaves in a slot (``_Kind.read_len``)
    out of the rows the slot has (``_Kind.slot_rows``): the position and
    ``s_max`` themselves but for "eva" layers."""
    attn = _row(_length_kind(config), config)
    lanes = ([max(rows)] * len(rows) if attn.walks_longest
             else [r for r in rows if r])
    chunk = decode_attn_chunk(config, s_max)
    return sum(attn_rows_walked(attn.read_len(config, r + k),
                                attn.slot_rows(config, s_max), chunk)
               for r in lanes for k in range(steps))


def admission_rows(config: TransformerConfig, bucket: int,
                   s_max: int) -> Tuple[int, int]:
    """(rows of a slot an admission at ``bucket`` holds and writes, rows
    the slot has), of the leaves that give a slot its length: the engine's
    count, plain integers (``_admission_slot``: the bucket's rows where
    they lie one a token, else all the slot has)."""
    row = _row(_length_kind(config), config)
    slot = row.slot_rows(config, s_max)
    return (min(bucket, slot) if row.row_a_token else slot), slot


def _attend_prefix_plus_self(q, ck, cv, k_new, v_new, pos, *, layer,
                             schedule):
    """q [B,1,H,D] against the UNWRITTEN cache prefix (k_pos < pos,
    strict — the row at ``pos`` may hold stale garbage) plus the fresh
    (k_new, v_new) [B,1,Hkv,D] as one extra logical position. Exactly
    equivalent to writing the token's k/v at ``pos`` first and attending
    ``k_pos <= pos``, but the attention does not wait for the write: the
    row only feeds LATER steps (``_decode_attn``).

    A masked row is not free: it is an HBM read, and the read is all a
    decode step's attention costs. So each slot's rows are read in chunks
    of ``chunk`` up to the slot's OWN ``pos`` by one Pallas kernel
    (``ops/decode_attention``: its ``schedule`` of (slot, chunk) visits is
    made once a step and shared by the layers) with an online softmax in
    float32 (running max, sum and accumulator, seeded here by the self
    position). Within a chunk the strict mask stays, so this is the same
    attention over the same rows (bf16 operands, float32 scores and
    accumulation). A lane at ``pos`` 0 attends itself alone and reads
    nothing: that is where the engine parks its free slots.

    ck/cv are the whole [L,B,S_max,Hkv,D] cache and ``layer`` the one to
    read: the kernel reads a chunk where it lies in the big buffer (a
    layer sliced out of it would be copied whole)."""
    m, scale = _self_score(q, k_new)
    acc = repeat_kv(v_new, q.shape[2] // ck.shape[3])[:, 0].astype(
        jnp.float32)
    out = decode_attention(
        (q[:, 0],), (ck,), cv, m, acc, pos, schedule, layer=layer,
        scale=scale)
    return out[:, None]


def _self_score(q, k_new):
    """The self position's scores, q [B,1,H,D] . k_new [B,1,Hkv,D] as
    [B,H] in float32, and the scale they carry: what seeds the decode
    attention's running max."""
    scale = q.shape[-1] ** -0.5
    return jnp.einsum(
        "bqhd,bqhd->bh", q, repeat_kv(k_new, q.shape[2] // k_new.shape[2]),
        preferred_element_type=jnp.float32) * scale, scale


def _attend_flat_prefix_plus_self(q, ck, cv, k_new, v_new, pos, *, layer,
                                  schedule):
    """``_attend_prefix_plus_self`` over a cache whose rows hold their KV
    heads flat, ck / cv [L,B,S_max,Hkv x D] (``_kv_rows``). To the kernel
    this is one key Hkv x D wide that all heads share: a query head's own
    D numbers sit in its KV head's place and zeros elsewhere, so its score
    is its own head's; it gathers all Hkv heads' values and keeps its own.
    The kernel streams the same rows either way, and the rows are all a
    decode step's attention costs."""
    B, _, n_heads, _d = q.shape
    h_kv, d_v = k_new.shape[2], v_new.shape[-1]
    q_wide, own = _wide_queries(q, h_kv)
    m, scale = _self_score(q, k_new)
    acc = jnp.broadcast_to(
        v_new.reshape(B, 1, h_kv * d_v).astype(jnp.float32),
        (B, n_heads, h_kv * d_v))
    out = decode_attention(
        (q_wide,), (ck,), cv, m, acc, pos, schedule, layer=layer,
        scale=scale)
    return _own_values(out, own)


def _wide_queries(q, h_kv: int):
    """q [B,1,H,D] as [B,H,Hkv x D], each head's D numbers in its KV
    head's place and zeros elsewhere, and ``own`` [H,Hkv]: whose place
    that is."""
    B, _, n_heads, d = q.shape
    own = (jnp.arange(n_heads)[:, None] // (n_heads // h_kv)
           == jnp.arange(h_kv)[None, :])  # [H,Hkv]
    return (q[:, 0, :, None, :] * own[None, :, :, None].astype(q.dtype)
            ).reshape(B, n_heads, h_kv * d), own


def _own_values(out, own):
    """The other half of ``_wide_queries``: of the values of all Hkv heads
    that each query head gathered, out [B,H,Hkv x Dv], its own KV head's:
    [B,1,H,Dv]."""
    B, n_heads, wide = out.shape
    h_kv = own.shape[1]
    out = jnp.einsum("bhgd,hg->bhd",
                     out.reshape(B, n_heads, h_kv, wide // h_kv),
                     own.astype(out.dtype))
    return out[:, None]


def ring_rows(pos, window: int):
    """Rows of each lane's ring that hold a position its token attends
    ONCE THE TOKEN'S OWN ROW IS WRITTEN: min(pos + 1, window), none for a
    parked lane (``pos`` 0)."""
    return jnp.where(pos > 0, jnp.minimum(pos + 1, window), 0)


def _attend_ring(q, wk, wv, sink, rows, *, layer, schedule):
    """A window layer's decode attention: q [B,1,H,D] over the first
    ``rows`` [B] rows of each lane's ring, wk [Lw,B,W,Hkv x D] / wv
    [Lw,B,W,Hkv x Dv], which already holds the token's own row: a ring
    keeps the last W positions whatever their order (softmax does not
    ask; every key carries its rotary), and the row a token overwrites is
    the one that just left its window. The same kernel as the rows'
    (``ops/decode_attention``; to it a ring is a cache of W rows, read
    where it lies in the stacked leaf), its online softmax seeded not by
    the token's own position but by the SINK: a logit ``sink`` [H] of
    weight exp(sink) that carries no value (none: a seed of no weight).
    A parked lane reads nothing and returns zeros."""
    B, _, n_heads, d = q.shape
    h_kv = wk.shape[-1] // d
    d_v = wv.shape[-1] // h_kv
    f32 = jnp.float32
    q_wide, own = _wide_queries(q, h_kv)
    m = jnp.broadcast_to(
        jnp.full((n_heads,), NEG_INF, f32) if sink is None
        else sink.astype(f32), (B, n_heads))
    acc = jnp.zeros((B, n_heads, h_kv * d_v), f32)
    out = decode_attention((q_wide,), (wk,), wv, m, acc, rows, schedule,
                           layer=layer, scale=d ** -0.5)
    return _own_values(out, own)


def _attend_latent_prefix_plus_self(q_lat, q_rope, ckv, kr, c_new, r_new,
                                    pos, *, layer, scale: float, schedule):
    """``_attend_prefix_plus_self`` for a latent cache: ONE key that all
    heads share, in two parts, the latent ``ckv`` [L,B,S_max,R] (which is
    the value as well) and the rotary key ``kr`` [L,B,S_max,rope]. q_lat
    [B,H,R] (the query with W_uk absorbed) and q_rope [B,H,rope] against
    the unwritten prefix plus the token's own (c_new [B,R], r_new
    [B,rope]); scores (q_lat . c + q_rope . r) * scale in float32. The
    same kernel and the same online softmax: to it this is the
    grouped-query form with one KV head, a key in two parts and no value
    array; a chunk of latents is read once and serves as key and as
    value. Returns o_lat [B,H,R], float32 accumulation cast to q's type.

    The two parts are two arrays (PR 28, read from the chip's trace)
    because the chip's own layout for an array whose minor dim is 576 (no
    multiple of 128) is rows-minor, which cost two copies of the cache
    per block at the program's edges; 512 is a multiple, and the rotary
    part is a ninth of the bytes. The 64-wide rotary part lies rows-minor
    on the chip as well (compiled for a described v5e:
    ``bf16[8,32,4096,64]{2,3,1,0}``) and a kernel takes its operands
    row-major, so it is handed over with rows last: there the swap is a
    bitcast, where the array as it is was copied whole before every
    call."""
    f32 = jnp.float32
    m = (jnp.einsum("bhd,bd->bh", q_lat, c_new, preferred_element_type=f32)
         + jnp.einsum("bhd,bd->bh", q_rope, r_new,
                      preferred_element_type=f32)) * scale
    acc = jnp.broadcast_to(c_new.astype(f32)[:, None], q_lat.shape)
    return decode_attention(
        (q_lat, q_rope), (ckv, kr.swapaxes(2, 3)), None, m, acc, pos,
        schedule, layer=layer, scale=scale, rows_last=(False, True))


# ---------------- learned sparse attention over the latent cache ----------------
# (a block with an indexer, ``TransformerConfig.index_topk``: each query
# attends the ``index_topk`` cache rows its layer's indexer scores best;
# transformer.index_project makes the index queries, keys and weights.)

# Rows one iteration of the decode step reads, of index keys (scoring) and
# of latent rows (the attention: 12 lanes x 2,048 rows x 640 numbers are
# 31 MB; a 20,000-row lane is ten iterations), and the prompt's queries
# one iteration of the prefill choice scores against the whole prompt
# (128 queries x 32 heads x 24,576 rows of float32: 0.4 GB).
DSA_CHUNK = 2048
DSA_QUERY_BLOCK = 128
# The heads a prefill under a choice expands keys and values for at a time,
# one call of the attention's kernel a group (keys and values of 16 heads
# over 24,576 tokens are 0.2 GB each, where all 64 at once would not fit
# beside the weights).
PREFILL_HEAD_GROUP = 16


def _index_scores(q, w, k):
    """q [..., T, nI, dI] and w [..., T, nI] (float32) against the index
    keys k [..., S, dI]: ``sum_j w_j relu(q_j . k_s)``, [..., T, S] in
    float32 (operands in the compute dtype, float32 products)."""
    s = jnp.einsum("...tjd,...sd->...tjs", q, k,
                   preferred_element_type=jnp.float32)
    return (jax.nn.relu(s) * w[..., None]).sum(-2)


def select_rows(scores, valid, k: int):
    """The mask [..., S] of the ``k`` largest ``scores`` (float32) among
    the ``valid`` rows, ties to the lower row, every valid row where there
    are at most ``k``: exactly ``lax.top_k``'s set, without its sort, and
    a mask is what both attentions want (timed alone on a v5e, 12 lanes x
    25,600 scores: 0.67 ms against ``lax.top_k``'s 0.95, both with ~0.6 ms
    of dispatch in them; in the traced cell the choice is 0.5 % of a
    decode step; my chip runs, PR 32). The k-th largest score is found
    bit by bit (32
    counts over the row: the largest threshold that at least ``k`` scores
    reach), then the rows above it are taken and, of the rows that equal
    it, the first few."""
    u = lax.bitcast_convert_type(scores, jnp.uint32)
    # order-preserving: a float's bits, sign flipped (negatives: all bits)
    key = jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))
    key = jnp.where(valid, key, jnp.uint32(0))  # under every score

    def bit(i, t):
        cand = t | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        enough = (key >= cand[..., None]).sum(-1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, t)

    t = lax.fori_loop(0, 32, bit, jnp.zeros(scores.shape[:-1], jnp.uint32))
    above = key > t[..., None]
    level = (key == t[..., None]) & valid
    room = k - above.sum(-1, dtype=jnp.int32)
    return above | (level & (jnp.cumsum(level, -1, dtype=jnp.int32)
                             <= room[..., None]))


def _prefill_choice(q, k, w, topk: int, dtype=bool):
    """A prompt's choice: index queries q [S, nI, dI], keys k [S, dI],
    weights w [S, nI] -> the mask [S, S] (of ``dtype``) whose row t marks
    the min(t + 1, topk) rows s <= t that query t attends. Scored and
    chosen ``DSA_QUERY_BLOCK`` queries at a time against every row."""
    S = q.shape[0]
    blk = block_of(S, DSA_QUERY_BLOCK)
    rows = jnp.arange(S)

    def one(block):
        qb, wb, tb = block
        with jax.named_scope("raytpu.dsa.index"):
            scores = _index_scores(qb, wb, k)  # [blk, S]
        with jax.named_scope("raytpu.dsa.select"):
            return select_rows(scores, rows[None, :] <= tb[:, None],
                               topk).astype(dtype)

    masks = lax.map(one, (q.reshape((-1, blk) + q.shape[1:]),
                          w.reshape(-1, blk, w.shape[-1]),
                          rows.reshape(-1, blk)))
    return masks.reshape(S, S)


def _decode_choice(q, w, ik, slot, k_new, pos, topk: int):
    """A decode step's choice: q [B, nI, dI] and w [B, nI] of each lane's
    token against the lane's index keys ``ik[slot]`` [B, S_max, dI] below
    ``pos`` plus the token's own ``k_new`` [B, dI] as the row at ``pos``
    (a candidate like any other; its cache row is not written yet). The
    keys are scored ``DSA_CHUNK`` rows at a time up to the longest lane,
    like ``_attend_latent_chosen``'s walk. Returns the mask [B, S_max] of the
    min(pos + 1, topk) best rows of each lane (``select_rows``: exactly
    ``lax.top_k``'s set)."""
    B, s_max, d_i = ik.shape[1:]
    chunk = min(DSA_CHUNK, s_max)
    with jax.named_scope("raytpu.dsa.index"):
        def walk(c, scores):
            start = jnp.minimum(c * chunk, s_max - chunk)
            kc = lax.dynamic_slice(ik, (slot, 0, start, 0),
                                   (1, B, chunk, d_i))[0]
            sc = _index_scores(q[:, None], w[:, None], kc)[:, 0]
            return lax.dynamic_update_slice(scores, sc, (0, start))

        bound = jnp.minimum(jnp.max(pos), s_max)
        scores = lax.fori_loop(
            0, (bound + chunk - 1) // chunk, walk,
            jnp.zeros((B, s_max), jnp.float32))
        own = _index_scores(q[:, None], w[:, None], k_new[:, None])[:, 0]
        rows = jnp.arange(s_max)[None, :]
        scores = jnp.where(rows == pos[:, None], own, scores)
    with jax.named_scope("raytpu.dsa.select"):
        return select_rows(scores, rows <= pos[:, None], topk)


def _attend_latent_chosen(q_lat, q_rope, ckr, row_new, pos, chosen, *,
                          layer, scale: float, chunk: int = DSA_CHUNK):
    """``_attend_latent_prefix_plus_self`` for a block that chooses: q_lat
    [B,H,R] and q_rope [B,H,rope] attend the rows of ``chosen`` [B,S_max]
    alone. The cache is ``ckr`` [L,B,S_max,W], rows [c | rot(k_r) | zeros];
    the token's own ``row_new`` [B,W] stands for the row at ``pos`` (not
    written yet) and counts only where ``chosen`` has it. An online
    softmax in float32 (running max ``m``, sum ``l``, accumulator ``acc``)
    over the chosen rows, carried through whole chunks of ``chunk`` cache
    rows up to the longest live lane, ceil(max(pos) / chunk) iterations:
    the trip count is data, so one compiled program serves every length.
    A lane attends a chunk's rows below its ``pos`` (strict); the last
    chunk of an S_max that chunk does not divide starts early (a slice
    must stay in bounds) and masks what it re-reads. One product gives a
    chunk's scores (the query laid out like a row). Returns o_lat [B,H,R].

    Why a masked walk and not a gather of the chosen rows: timed alone on
    a v5e, XLA's gather of 2,048 rows x 640 numbers for 12 lanes read 1.02
    ms a layer, and the walk was built on that; the reading held ~0.6 ms
    of dispatch, so the gather's device time is not known (PERF.md,
    section 6, PR 32). The choice decides what is attended, not yet what
    is read: in the traced cell this walk is 38 % of a decode step."""
    B, s_max, width = ckr.shape[1:]
    chunk = min(chunk, s_max)
    f32 = jnp.float32
    r_lat = q_lat.shape[-1]
    q = _ckr_rows(q_lat, q_rope, width)  # [B,H,W]
    has_own = jnp.take_along_axis(chosen, pos[:, None], axis=1)  # [B,1]
    own = jnp.einsum("bhd,bd->bh", q, row_new,
                     preferred_element_type=f32)[:, None] * scale
    m = jnp.where(has_own[:, :, None], own, NEG_INF)  # [B,1,H]
    l = jnp.broadcast_to(has_own[:, :, None].astype(f32), m.shape)
    acc = has_own[:, :, None] * jnp.broadcast_to(
        row_new[:, None, :r_lat].astype(f32), q_lat.shape)

    def walk(i, state):
        m, l, acc = state  # [B,1,H], [B,1,H], [B,H,R]
        lo = i * chunk
        start = jnp.minimum(lo, s_max - chunk)
        rows = lax.optimization_barrier(lax.dynamic_slice(
            ckr, (layer, 0, start, 0), (1, B, chunk, width))[0])
        k_pos = start + jnp.arange(chunk)
        take = ((k_pos >= lo)[None, :] & (k_pos[None, :] < pos[:, None])
                ) & lax.dynamic_slice(chosen, (0, start), (B, chunk))
        s = jnp.einsum("bkd,bhd->bkh", rows, q,
                       preferred_element_type=f32) * scale
        s = jnp.where(take[:, :, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        p = jnp.where(take[:, :, None], jnp.exp(s - m_new), 0.0)
        shrink = jnp.exp(m - m_new)
        l = shrink * l + p.sum(axis=1, keepdims=True)
        acc = shrink[:, 0, :, None] * acc + jnp.einsum(
            "bkh,bkd->bhd", p.astype(q_lat.dtype), rows[..., :r_lat],
            preferred_element_type=f32)
        return m_new, l, acc

    bound = jnp.minimum(jnp.max(pos), s_max)
    _, l, acc = lax.fori_loop(
        0, (bound + chunk - 1) // chunk, walk, (m, l, acc))
    return (acc / l[:, 0, :, None]).astype(q_lat.dtype)


def _prefill_attn_chosen(single, li, wp, choice, c: TransformerConfig,
                         prompt_len=None):
    """One prefill layer's ``attn_fn`` for a latent block with an indexer
    (the counterpart of ``_decode_attn_chosen``). ``single`` is one slot's
    cache, ``wp`` the layer's attention weights with its kind
    (``scan_stack``), ``choice`` what the layer scan carries: the mask
    [S, S] of the nearest layer below that owns an indexer (bool, or int8
    as the attention's kernel reads it: a layer that chooses afresh keeps
    the type it was handed), and that layer's index keys ``k`` [S, dI];
    ``prompt_len`` the prompt's real tokens (None: all S): the queries
    past them are not attended and their output rows are zeros. Returns
    (output, (single with this layer's rows written, choice))."""
    S = choice["mask"].shape[0]

    def afresh(q, k, w):
        return {"mask": _prefill_choice(q[0], k[0], w[0], c.index_topk,
                                        choice["mask"].dtype),
                "k": k[0]}

    @_latent
    def cached_attn(q_nope, q_rope, c_kv, k_r, wp, chosen):
        new = {"ckr": _put_layer(single["ckr"], _ckr_rows(
                   c_kv, k_r[:, :, 0], single["ckr"].shape[-1])[None], li),
               "ik": _put_layer(single["ik"], chosen["k"][None][None],
                                wp["index_slot"])}
        q = jnp.concatenate([q_nope, q_rope], -1)[0]  # [S,H,D]
        n_h = q.shape[1]
        g = PREFILL_HEAD_GROUP if n_h % PREFILL_HEAD_GROUP == 0 else n_h

        def heads(i):  # keys and values of g heads at a time
            def of(x):
                return lax.dynamic_slice_in_dim(x, i * g, g, axis=1)

            k, v = mla_expand(c_kv, k_r, {"wuk": of(wp["wuk"]),
                                          "wuv": of(wp["wuv"])}, c)
            return blocked_causal_attention(
                of(q)[None], k, v, prompt_len, mask=chosen["mask"])[0]

        out = lax.map(heads, jnp.arange(n_h // g))  # [H/g,S,g,v]
        out = out.transpose(1, 0, 2, 3).reshape(S, n_h, -1)
        return out[None], (new, chosen)

    cached_attn.choose = _choose(wp, choice, afresh)
    return cached_attn


def _own_indexer(wp):
    """This layer's indexer out of its stack's (transformer.scan_stack)."""
    return jax.tree.map(lambda a: a[wp["index_local"]], wp["indexer"])


def _choose(wp, choice, afresh):
    """What a block with an indexer hands its mixer as ``attn_fn.choose``
    (``transformer._mla_mixer`` calls it with ``project``, which makes an
    indexer's queries, keys and weights): a layer that owns an indexer
    chooses ``afresh(q, k, w)``, the others attend the ``choice`` the
    layer scan carries and run nothing of it (a ``lax.cond``)."""
    def choose(project):
        return lax.cond(
            wp["index_own"],
            lambda _: afresh(*project(_own_indexer(wp))),
            lambda _: choice, None)

    return choose


def _mla_scale(c: TransformerConfig) -> float:
    return (c.qk_nope_dim + c.qk_rope_dim) ** -0.5


def _latent(fn):
    """Marks an ``attn_fn`` that takes latents (transformer._mla_mixer)."""
    fn.latent = True
    return fn


def _absorbed(q_nope, wp, c: TransformerConfig):
    """The absorbed form of a latent decode attention: W_uk goes into the
    query and W_uv onto the output, so the walk sees one key that all
    heads share, whose latent part is the value as well. q_nope
    [B,1,H,nope] -> (q_lat [B,1,H,R], ``onto_heads``: o_lat [B,H,R] ->
    the heads' outputs [B,1,H,v])."""
    def onto_heads(o_lat):
        return jnp.einsum("bshc,chk->bshk", o_lat[:, None],
                          wp["wuv"].astype(c.dtype))

    return jnp.einsum("bshk,chk->bshc", q_nope,
                      wp["wuk"].astype(c.dtype)), onto_heads


def _decode_attn_chosen(cache, li, pos, b_idx, c: TransformerConfig, wp,
                        choice):
    """``_decode_attn`` for a latent block with an indexer. ``wp`` is the
    layer's attention weights with its kind (``scan_stack``); ``choice``
    is what the layer scan carries: the rows chosen by the nearest layer
    below that owns an indexer, ``mask`` [B,S_max], and that layer's new
    index key ``k`` [B,dI]. A layer that owns an indexer chooses afresh
    (a ``lax.cond``: the others run nothing of it). Every layer attends
    its own latent rows at the chosen places alone; the token's own row,
    where chosen, is taken from the fresh latents (its cache row is
    written after). Returns (output, (cache, choice))."""
    ckr, ik = cache["ckr"], cache["ik"]

    def afresh(q, k, w):  # [B,1,nI,dI], [B,1,dI], [B,1,nI]
        return {"mask": _decode_choice(
            q[:, 0], w[:, 0], ik, wp["index_slot"], k[:, 0], pos,
            c.index_topk), "k": k[:, 0]}

    @_latent
    def cached_attn(q_nope, q_rope, c_kv, k_r, wp, chosen):
        q_lat, onto_heads = _absorbed(q_nope, wp, c)
        row = _ckr_rows(c_kv[:, 0], k_r[:, 0, 0], ckr.shape[-1]).astype(
            ckr.dtype)  # [B,W]: the token's own
        o_lat = _attend_latent_chosen(
            q_lat[:, 0], q_rope[:, 0], ckr, row, pos, chosen["mask"],
            layer=li, scale=_mla_scale(c))
        # the index key is written by every layer that attends its choice:
        # the same row, the same value
        return onto_heads(o_lat), ({
            "ckr": _put_token(ckr, li, b_idx, pos, row),
            "ik": _put_token(ik, wp["index_slot"], b_idx, pos, chosen["k"])
        }, chosen)

    cached_attn.choose = _choose(wp, choice, afresh)
    return cached_attn


def _decode_attn(cache, li, pos, b_idx, c: TransformerConfig, schedule):
    """One decode layer's ``attn_fn`` for ``c.mixer``: attends the
    cache's prefix plus the token itself WITHOUT a pre-write (see
    _attend_prefix_plus_self) and returns (output, the cache with the
    token's row written at ``pos``): the write only feeds LATER steps, so
    it stays off the attention's critical path. ``b_idx`` is
    arange(B); ``schedule`` the step's visits (``_visits``)."""
    if c.mixer == "mla":
        @_latent
        def cached_attn(q_nope, q_rope, c_kv, k_r, wp):
            ckv, kr = cache["ckv"], cache["kr"]
            q_lat, onto_heads = _absorbed(q_nope, wp, c)
            c_new, r_new = c_kv[:, 0], k_r[:, 0, 0]  # [B,R], [B,rope]
            o_lat = _attend_latent_prefix_plus_self(
                q_lat[:, 0], q_rope[:, 0], ckv, kr, c_new, r_new, pos,
                layer=li, scale=_mla_scale(c), schedule=schedule)
            out = onto_heads(o_lat)
            # a parked lane (pos 0) keeps its rows as they are: its write
            # goes past the last row and is dropped
            at = jnp.where(pos > 0, pos, ckv.shape[2])
            return out, {
                **cache,
                "ckv": ckv.at[li, b_idx, at].set(
                    c_new.astype(ckv.dtype), mode="drop"),
                "kr": kr.at[li, b_idx, at].set(
                    r_new.astype(kr.dtype), mode="drop")}

        return cached_attn

    def cached_attn(q, k, v):
        ck_all, cv_all = cache["k"], cache["v"]
        attend = (_attend_flat_prefix_plus_self if ck_all.ndim == 4
                  else _attend_prefix_plus_self)
        out = attend(
            q, ck_all, cv_all, k, v, pos, layer=li, schedule=schedule)
        return out, {**cache,
                     "k": _put_token(ck_all, li, b_idx, pos, k[:, 0]),
                     "v": _put_token(cv_all, li, b_idx, pos, v[:, 0])}

    return cached_attn


def _prefill_attn(single, li, c: TransformerConfig, prompt_len):
    """One prefill layer's ``attn_fn`` for ``c.mixer`` (the counterpart of
    ``_decode_attn``). ``single`` is one slot's rows as the admission
    holds them (the bucket's alone: ``prefill_into_slot``), ``prompt_len``
    the prompt's real tokens. Every form attends the prompt alone
    (``prefill_attention``): it starts at row 0 of the slot, so the slot's
    other rows do not count. Latent attention takes the plain form:
    per-head keys and values are expanded from the prompt's latents; what
    the slot keeps is the latent rows.
    Returns (output, single with this layer's rows written)."""
    if c.mixer == "mla":
        @_latent
        def cached_attn(q_nope, q_rope, c_kv, k_r, wp):
            new = {**single,
                   "ckv": _put_layer(single["ckv"], c_kv[None], li),
                   "kr": _put_layer(single["kr"], k_r[None, :, :, 0], li)}
            # q and k are both nope + rope wide: the scale is the
            # attention's own
            k, v = mla_expand(c_kv, k_r, wp, c)
            q = jnp.concatenate([q_nope, q_rope], -1)
            return prefill_attention(q, k, v, prompt_len), new

        return cached_attn

    def cached_attn(q, k, v):
        ck_all, cv_all = single["k"], single["v"]
        # a row as the cache keeps it: (Hkv, D), or the heads flat
        k_rows, v_rows = (x.reshape(x.shape[:2] + leaf.shape[3:])
                          for leaf, x in ((ck_all, k), (cv_all, v)))
        new = {**single, "k": _put_layer(ck_all, k_rows[None], li),
               "v": _put_layer(cv_all, v_rows[None], li)}
        return prefill_attention(q, k, v, prompt_len), new

    return cached_attn


def _decode_window_attn(cache, li, pos, b_idx, c: TransformerConfig,
                        schedule):
    """One decode layer's ``attn_fn`` for a window layer (the counterpart
    of ``_decode_attn``): the token's key and value go into row ``pos mod
    window`` of the slot's ring FIRST (the row they replace left the
    window with this token), then the ring is attended (``_attend_ring``;
    ``schedule``: the step's visits of the rings, one a lane). Returns
    (output, the cache)."""
    def cached_attn(q, k, v, sink):
        state = cache_state(cache)
        with jax.named_scope("raytpu.swa.ring"):
            at = pos % c.window
            wk = _put_token(state["wk"], li, b_idx, at, k[:, 0])
            wv = _put_token(state["wv"], li, b_idx, at, v[:, 0])
        with jax.named_scope("raytpu.swa.attend"):
            out = _attend_ring(q, wk, wv, sink, ring_rows(pos, c.window),
                               layer=li, schedule=schedule)
        return out, {**cache, "state": {**state, "wk": wk, "wv": wv}}

    return cached_attn


def _prefill_window_attn(single, li, prompt_len, c: TransformerConfig):
    """One prefill layer's ``attn_fn`` for a window layer: the prompt is
    attended block by block, each block against the rows its windows
    reach alone (``ops/attention.window_attention``), and the slot's ring
    is handed the prompt's last ``window`` rows, position p in row ``p
    mod window`` (rows no position fills yet hold whatever: nothing
    attends them before a token overwrites them). ``single`` is one
    slot's cache. Returns (output, single with this layer's ring)."""
    def cached_attn(q, k, v, sink):
        state = cache_state(single)
        with jax.named_scope("raytpu.swa.ring"):
            j = jnp.arange(c.window)
            last = prompt_len - 1
            src = jnp.clip(last - (last - j) % c.window, 0, k.shape[1] - 1)

            def put(name, rows):
                ring = jnp.take(rows[0], src, axis=0).reshape(c.window, -1)
                return lax.dynamic_update_slice(
                    state[name], ring[None, None].astype(state[name].dtype),
                    (li, 0, 0, 0))

            new = {**state, "wk": put("wk", k), "wv": put("wv", v)}
        with jax.named_scope("raytpu.swa.attend"):
            out = window_attention(q, k, v, sink, window=c.window)
        return out, {**single, "state": new}

    return cached_attn


def _ssm_scan_inputs(xbc, wp, c: TransformerConfig):
    """The convolved channels as the scan's x, B, C, and A."""
    x, B, C = ssm_split(jax.nn.silu(xbc), c)
    return x, B, C, -jnp.exp(wp["a_log"].astype(jnp.float32))


def _recurrence(recur):
    """What a state-space layer's body hands ``apply_block`` where an
    attention layer hands its ``attn_fn``: nothing to attend with, and
    the layer's recurrence as ``.recur`` (``transformer._ssm_mixer``)."""
    def no_attention(*_a):
        raise TypeError("a state-space layer has no attention")

    no_attention.recur = recur
    return no_attention


def _conv_step(conv, li, x, w, b, moves):
    """One token through a layer's causal convolution on decode: layer
    ``li``'s tail (the last inputs of every lane, flat in ``conv``
    [layers, B, (taps - 1) x width]) is taken out of its leaf, convolved
    with the token ``x`` [B,1,width] under ``w`` [taps, width] and bias
    ``b`` (or None), moved on one token for the lanes of ``moves`` [B]
    (None: all) and put back in place. Returns (the convolution's output
    [B,1,width], ``conv``)."""
    tail = lax.dynamic_index_in_dim(conv, li, 0, False)
    tail = tail.reshape(tail.shape[0], w.shape[0] - 1, -1)
    out = causal_conv(x, w, b, tail)
    moved = jnp.concatenate([tail[:, 1:], x.astype(tail.dtype)], 1)
    if moves is not None:
        moved = jnp.where(moves[:, None, None], moved, tail)
    return out, lax.dynamic_update_index_in_dim(
        conv, moved.reshape(moved.shape[0], -1), li, 0)


def _conv_prompt(conv, li, x, w, b, prompt_len):
    """A padded prompt ``x`` [1,S,width] through a layer's causal
    convolution from an empty tail; what layer ``li`` of ``conv`` is
    handed is the last ``taps - 1`` REAL inputs (zeros where the prompt
    is shorter). Returns (the output [1,S,width], ``conv``)."""
    k1, S = w.shape[0] - 1, x.shape[1]
    out = causal_conv(x, w, b)
    at = prompt_len - k1 + jnp.arange(k1)
    tail = jnp.where((at >= 0)[None, :, None], jnp.take(
        x, jnp.clip(at, 0, S - 1), axis=1), 0)
    return out, lax.dynamic_update_index_in_dim(
        conv, tail.reshape(1, -1).astype(conv.dtype), li, 0)


def _decode_recur(cache, li, pos, c: TransformerConfig):
    """One decode layer's ``attn_fn`` for a state-space layer (the
    counterpart of ``_decode_attn``; ``transformer._ssm_mixer`` calls its
    ``recur``): every lane's convolution window moves on one token (a
    parked lane's too: 26 KB a slot and layer, overwritten whole by the
    next prefill) and every LIVE lane's state one step; a PARKED lane's
    state (``pos`` 0) belongs to nobody and is neither read nor written.
    The states are stepped by ``ops/ssm.ssm_update``, a kernel that takes
    the WHOLE [layers, B, ...] leaf, aliased, and reads and writes the
    live lanes' tiles of layer ``li`` where they lie: a state moves once
    each way and nothing slices a layer out. The window's layer is taken
    out of its leaf and put back in place (``_conv_step``). Returns (y,
    the cache)."""
    def recur(xbc, dt, wp):
        state = cache_state(cache)
        with jax.named_scope("raytpu.ssm.conv"):
            out, conv = _conv_step(state["conv"], li, xbc, wp["conv_w"],
                                   wp["conv_b"], None)
        with jax.named_scope("raytpu.ssm.update"):
            x, B, C, A = _ssm_scan_inputs(out, wp, c)
            y, ssm = ssm_update(state["ssm"], li, x[:, 0], dt[:, 0], A,
                                B[:, 0], C[:, 0], wp["d"], pos > 0)
        return y[:, None], {**cache, "state": {"ssm": ssm, "conv": conv}}

    return _recurrence(recur)


def _prefill_recur(single, li, prompt_len, c: TransformerConfig):
    """One prefill layer's ``attn_fn`` for a state-space layer: the
    convolution and the chunked scan (``ops/ssm.ssm_chunked``) over the
    padded prompt from an empty state. What the slot is handed is the
    state AT ``prompt_len`` (the padding's ``dt`` is taken as 0: it
    neither decays nor adds) and the last ``ssm_conv - 1`` REAL inputs of
    the convolution (zeros where the prompt is shorter). ``single`` is
    one slot's cache. Returns (y, single with this layer's state)."""
    def recur(xbc, dt, wp):
        state = cache_state(single)
        with jax.named_scope("raytpu.ssm.conv"):
            out, conv = _conv_prompt(state["conv"], li, xbc, wp["conv_w"],
                                     wp["conv_b"], prompt_len)
        with jax.named_scope("raytpu.ssm.scan"):
            x, B, C, A = _ssm_scan_inputs(out, wp, c)
            y, end = ssm_chunked(
                x, dt, A, B, C, wp["d"], c.ssm_chunk,
                valid=(jnp.arange(xbc.shape[1]) < prompt_len)[None])
            ssm = lax.dynamic_update_index_in_dim(state["ssm"], end, li, 0)
        return y, {**single, "state": {"ssm": ssm, "conv": conv}}

    return _recurrence(recur)


def _decode_kda(cache, li, pos, c: TransformerConfig):
    """One decode layer's ``attn_fn`` for a "kda" layer (the counterpart
    of ``_decode_recur``; ``transformer._kda_mixer`` calls its ``recur``):
    every live lane's convolution window moves on one token and its state
    one step of the delta rule; a PARKED lane (``pos`` 0) keeps its state
    and its window as they were, and its state is neither read nor
    written. The states are stepped by ``ops/kda.kda_update`` on the WHOLE
    [layers, B, ...] leaf, aliased, at layer ``li``. Returns (o, the
    cache)."""
    def recur(qkv, g, beta, wp):
        state, live = cache_state(cache), pos > 0
        with jax.named_scope("raytpu.kda.conv"):
            out, conv = _conv_step(state["conv"], li, qkv, wp["conv_w"],
                                   None, live)
        with jax.named_scope("raytpu.kda.update"):
            q, k, v = kda_split(out[:, 0], c)
            o, kda = kda_update(state["kda"], li, q, k, v, g[:, 0],
                                beta[:, 0], live)
        return o[:, None], {**cache, "state": {"kda": kda, "conv": conv}}

    return _recurrence(recur)


def _prefill_kda(single, li, prompt_len, c: TransformerConfig):
    """One prefill layer's ``attn_fn`` for a "kda" layer: the convolution
    and the chunked delta rule (``ops/kda.kda_chunked``) over the padded
    prompt from an empty state. What the slot is handed is the state AT
    ``prompt_len`` (the padding neither decays nor writes) and the last
    ``kda_conv - 1`` REAL inputs of the convolution (zeros where the
    prompt is shorter). ``single`` is one slot's cache. Returns (o, single
    with this layer's state)."""
    def recur(qkv, g, beta, wp):
        state = cache_state(single)
        with jax.named_scope("raytpu.kda.conv"):
            out, conv = _conv_prompt(state["conv"], li, qkv, wp["conv_w"],
                                     None, prompt_len)
            q, k, v = kda_split(out, c)
        with jax.named_scope("raytpu.kda.chunk"):
            o, end = kda_chunked(
                q, k, v, g, beta, c.kda_chunk,
                valid=(jnp.arange(qkv.shape[1]) < prompt_len)[None])
            kda = lax.dynamic_update_index_in_dim(state["kda"], end, li, 0)
        return o, {**single, "state": {"kda": kda, "conv": conv}}

    return _recurrence(recur)


def _decode_mamba(cache, li, pos, c: TransformerConfig, handed):
    """One decode layer's ``attn_fn`` for a "mamba" layer (the counterpart
    of ``_decode_kda``; ``transformer._mamba_mixer`` calls its ``recur``):
    every live lane's convolution window moves on one token and its state
    one step (``ops/mamba.mamba_update`` on the stacked leaf at layer
    ``li``); a PARKED lane (``pos`` 0) keeps both as they were. The
    recurrence's output goes up in ``handed`` as ``m``. Returns (y, (the
    cache, handed))."""
    def recur(x, wp):
        state, live = cache_state(cache), pos > 0
        with jax.named_scope("raytpu.mamba1.conv"):
            out, conv = _conv_step(state["conv"], li, x, wp["conv_w"],
                                   wp["conv_b"], live)
            x = jax.nn.silu(out[:, 0])
        with jax.named_scope("raytpu.mamba1.update"):
            dt, B, C, A = mamba_inputs(x, wp, c)
            y, new = mamba_update(state["mamba"], li, x, dt, A, B, C,
                                  wp["d"], live)
        return y[:, None], (
            {**cache, "state": {**state, "mamba": new, "conv": conv}},
            {**handed, "m": y})

    return _recurrence(recur)


def _prefill_mamba(single, li, prompt_len, c: TransformerConfig, handed):
    """One prefill layer's ``attn_fn`` for a "mamba" layer: the
    convolution and the scan (``ops/mamba.mamba_scan``) over the padded
    prompt from an empty state. What the slot is handed is the state AT
    ``prompt_len`` (the padding neither decays nor adds) and the last
    ``mamba_conv - 1`` REAL inputs of the convolution. Returns (y, (single
    with this layer's state, handed with every token's ``m``))."""
    def recur(x, wp):
        state = cache_state(single)
        with jax.named_scope("raytpu.mamba1.conv"):
            out, conv = _conv_prompt(state["conv"], li, x, wp["conv_w"],
                                     wp["conv_b"], prompt_len)
            x = jax.nn.silu(out)
        with jax.named_scope("raytpu.mamba1.scan"):
            dt, B, C, A = mamba_inputs(x, wp, c)
            y, end = mamba_scan(
                x, dt, A, B, C, wp["d"],
                valid=(jnp.arange(x.shape[1]) < prompt_len)[None])
            new = lax.dynamic_update_index_in_dim(state["mamba"], end, li, 0)
        return y, (
            {**single, "state": {**state, "mamba": new, "conv": conv}},
            {**handed, "m": y[0]})

    return _recurrence(recur)


def _decode_attn_handing(cache, li, s, c: TransformerConfig, handed):
    """``_decode_attn`` for the "attention" layer whose rows the "cross"
    layers above attend too: the token's own key and value, which the
    step's later layers cannot read from the cache's prefix, go up in
    ``handed``. Returns (output, (the cache, handed))."""
    attend = _decode_attn(cache, li, s.pos, s.b_idx, c, s.visits["attn"])

    def cached_attn(q, k, v):
        out, new = attend(q, k, v)
        return out, (new, {**handed, "k": k[:, 0], "v": v[:, 0]})

    return cached_attn


def _decode_cross(cache, s, c: TransformerConfig, handed):
    """One decode layer's ``attn_fn`` for a "cross" layer: its queries
    [B,1,H,D] against the rows of the LAST "attention" layer below, the
    prefix where it lies in that layer's cache and the token's own row
    from ``handed`` (``_attend_flat_prefix_plus_self``; the step's schedule
    of that layer's visits serves these layers too). Nothing is written:
    returns (output, the cache)."""
    def attend(q):
        return _attend_flat_prefix_plus_self(
            q, cache["k"], cache["v"], handed["k"][:, None],
            handed["v"][:, None], s.pos, layer=c.n_attn_layers - 1,
            schedule=s.visits["attn"]), cache

    return attend


def _prefill_cross(single, c: TransformerConfig, p):
    """One prefill layer's ``attn_fn`` for a "cross" layer, which runs on
    the prompt's last real token alone: its one query [1,1,H,D] against
    the rows the "attention" layer below wrote into ``single`` for the
    bucket, those below ``prompt_len`` (the token's own among them).
    Returns (output [1,1,H,Dv], single)."""
    def attend(q):
        layer, f32 = c.n_attn_layers - 1, jnp.float32
        n_heads, width = q.shape[2:]
        bucket = p.positions.shape[0]
        k, v = (single[name][layer, 0, :bucket] for name in ("k", "v"))
        groups = k.shape[-1] // width
        k, v = (x.reshape(bucket, groups, -1) for x in (k, v))
        qg = q[0, 0].reshape(groups, n_heads // groups, width)
        scores = jnp.einsum("grd,sgd->grs", qg, k,
                            preferred_element_type=f32) * width ** -0.5
        scores = jnp.where(p.positions < p.prompt_len, scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("grs,sgd->grd", probs, v)
        return out.reshape(1, 1, n_heads, -1), single

    return attend


def _decode_eva(cache, li, s, c: TransformerConfig):
    """One decode layer's ``attn_fn`` for an "eva" layer: the token
    attends the slot's first ``eva_read_len(pos)`` rows, the closed
    windows' summaries and then its own window's tokens, plus itself
    (``_attend_prefix_plus_self``: the kernel every dense cache uses, told
    that length and not the position; ``s.visits["eva"]`` is made from
    it), and its row is written there. A parked lane (``pos`` 0) reads
    nothing and its write is dropped. The window the token may fill is
    folded after the step's layers (``_eva_close``). Returns (output, the
    cache)."""
    def attend(q, k, v, wp):
        ek, ev = cache["ek"], cache["ev"]
        rows = eva_read_len(c, s.pos)
        with jax.named_scope("raytpu.eva.attend"):
            out = _attend_prefix_plus_self(
                q, ek, ev, k, v, rows, layer=li, schedule=s.visits["eva"])
            at = jnp.where(s.pos > 0, rows, ek.shape[2])
            new = {**cache,
                   "ek": ek.at[li, s.b_idx, at].set(
                       k[:, 0].astype(ek.dtype), mode="drop"),
                   "ev": ev.at[li, s.b_idx, at].set(
                       v[:, 0].astype(ev.dtype), mode="drop")}
        return out, new

    return attend


def _eva_close(params, cache, pos, c: TransformerConfig):
    """What a decode step does to the "eva" layers' slots once its layers
    have run: a lane whose token filled its window (``pos mod eva_window``
    = ``eva_window`` - 1; the token attended the window's rows, its own
    among them now) has that window's rows pooled, layer by layer, and
    the summaries written over the rows' own first ``eva_window /
    eva_chunk`` (``ops/eva.eva_pool`` under the layer's ``phi`` and
    ``mu``). One loop over the (closing lane, layer) pairs there ARE: on
    a step at which no lane closes a window, all but one in
    ``eva_window`` a lane, it runs no iteration and reads nothing; the
    other lanes never pay. Returns the cache."""
    W, per = c.eva_window, c.eva_window // c.eva_chunk
    ek, ev = cache["ek"], cache["ev"]
    n_layers, _b, _rows, H, D = ek.shape
    closing = _eva_closing(c, pos)
    lanes = jnp.argsort(~closing)  # stable: the closing lanes first
    mixer = params["eva_layers"]["eva"]
    phi, mu = mixer["phi"], mixer["mu"]

    def one(i, kv):
        ek, ev = kv
        b, li = lanes[i // n_layers], i % n_layers
        at = (li, b, pos[b] // W * per, 0, 0)
        ks, vs = eva_pool(
            lax.dynamic_slice(ek, at, (1, 1, W, H, D)),
            lax.dynamic_slice(ev, at, (1, 1, W, H, D)),
            phi[li], mu[li], c.eva_chunk)
        return (lax.dynamic_update_slice(ek, ks, at),
                lax.dynamic_update_slice(ev, vs, at))

    with jax.named_scope("raytpu.eva.pool"):
        ek, ev = lax.fori_loop(
            0, closing.sum(dtype=jnp.int32) * n_layers, one, (ek, ev))
    return {**cache, "ek": ek, "ev": ev}


def _prefill_eva(single, li, p, c: TransformerConfig):
    """One prefill layer's ``attn_fn`` for an "eva" layer: the bucket's
    complete windows are pooled, the prompt attends as the layer is
    written (``ops/eva.eva_attention``: ONE kernel call, a window's
    queries over the summaries before it and its own rows up to the
    diagonal, nothing past ``prompt_len`` computed and those rows zeros;
    a real token's window never sees a summary that holds padding, which
    only windows past the prompt's last can), and the slot is handed what
    a decode step expects to find: the
    summaries of the prompt's ``prompt_len // eva_window`` complete
    windows, then the rows of the tokens after them (rows past
    ``eva_read_len(prompt_len)`` hold whatever: nothing attends them
    before a token overwrites them). ``single`` is one slot's cache.
    Returns (output, single with this layer's rows)."""
    def attend(q, k, v, wp):
        S, W = q.shape[1], c.eva_window
        per = W // c.eva_chunk
        whole = S // W * W
        with jax.named_scope("raytpu.eva.pool"):
            # a window at a time: the float32 copies of a whole bucket's
            # keys and values would be 0.9 GB at 28,672 tokens
            ks, vs = (x.reshape((1, -1) + x.shape[2:]) for x in lax.map(
                lambda kv: eva_pool(*kv, wp["phi"], wp["mu"], c.eva_chunk),
                tuple(x[0, :whole].reshape((-1, W) + x.shape[2:])
                      for x in (k, v))))
        with jax.named_scope("raytpu.eva.attend"):
            out = eva_attention(q, k, v, ks, vs, p.prompt_len, window=W,
                                chunk=c.eva_chunk)
        with jax.named_scope("raytpu.eva.pool"):
            closed = p.prompt_len // W
            rows = jnp.arange(min(single["ek"].shape[2], eva_rows(c, S)))
            new = dict(single)
            for name, raw, pooled in (("ek", k, ks), ("ev", v, vs)):
                kept = jnp.take(raw[0], rows + closed * (W - per), axis=0,
                                mode="clip")
                if whole:  # a bucket shorter than a window closes none
                    kept = jnp.where(
                        (rows < closed * per)[:, None, None],
                        jnp.take(pooled[0], rows, axis=0, mode="clip"), kept)
                new[name] = _put_layer(single[name], kept[None, None], li)
        return out, new

    return attend


def prefill_stat_keys(config: TransformerConfig) -> Tuple[str, ...]:
    """Names of the int32 counters the admission form of
    ``prefill_into_slot`` returns with its first token, in the order they
    leave it (a dict's: sorted): for dropless routed experts
    ``prefill_moe_assignments`` and ``prefill_moe_pair_rows``
    (``ops/moe.routed_ffn``'s ``moe_assignments`` and ``moe_pair_rows``,
    summed over the prompt's routed layers); for a block with an indexer
    ``prefill_attn_blocks``, the (block of queries, block of rows) pairs
    the attention's kernel computes a head for this prompt
    (``ops/attention.prefill_block_pairs``), and
    ``prefill_attn_blocks_bucket``, what the whole bucket's causal blocks
    would be, summed over the prompt's layers; for "eva" layers the same
    two names: the blocks of a window's rows and of the summaries before
    it that the kernel computes, and every block of queries against its
    whole window and all the bucket's summaries
    (``ops/eva.eva_block_pairs``). None for the other models."""
    keys = sum((row.prefill_counters
                for _kind, row, _n in _kinds_of(config)), ())
    if config.moe_experts and config.moe_impl == "dropless":
        keys += ("prefill_moe_assignments", "prefill_moe_pair_rows")
    return tuple(sorted(keys))


def _add_stats(total, stats):
    """``total`` with the counters of ``stats`` that it names added."""
    return {**total, **{k: total[k] + v for k, v in stats.items()
                       if k in total}}


def block_stat_keys(config: TransformerConfig) -> Tuple[str, ...]:
    """Names of the int32 counters ``decode_block`` returns with its
    tokens for this model: a dropless routed layer's (``ops/moe.
    routed_ffn``), and a block with an indexer's: ``dsa_rows_scored``
    (index keys scored), ``dsa_rows_selected`` (rows attended:
    min(pos + 1, index_topk) a live lane a layer) and ``dsa_rows_live``
    (rows a walk over every live row would attend), summed over lanes,
    layers and steps; a model with window layers': ``window_rows_read``,
    the ring rows its decode attention read (min(pos + 1, window) a live
    lane a window layer); a model with "cross" layers': ``cross_rows_read``,
    the rows of ANOTHER layer's cache their decode attention walked (the
    "attention" layer's chunks up to each live lane's length, once a
    "cross" layer: what ``attn_rows_read`` counts for the owner, times
    those layers). None for the other models."""
    keys = ()
    if config.moe_experts and config.moe_impl == "dropless":
        keys += ("moe_assignments", "moe_experts_touched",
                 "moe_experts_capacity", "moe_max_load", "moe_weight_visits")
    return keys + sum(
        (row.counters for _kind, row, _n in _kinds_of(config)), ())


def _dsa_stats(pos, c: TransformerConfig, n: int, cache):
    """One decode step's selection counters, from the lanes' positions."""
    s_max = cache["ik"].shape[2]
    live, rows = pos > 0, pos + 1
    chunk = min(DSA_CHUNK, s_max)  # whole chunks, as _decode_choice walks
    bound = jnp.minimum(jnp.max(pos), s_max)
    return {
        "dsa_rows_scored": c.n_index_layers * pos.shape[0] * jnp.minimum(
            (bound + chunk - 1) // chunk * chunk, s_max),
        "dsa_rows_selected": n * jnp.where(
            live, jnp.minimum(rows, c.index_topk), 0).sum(),
        "dsa_rows_live": n * jnp.where(live, rows, 0).sum(),
    }


def _chosen_prefill_stats(c: TransformerConfig, n: int, bucket: int,
                          prompt_len):
    """What one admission's ``n`` layers that attend under a choice add
    to its counters: the kernel's blocks for the prompt, and for a prompt
    as long as its bucket (a group of heads a call: each its own KV head)."""
    return {"prefill_attn_blocks": n * prefill_block_pairs(
                bucket, prompt_len, 1),
            "prefill_attn_blocks_bucket": n * prefill_block_pairs(
                bucket, bucket, 1)}


def _eva_prefill_stats(c: TransformerConfig, n: int, bucket: int,
                       prompt_len):
    """What one admission's ``n`` "eva" layers add to its counters: the
    blocks their attention computes a head for the prompt, and every
    block of queries against its whole window and all the bucket's
    summaries (``ops/eva.eva_block_pairs``)."""
    computed, every = eva_block_pairs(
        bucket, prompt_len, window=c.eva_window, chunk=c.eva_chunk)
    return {"prefill_attn_blocks": n * computed,
            "prefill_attn_blocks_bucket": n * every}


def _window_stats(pos, c: TransformerConfig, n: int, cache):
    """The ring rows one decode step's window layers read."""
    return {"window_rows_read": n * ring_rows(pos, c.window).sum()}


def _cross_stats(pos, c: TransformerConfig, n: int, cache):
    """The rows one decode step's "cross" layers walk in the cache of the
    layer they read: whole chunks up to each live lane's length."""
    arrays = jax.tree.leaves(cache_rows(cache))
    s_max = arrays[0].shape[2]
    chunk = min(dense_attn_chunk(arrays), s_max)
    walked = jnp.minimum(-(-jnp.minimum(pos, s_max) // chunk) * chunk, s_max)
    return {"cross_rows_read": n * walked.sum()}


def _eva_stats(pos, c: TransformerConfig, n: int, cache):
    """What one decode step's "eva" layers read and fold, from the lanes'
    positions: the (row, layer) pairs of the open windows' tokens and of
    the closed windows' summaries their attention read (a parked lane:
    none), and the (window, layer) pairs the step closed."""
    W, per = c.eva_window, c.eva_window // c.eva_chunk
    return {"eva_window_rows_read": n * (pos % W).sum(),
            "eva_summary_rows_read": n * (pos // W * per).sum(),
            "eva_windows_closed": n * _eva_closing(c, pos).sum()}


def _zero_stats(config: TransformerConfig):
    return {k: jnp.zeros((), jnp.int32) for k in block_stat_keys(config)}


# ---------------- the table of layer kinds ----------------

class _Step(NamedTuple):
    """What one decode step holds for every layer: the lanes' positions
    ``pos`` [B], ``b_idx`` (arange(B)), and ``visits``: for each kind the
    model has, the step's schedule for that kind's kernel (``_Kind.
    visits``), made once and shared by the kind's layers."""
    pos: jax.Array
    b_idx: jax.Array
    visits: Dict


class _Prompt(NamedTuple):
    """What one prefill holds for every layer: the prompt's real length
    and the padded prompt's ``positions`` [S]."""
    prompt_len: jax.Array
    positions: jax.Array


class _Kind(NamedTuple):
    """One row of ``_KINDS``: all that this module knows of one kind of
    layer (``transformer.layer_kind``: the key its mixer's weights sit
    under). A new kind is a row here and the functions it names."""
    # c -> how many of the model's layers are of this kind
    layers: Callable
    # (c, n, batch, max_len) -> (row leaves, state leaves) its n layers
    # keep for ``batch`` slots (``init_kv_cache``)
    keeps: Callable
    # (cache, li, lp, c, step, choice) -> a decode layer's ``attn_fn``
    decode: Callable
    # (single, li, lp, c, prompt, choice) -> a prefill layer's ``attn_fn``
    prefill: Callable
    # whether its row leaves hold ONE ROW A TOKEN from row 0 of the slot:
    # an admission then holds, and writes, the bucket's rows of them alone
    # (``_admission_slot``); of any other leaf the whole slot's
    row_a_token: bool = True
    # (pos, c, cache) -> the step's schedule for its decode kernel
    visits: Optional[Callable] = None
    # its int32 counters (``block_stat_keys``), and (pos, c, n, cache) ->
    # what one decode step adds to them, from the lanes' positions
    counters: Tuple[str, ...] = ()
    counts: Optional[Callable] = None
    # an admission's int32 counters of the kind (``prefill_stat_keys``),
    # and (c, n, bucket, prompt_len) -> what its n layers add to them
    prefill_counters: Tuple[str, ...] = ()
    prefill_counts: Optional[Callable] = None
    # whether it keeps nothing and reads what layers below keep or hand on
    # for the SAME token: a prefill runs such layers, where they are the
    # model's last, on the prompt's last real token alone
    last_token: bool = False
    # (params, cache, pos, c) -> the cache: what a decode step does to
    # its slots once the step's layers have run
    closes: Optional[Callable] = None
    # the layers that attend every row only. (c, queries, rows, prompt) ->
    # what the layer scan's carry hands from layer to layer (``prompt``:
    # in a prefill), or None
    hands_on: Optional[Callable] = None
    # the kind whose row leaves give a slot its length (``_length_kind``).
    # (c, s_max) -> rows of a slot one visit of the decode attention
    # reads; whether it walks EVERY slot up to the longest lane; (c, pos)
    # -> the rows of a slot its decode attention reads when the lane's
    # token is at ``pos`` (a row a token: ``pos`` itself), and (c,
    # max_len) -> the rows its slot has (``attn_rows_read``: the host's
    # count)
    chunk: Optional[Callable] = None
    walks_longest: bool = False
    read_len: Callable = lambda c, pos: pos
    slot_rows: Callable = lambda c, max_len: max_len


# In the order a decode step makes the kinds' visits (a window model's
# programs make the rings' before the rows').
_KINDS = {
    "ssm": _Kind(
        layers=lambda c: c.n_ssm_layers, keeps=_ssm_keeps,
        decode=lambda cache, li, lp, c, s, choice: _decode_recur(
            cache, li, s.pos, c),
        prefill=lambda single, li, lp, c, p, choice: _prefill_recur(
            single, li, p.prompt_len, c)),
    "kda": _Kind(
        layers=lambda c: c.n_kda_layers, keeps=_kda_keeps,
        decode=lambda cache, li, lp, c, s, choice: _decode_kda(
            cache, li, s.pos, c),
        prefill=lambda single, li, lp, c, p, choice: _prefill_kda(
            single, li, p.prompt_len, c)),
    "mamba": _Kind(
        layers=lambda c: c.n_of("mamba"), keeps=_mamba_keeps,
        decode=lambda cache, li, lp, c, s, handed: _decode_mamba(
            cache, li, s.pos, c, handed),
        prefill=lambda single, li, lp, c, p, handed: _prefill_mamba(
            single, li, p.prompt_len, c, handed)),
    "swa": _Kind(
        layers=lambda c: c.n_window_layers, keeps=_window_keeps,
        decode=lambda cache, li, lp, c, s, choice: _decode_window_attn(
            cache, li, s.pos, s.b_idx, c, s.visits["swa"]),
        prefill=lambda single, li, lp, c, p, choice: _prefill_window_attn(
            single, li, p.prompt_len, c),
        # the rings' visits: one a lane
        visits=lambda pos, c, cache: slot_schedule(
            ring_rows(pos, c.window), c.window, c.window),
        counters=("window_rows_read",), counts=_window_stats),
    "attn": _Kind(
        layers=lambda c: c.n_attn_layers, keeps=_attn_keeps,
        decode=lambda cache, li, lp, c, s, choice: _decode_attn(
            cache, li, s.pos, s.b_idx, c, s.visits["attn"]),
        prefill=lambda single, li, lp, c, p, choice: _prefill_attn(
            single, li, c, p.prompt_len),
        visits=lambda pos, c, cache: _visits(
            pos, jax.tree.leaves(cache_rows(cache))),
        hands_on=lambda c, queries, rows, prompt=False: None,
        chunk=_dense_chunk),
    "eva": _Kind(
        layers=lambda c: c.n_of("eva"), keeps=_eva_keeps,
        decode=lambda cache, li, lp, c, s, choice: _decode_eva(
            cache, li, s, c),
        prefill=lambda single, li, lp, c, p, choice: _prefill_eva(
            single, li, p, c),
        visits=lambda pos, c, cache: _visits(
            eva_read_len(c, pos), [cache["ek"], cache["ev"]]),
        counters=("eva_window_rows_read", "eva_summary_rows_read",
                  "eva_windows_closed"),
        counts=_eva_stats,
        prefill_counters=("prefill_attn_blocks",
                          "prefill_attn_blocks_bucket"),
        prefill_counts=_eva_prefill_stats, closes=_eva_close,
        chunk=_dense_chunk, read_len=eva_read_len, slot_rows=eva_rows,
        row_a_token=False),
    "gmu": _Kind(
        layers=lambda c: c.n_of("gmu"), keeps=_nothing_kept,
        decode=lambda cache, li, lp, c, s, handed: recalling(
            handed["m"][:, None], cache),
        prefill=lambda single, li, lp, c, p, handed: recalling(
            handed["m"][None], single),
        last_token=True),
    "cross": _Kind(
        layers=lambda c: c.n_of("cross"), keeps=_nothing_kept,
        decode=lambda cache, li, lp, c, s, handed: _decode_cross(
            cache, s, c, handed),
        prefill=lambda single, li, lp, c, p, handed: _prefill_cross(
            single, c, p),
        counters=("cross_rows_read",), counts=_cross_stats,
        last_token=True),
    # a "single" block's routed FFN: no mixer, so its ``attn_fn`` takes
    # nothing and hands the cache back as it is; the routed layer's
    # counters are ``block_stat_keys``' own
    "moe": _Kind(
        layers=lambda c: c.n_of("experts"), keeps=_nothing_kept,
        decode=lambda cache, li, lp, c, s, choice: lambda: cache,
        prefill=lambda single, li, lp, c, p, choice: lambda: single),
}
# the "attn" row of a latent block with an indexer (``c.index_topk``)
_CHOSEN = _Kind(
    layers=lambda c: c.n_attn_layers, keeps=_chosen_keeps,
    decode=lambda cache, li, lp, c, s, choice: _decode_attn_chosen(
        cache, li, s.pos, s.b_idx, c, lp["attn"], choice),
    prefill=lambda single, li, lp, c, p, choice: _prefill_attn_chosen(
        single, li, lp["attn"], choice, c, p.prompt_len),
    counters=("dsa_rows_scored", "dsa_rows_selected", "dsa_rows_live"),
    counts=_dsa_stats,
    prefill_counters=("prefill_attn_blocks", "prefill_attn_blocks_bucket"),
    prefill_counts=_chosen_prefill_stats,
    # a prompt's choice as its attention's kernel reads it, a step's as
    # the walk does
    hands_on=lambda c, queries, rows, prompt=False: {
        "mask": jnp.zeros((queries, rows), jnp.int8 if prompt else bool),
        "k": jnp.zeros((queries, c.index_head_dim), c.dtype)},
    chunk=lambda c, s_max: min(DSA_CHUNK, s_max), walks_longest=True)


def _handed_up(c: TransformerConfig, queries: int, rows: int,
               prompt: bool = False):
    """What the layers of a decoder-hybrid-decoder hand on for each of
    ``queries`` tokens: ``m`` [queries, inner], the last "mamba" layer's
    recurrence output (for the "gmu" layers), and on decode ``k`` and
    ``v``, the token's own row as the "attention" layer made it (for the
    "cross" layers, heads paired as the attentions see them; a prefill's
    one query reads every row back from the slot)."""
    m = {"m": jnp.zeros((queries, c.mamba_inner), c.dtype)}
    if prompt:
        return m
    r = 2 if c.diff_attn else 1
    return {**m, **{name: jnp.zeros((queries, c.kv_heads // r, r * d),
                                    c.dtype)
                    for name, d in (("k", c.d_head), ("v", c.v_dim))}}


# the "attn" row of a model whose upper layers read what the lower hand on
_SHARED = _KINDS["attn"]._replace(
    decode=lambda cache, li, lp, c, s, handed: _decode_attn_handing(
        cache, li, s, c, handed),
    hands_on=_handed_up)


def _row(kind: str, c: TransformerConfig) -> _Kind:
    """The table's row for a layer of ``kind`` in ``c``'s model."""
    if kind != "attn":
        return _KINDS[kind]
    return (_CHOSEN if c.index_topk else
            _SHARED if c.n_of("gmu") or c.n_of("cross") else _KINDS[kind])


def _length_kind(c: TransformerConfig) -> str:
    """The kind whose ROW leaves give a slot its length, and whose decode
    attention the host counts the reads of (``attn_rows_read``): "eva"
    where the model's layers are of that kind (what such a slot holds
    grows by a row a token inside a window and shrinks when one closes),
    else "attn", the layers that attend every row and keep them, a row a
    token."""
    return "eva" if c.n_of("eva") else "attn"


def _kinds_of(c: TransformerConfig):
    """(kind, its row, how many of the model's layers are of it) for the
    kinds ``c``'s model has, in the table's order. The kind that gives a
    slot its length (``_length_kind``) is always among them: every cache
    has row leaves, [.., B, S, ..], whose third axis is the slot's room
    (a "cross" layer attends every row too and keeps none: it reads the
    "attn" layers'). A model of "eva" layers has no "attn" row at all."""
    found = ((kind, _row(kind, c)) for kind in _KINDS)
    return [(kind, row, row.layers(c)) for kind, row in found
            if kind == _length_kind(c) or row.layers(c)]


def _admission_slot(cache, c: TransformerConfig, bucket: int):
    """One slot of ``cache`` as an admission at ``bucket`` holds it, all
    zeros: of a leaf that keeps one row a token from row 0 (the row leaves
    of a kind with ``_Kind.row_a_token``) the bucket's rows alone, [layers,
    1, min(bucket, S_max), ...], which is all a prompt fills of it and all
    the admission writes back; the rest of the slot stays as it lies (a
    decode step reads a slot below its position and overwrites a cell
    before reaching it, as with the rows of a bucket's padding). Every
    other leaf whole, [layers, 1, ...]: states, convolution tails and a
    window's ring are the prompt's to overwrite, and so are the rows of a
    kind that does not keep one a token ("eva": where a row lies depends
    on the windows closed before it)."""
    short = {name for _kind, row, n in _kinds_of(c) if row.row_a_token
             for name in row.keeps(c, n, 1, 1)[0]}

    def held(name, a):
        return jnp.zeros_like(a[:, :1, :bucket] if name in short
                              else a[:, :1])

    return {name: jax.tree.map(partial(held, name), leaf)
            for name, leaf in cache.items()}


def _taps(c: TransformerConfig, x):
    """Room for every layer's INPUT, shaped as ``x``, a kind: ``{kind:
    [the model's layers of that kind, *x.shape]}``. A program asked for
    ``taps`` hands them back with the last layer's output under "out", so
    that a check can put one layer's input, as the program made it, to a
    reference of that layer alone (a layer's output is the next one's
    input). Not asked for, nothing of this is in the program."""
    return {kind: jnp.zeros((n,) + x.shape, x.dtype)
            for kind, _row, n in _kinds_of(c) if n}


def _tap(taps, lp, li, x):
    kind = layer_kind(lp)
    return {**taps, kind: lax.dynamic_update_index_in_dim(
        taps[kind], x, li, 0)}


def _prompt_parts(stack, lc: TransformerConfig, first: int):
    """One group of ``layer_groups`` as a prefill runs it: ``(stacks,
    index of their first layer, whether on the last real token alone)``.
    One part, but where the group's LAST layers are all of kinds that keep
    nothing (``_Kind.last_token``: "gmu", "cross"): nothing of the
    prompt's other tokens outlives such layers, so the layers below them
    run over the bucket and they on the prompt's last real token alone."""
    if not lc.layer_types:
        return [(stack, first, False)]
    n = sum(s["ln1"]["scale"].shape[0] for s in stack.values())
    kinds = lc.layer_types[first:first + n]
    upper = {kind for kind in stack
             if _KINDS[layer_kind(stack[kind])].last_token}
    cut = next((i for i in range(n) if set(kinds[i:]) <= upper), n)
    if cut in (0, n) or upper & set(kinds[:cut]):
        return [(stack, first, False)]
    return [({k: v for k, v in stack.items() if k not in upper}, first,
             False),
            ({k: v for k, v in stack.items() if k in upper}, first + cut,
             True)]


def _decode_forward_multi(params, token, cache, pos,
                          config: TransformerConfig, taps: bool = False):
    """Core of the per-slot decode step (tokens [B] at per-slot positions
    pos [B]); shared by decode_step_multi and the scanned decode_block.
    The whole cache travels as the layer scan's carry (aliased in place)
    and each layer writes its token's row. Returns (logits [B,V], cache,
    stats), and with ``taps`` every layer's input as well (``_taps``)."""
    c = config
    x = embed_tokens(params, token, c)[:, None]  # [B,1,D]
    # a parked lane's token picks no expert (only a routed layer asks)
    routed = c.moe_experts and c.moe_impl == "dropless"
    live = (pos > 0)[:, None] if routed else None
    B = token.shape[0]
    kinds = _kinds_of(c)
    # each kind's visits of its kernel, the same for every layer of the
    # step, and what a block with an indexer's layers hand on
    step = _Step(pos, jnp.arange(B), {
        kind: row.visits and row.visits(pos, c, cache)
        for kind, row, _n in kinds})
    choice = _row("attn", c).hands_on(
        c, B, jax.tree.leaves(cache_rows(cache))[0].shape[2])
    carry = (x, cache, _zero_stats(c), choice) + (
        (_taps(c, x),) if taps else ())
    for stack, lc, first in layer_groups(params, c):
        def layer(carry, lp, li, lc=lc):
            x, cache, total, choice, *tapped = carry
            attn = _row(layer_kind(lp), lc).decode(
                cache, li, lp, lc, step, choice)
            y, _aux, cache, stats = apply_block(
                x, lp, lc, pos[:, None], attn, token_mask=live)
            if isinstance(cache, tuple):  # a layer that hands something on
                cache, choice = cache
            return (y, cache, _add_stats(total, stats), choice) + tuple(
                _tap(t, lp, li, x) for t in tapped)

        carry = scan_stack(layer, carry, stack, lc, first)
    x, cache, stats, _choice, *tapped = carry
    for _kind, row, n in kinds:
        if row.counts:
            stats = _add_stats(stats, row.counts(pos, c, n, cache))
        if row.closes:
            cache = row.closes(params, cache, pos, c)
    return (lm_logits(params, x, c)[:, 0, :], cache, stats) + tuple(
        {**t, "out": x} for t in tapped)


@partial(jax.jit, static_argnames=("config",), donate_argnums=(2,))
def decode_step_multi(params, token, cache, pos, config: TransformerConfig):
    """One token per SLOT at per-slot absolute positions.

    token [B] int32, pos [B] int32 (position each slot's token occupies).
    Inactive slots simply decode garbage into their own lane — they attend
    only their own cache row, so active slots are unaffected; the engine
    ignores their outputs. Returns (logits [B, V], cache); of a model
    with several prediction heads logits [B, n_pred_heads, V]."""
    return _decode_forward_multi(params, token, cache, pos, config)[:2]


def _sample_vec(logits, temps, seeds, counts):
    """Per-slot on-device sampling: greedy where temps==0, Gumbel-max
    categorical elsewhere, deterministic per (seed, count). Of several
    prediction heads' logits [B, n_pred_heads, V] the NEXT token's are
    sampled, head 0's."""
    if logits.ndim == 3:
        logits = logits[:, 0]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def one(lg, t, s, c):
        key = jax.random.fold_in(jax.random.key(s), c)
        g = jax.random.gumbel(key, lg.shape, jnp.float32)
        return jnp.argmax(
            lg.astype(jnp.float32) / jnp.maximum(t, 1e-6) + g
        ).astype(jnp.int32)

    sampled = jax.vmap(one)(logits, temps, seeds, counts)
    return jnp.where(temps <= 0.0, greedy, sampled)


@partial(jax.jit, static_argnames=("config", "steps", "taps"),
         donate_argnums=(1,))
def decode_block(params, cache, token, pos, temps, seeds, counts,
                 config: TransformerConfig, steps: int, taps: bool = False):
    """``steps`` decode iterations as ONE compiled program with on-device
    per-slot sampling — the serving engine's unit of work. One host
    transfer ([B, steps] int32 tokens) per block instead of per token:
    the host<->device link's latency is paid once per block, and the
    per-slot positions let slots admitted at different times share the
    batch.

    A lane at ``pos`` 0 is PARKED: it stays at 0 (the engine puts a freed
    slot there), and the decode attention reads nothing of its slot (in a
    block with an indexer it does not move the walk's bound, the largest
    ``pos``); a free lane that kept counting would be read to S_max on an
    idle engine.

    Returns (tokens [B, steps], cache, token', pos', counts', stats):
    ``stats`` holds the block's int32 counters named by
    ``block_stat_keys`` (an empty dict for most models), summed over its
    steps and layers; they leave the device with the tokens. With
    ``taps`` (another program: a check's, never the engine's) a seventh
    value: every step's layer inputs, ``{kind: [steps, layers of the
    kind, B, 1, D], "out": [steps, B, 1, D]}`` (``_taps``)."""
    def step(carry, _):
        tok, cache, pos, counts, total = carry
        logits, cache, stats, *tapped = _decode_forward_multi(
            params, tok, cache, pos, config, taps)
        nxt = _sample_vec(logits, temps, seeds, counts)
        return (nxt, cache, pos + (pos > 0), counts + 1,
                _add_stats(total, stats)), (nxt, *tapped)

    (token, cache, pos, counts, stats), (toks, *tapped) = lax.scan(
        step, (token, cache, pos, counts, _zero_stats(config)), None,
        length=steps,
    )
    return (toks.T, cache, token, pos, counts, stats, *tapped)


@partial(jax.jit, static_argnames=("config", "taps"), donate_argnums=(4, 6))
def prefill_into_slot(params, prompt, prompt_len, slot, cache,
                      config: TransformerConfig, lanes=None,
                      temperature=None, seed=None, taps: bool = False):
    """Run ONE padded prompt [1, Sb] and write its rows into ``slot`` of
    the shared batch cache (static shapes: Sb is a bucket size; compile
    count = number of buckets). Positions past prompt_len write junk rows
    that are never attended: the prompt's own attention is causal, a decode
    step reads a slot below its position, and decode overwrites those
    cells before reaching them. For the same reason the admission works on
    the bucket's rows of the slot and on no other (``_admission_slot``):
    rows [Sb, S_max) of a leaf that keeps a row a token are neither zeroed
    nor written, and hold what an earlier request left there. An int8
    weight that says where it lies (``QTensor.order``) is read there, at
    every bucket (``_read_where_they_lie``).

    Latent attention takes the plain form here: per-head keys and values
    are expanded from the prompt's latents and attended causally over the
    prompt; what the slot keeps is the latent rows ``c_kv`` and ``rot(k_r)``
    of every layer and, where the block has an indexer, the index key of
    every layer that owns one. Such a block's queries attend their chosen
    rows alone, inside the prefill kernel (``_prefill_choice``,
    ``blocked_causal_attention`` under the choice's mask: the choice, a
    mask [Sb, Sb] in int8, travels from the layer that makes it to the
    layers that share it in the layer scan's carry; the blocks of queries
    past ``prompt_len`` are skipped).

    A state-space layer runs its chunked scan over the padded prompt and
    the slot's STATE leaves are overwritten whole with the state at
    ``prompt_len`` (``_prefill_recur``): a reused slot starts from an
    empty state, whatever the lane did while it was parked.

    Returns (last-valid-token logits [V], cache); of a model with
    several prediction heads logits [n_pred_heads, V].

    Given ``lanes``, the engine's five per-slot vectors (token, pos,
    temps, seeds, counts: ``decode_block``'s arguments, donated here with
    the cache), and the request's ``temperature`` and ``seed``, the same
    program is a whole admission: it samples the first token from those
    logits (``_sample_vec``, one row, count 0), writes the slot's entry of
    each vector (the token, ``prompt_len``, the temperature, the seed, 1)
    and returns (first token, cache, lanes, stats). Hand every scalar over
    as a numpy value of one dtype (``np.int32``, ``np.float32``): a Python
    number is weakly typed, which is another program. ``stats`` is empty
    (no output at all) but for the int32 scalars ``prefill_stat_keys``
    names and describes (dropless routed experts' pairs, the blocks an
    attention under a choice computes), summed over the layers; they leave
    the device as the token does.

    With ``taps`` (another program: a check's, never the engine's) one
    value more: every layer's input over the bucket, ``{kind: [layers of
    the kind, 1, Sb, D], "out": [1, Sb, D]}`` (``_taps``; a model whose
    upper layers run on the last token alone has none to give)."""
    c = config
    S = prompt.shape[1]
    single = _admission_slot(cache, c, S)
    x = embed_tokens(params, prompt, c)
    positions = jnp.arange(S)
    # a prompt's padding picks no expert (only a routed layer asks)
    routed = c.moe_experts and c.moe_impl == "dropless"
    real = (positions < prompt_len)[None] if routed else None

    prompt_holds = _Prompt(prompt_len, positions)
    choice = _row("attn", c).hands_on(c, S, S, prompt=True)
    # what an admission reports: of its routed layers, summed as they run,
    # and of its kinds, from the prompt's length (none: an empty dict)
    routed_stats = {k: jnp.zeros((), jnp.int32)
                    for k in prefill_stat_keys(c)}
    for _kind, row, n in _kinds_of(c):
        if row.prefill_counts:
            routed_stats = _add_stats(
                routed_stats, row.prefill_counts(c, n, S, prompt_len))
    carry = (x, single, choice, routed_stats) + (
        (_taps(c, x),) if taps else ())
    narrowed = False
    for stack, lc, first in layer_groups(params, c):
        for part, at, last_token in _prompt_parts(stack, lc, first):
            if last_token:  # from here on: the last real token alone
                if taps:
                    raise ValueError("no taps where layers run on the "
                                     "prompt's last token alone")
                narrowed = True
                x, single, choice, total = carry
                carry = (lax.dynamic_slice_in_dim(x, prompt_len - 1, 1, 1),
                         single, jax.tree.map(
                             lambda a: lax.dynamic_slice_in_dim(
                                 a, prompt_len - 1, 1, 0), choice), total)
                positions, real = (
                    lax.dynamic_slice_in_dim(a, prompt_len - 1, 1, -1)
                    if a is not None else None for a in (positions, real))

            def layer(carry, lp, li, lc=lc, positions=positions, real=real):
                x, single, choice, total, *tapped = carry
                lp = _read_where_they_lie(lp)
                attn = _row(layer_kind(lp), lc).prefill(
                    single, li, lp, lc, prompt_holds, choice)
                y, _aux, single, stats = apply_block(
                    x, lp, lc, positions, attn, token_mask=real)
                if isinstance(single, tuple):  # it hands something on
                    single, choice = single
                return (y, single, choice, _add_stats(total, {
                    "prefill_" + k: v for k, v in stats.items()})) + tuple(
                        _tap(t, lp, li, x) for t in tapped)

            # (a scope of its own: what the last-token rule leaves of them)
            with jax.named_scope("raytpu.upper.last_token"
                                 ) if last_token else nullcontext():
                carry = scan_stack(layer, carry, part, lc, at)
    x, single, _choice, routed_stats, *tapped = carry
    tapped = tuple({**t, "out": x} for t in tapped)
    x = _norm(x, params["final_ln"], c)
    head = (params["embed"].T if c.tie_embeddings else params["lm_head"])
    # [D] — last REAL token's features (all that is left where the upper
    # layers ran on that token alone)
    last = x[0, 0] if narrowed else x[0, prompt_len - 1]
    if c.n_pred_heads > 1:
        logits = pred_logits(last, head, c)
    else:
        logits = last @ head.astype(c.dtype)
        if c.logit_scale != 1.0:
            logits = logits * c.logit_scale
    # every leaf from its start: the bucket's rows, or the whole slot
    cache = jax.tree.map(
        lambda big, one: lax.dynamic_update_slice(
            big, one, (0, slot) + (0,) * (big.ndim - 2)),
        cache, single)
    if lanes is None:
        return (logits, cache, *tapped)
    first = _sample_vec(logits[None], temperature[None], seed[None],
                        jnp.zeros(1, jnp.int32))[0]
    lanes = tuple(lane.at[slot].set(v) for lane, v in zip(
        lanes, (first, prompt_len, temperature, seed, 1)))
    return (first, cache, lanes, routed_stats, *tapped)


def generate(
    params,
    prompt: jax.Array,  # [B, S] int32
    config: TransformerConfig,
    *,
    max_new_tokens: int = 32,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    max_len: Optional[int] = None,
) -> jax.Array:
    """Returns [B, max_new_tokens] generated ids (greedy when
    temperature=0), through the two programs the serving engine runs: row
    ``b`` is prefilled into slot ``b`` of a fresh cache
    (``prefill_into_slot``, one compiled program called B times), then ONE
    ``decode_block`` makes the remaining tokens, so every block the config
    describes is generated the way it is served.

    Sampling (temperature > 0) is ``_sample_vec``'s: deterministic per
    ``rng``, which seeds each row's own stream (not the stream
    ``jax.random.categorical`` would draw from ``rng``)."""
    B, S = prompt.shape
    max_len = max_len or config.max_seq_len
    if S + max_new_tokens > max_len:
        raise ValueError(
            f"prompt {S} + new {max_new_tokens} exceeds max_len {max_len}"
        )
    rng = rng if rng is not None else jax.random.key(0)
    seeds = jax.random.randint(
        rng, (B,), 0, jnp.iinfo(jnp.int32).max, jnp.int32)
    temps = jnp.full((B,), temperature, jnp.float32)
    cache = init_kv_cache(config, B, max_len)
    logits = []
    for b in range(B):
        row, cache = prefill_into_slot(
            params, prompt[b:b + 1], jnp.int32(S), jnp.int32(b), cache,
            config)
        logits.append(row)
    first = _sample_vec(
        jnp.stack(logits), temps, seeds, jnp.zeros(B, jnp.int32))
    if max_new_tokens == 1:
        return first[:, None]
    rest = decode_block(
        params, cache, first, jnp.full((B,), S, jnp.int32), temps, seeds,
        jnp.ones(B, jnp.int32), config, max_new_tokens - 1)[0]
    return jnp.concatenate([first[:, None], rest], axis=1)
