"""Decode attention over a ragged cache prefix: a Pallas TPU kernel.

One new token a slot attends its slot's cache rows below ``pos[b]`` plus
itself. A decode step's attention is an HBM read and a masked row is read
like a live one, so each slot is read in whole chunks up to ITS OWN
length: the schedule (``slot_schedule``) lists the (slot, chunk) visits of
the live rows, is scalar-prefetched and is the kernel's grid, with the
number of visits dynamic. A parked slot (``pos`` 0) has one visit that
names the block its neighbour fetched, so it reads nothing: the pipeline
fetches a block only when its index changes. A chunk is read where it
lies in the stacked [L, B, S_max, ...] cache, indexed by (layer, slot,
first row): nothing slices a layer out.

One body serves both dense caches, told apart by shapes alone. MHA / GQA:
one key array [L, B, S, Hkv, D] and a value array like it, as wide or
narrower ([.., Dv]; a cache whose rows hold their heads flat is one KV
head to the kernel, its caller's business). Latent
attention: one key all heads share, in two arrays [L, B, S, R] and
[L, B, S, rope], whose first is the value too: the grouped-query form
with one KV head, a key in parts and no value array. The chunk's rows of
all KV heads lie flat, [chunk * Hkv, D], and every query head is scored
against them all in ONE product, the other heads' columns masked: a slice
of one head out of the tile would be a re-layout, and the product is
bound by the rows it streams through the MXU, not by the query heads
beside them.

The mathematics is the plain form's: bf16 operands, float32 scores,
float32 running max / sum / accumulator seeded by the token's own
position (or, for a window layer's ring, which already holds the token's
row, by the layer's sink: a logit of sum 1 that carries no value,
``generation._attend_ring``), the strict mask ``row < pos[b]``. Off the
TPU the kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF

# What one visit reads of all the cache's arrays together: large enough
# that a grid step's fixed cost hides under its DMA, small enough that a
# short lane's last chunk holds few dead rows.
_VISIT_BYTES = 2 ** 20


@partial(jax.tree_util.register_dataclass,
         data_fields=["slot", "lo", "fetch_slot", "fetch_row", "visits"],
         meta_fields=["chunk"])
@dataclass(frozen=True)
class Schedule:
    slot: jax.Array  # [V] slot of visit v (V = slots x chunks a slot)
    lo: jax.Array  # [V] first row the visit attends: its chunk's
    fetch_slot: jax.Array  # [V] the block the visit names: its own rows,
    fetch_row: jax.Array  # [V] or for a parked slot a neighbour's
    visits: jax.Array  # () the visits there are: the others are padding
    chunk: int  # rows a visit reads


def chunk_rows(row_bytes: int, s_max: int) -> int:
    """Rows of one slot a visit reads: the largest power of two whose
    rows, ``row_bytes`` each over all the cache's arrays, stay within
    ``_VISIT_BYTES``; at least a tile's 16, at most ``s_max``."""
    rows = max(_VISIT_BYTES // row_bytes, 16)
    return min(1 << (rows.bit_length() - 1), s_max)


def slot_schedule(pos: jax.Array, s_max: int, chunk: int) -> Schedule:
    """The kernel's visits for lanes at ``pos`` (int32 [B]): slot by slot,
    the chunks of ``chunk`` rows that hold a row below ``pos``; one empty
    visit for a slot that has none. The last chunk of an ``s_max`` that
    ``chunk`` does not divide is read from ``s_max - chunk`` (a block must
    stay in bounds) and the rows it re-reads are masked."""
    n_slots = pos.shape[0]
    total = n_slots * -(-s_max // chunk)
    live = jnp.minimum(pos, s_max)
    spans = jnp.maximum(-(-live // chunk), 1)
    slot = jnp.repeat(jnp.arange(n_slots, dtype=jnp.int32), spans,
                      total_repeat_length=total)
    before = jnp.cumsum(spans) - spans  # visits ahead of each slot's
    v = jnp.arange(total, dtype=jnp.int32)
    # the padding stays a valid block, whoever looks ahead at it
    lo = jnp.clip((v - before[slot]) * chunk, 0, s_max - 1)
    # a visit without rows names the last block fetched before it (the
    # first one fetched after it, ahead of every live slot)
    reads = lo < live[slot]
    last = jax.lax.cummax(jnp.where(reads, v, -1))
    src = jnp.where(last >= 0, last, jnp.argmax(reads))
    return Schedule(
        slot, lo.astype(jnp.int32), slot[src],
        jnp.minimum(lo, s_max - chunk)[src].astype(jnp.int32),
        spans.sum().astype(jnp.int32), chunk)


def decode_attention(
    q: Sequence[jax.Array],  # each [B, H, Dk_i]: the query, part by part
    keys: Sequence[jax.Array],  # each [L, B, S, Hkv, Dk_i] or [L, B, S, Dk_i]
    values: Optional[jax.Array],  # like keys[0]; None: keys[0] is the value
    m0: jax.Array,  # [B, H] float32: the token's own score, scaled
    acc0: jax.Array,  # [B, H, Dv] float32: the token's own value
    pos: jax.Array,  # [B] int32: rows of each slot's prefix
    schedule: Schedule,  # slot_schedule(pos, S, chunk)
    *,
    layer,
    scale: float,
    rows_last: Sequence[bool] = (),
) -> jax.Array:
    """softmax over each slot's rows below ``pos`` and the token itself:
    [B, H, Dv] in ``q``'s type. ``m0`` and ``acc0`` seed the online
    softmax with the token's own position (sum 1; a caller whose rows hold
    the token already seeds it with a sink's logit and a zero value), so
    a slot without rows returns ``acc0``. A key part marked in ``rows_last`` is handed over as
    [L, B, Dk_i, S] (how the chip keeps a narrow array: see the caller)."""
    n_slots, n_heads, _ = q[0].shape
    s_max = keys[0].shape[2]
    h_kv = keys[0].shape[3] if keys[0].ndim == 5 else 1
    n_rep = n_heads // h_kv
    d_v = acc0.shape[-1]
    n_parts = len(keys)
    rows_last = tuple(rows_last) or (False,) * n_parts
    chunk = schedule.chunk
    cols = chunk * h_kv
    f32 = jnp.float32

    def scores(q_ref, k_ref, swapped):
        if swapped:  # [Dk, chunk]
            return jnp.dot(q_ref[...], k_ref[...], preferred_element_type=f32)
        return jax.lax.dot_general(
            q_ref[...], k_ref[...].reshape(cols, k_ref.shape[-1]),
            (((1,), (1,)), ((), ())), preferred_element_type=f32)

    def kernel(slot, lo, _fslot, frow, pos, _layer, *refs):
        q_refs, k_refs = refs[:n_parts], refs[n_parts:2 * n_parts]
        refs = refs[2 * n_parts:]
        v_ref = k_refs[0] if values is None else refs[0]
        m0_ref, acc0_ref, o_ref, m_s, l_s, acc_s = refs[-6:]
        v = pl.program_id(0)
        first, rows = lo[v], jnp.minimum(pos[slot[v]], s_max)

        @pl.when(first == 0)
        def _seed():
            m_s[...] = m0_ref[...]
            l_s[...] = jnp.ones_like(l_s)
            acc_s[...] = acc0_ref[...]

        @pl.when(first < rows)
        def _attend():
            s = sum(map(scores, q_refs, k_refs, rows_last)) * scale
            col = jax.lax.broadcasted_iota(jnp.int32, (n_heads, cols), 1)
            row = frow[v] + col // h_kv
            mine = (row >= first) & (row < rows)
            if h_kv > 1:  # the columns of a query head's own KV head
                head = jax.lax.broadcasted_iota(
                    jnp.int32, (n_heads, cols), 0)
                mine &= col % h_kv == head // n_rep
            s = jnp.where(mine, s, NEG_INF)
            m = m_s[...]
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # a masked column: exp(-1e30 - m) == 0
            shrink = jnp.exp(m - m_new)
            m_s[...] = m_new
            l_s[...] = shrink * l_s[...] + p.sum(axis=-1, keepdims=True)
            acc_s[...] = shrink * acc_s[...] + jnp.dot(
                p.astype(v_ref.dtype), v_ref[...].reshape(cols, d_v),
                preferred_element_type=f32)

        @pl.when(first + chunk >= rows)
        def _finish():
            o_ref[...] = (acc_s[...] / l_s[...]).astype(o_ref.dtype)

    def at_slot(v, slot, lo, fslot, frow, pos, layer):
        return slot[v], 0, 0

    def rows_of(array, swapped=False):
        def at(v, slot, lo, fslot, frow, pos, layer):
            # where the rows are one of an array's two minor dims the
            # compiler wants a whole tile's multiple, and is told what holds
            row = pl.multiple_of(frow[v], math.gcd(chunk, s_max))
            if swapped:
                return layer[0], fslot[v], 0, row
            return (layer[0], fslot[v], row) + (0,) * (array.ndim - 3)

        # a first row, not a block index: every dim is an element's then
        shape = (array.shape[2], chunk) if swapped else (
            (chunk,) + array.shape[3:])
        return pl.BlockSpec(
            (None, None) + tuple(pl.Element(n) for n in shape), at)

    def lane(x):
        return pl.BlockSpec((None,) + x.shape[1:], at_slot)

    m0 = m0[..., None]
    cache = [*keys] + [values] * (values is not None)
    interpret = jax.default_backend() != "tpu"
    if interpret:
        # the interpreter's loop copies every operand whole at every grid
        # step (timed on the host: a call's time grows with the layers in
        # the cache): it is handed the one layer the kernel reads
        cache = [jax.lax.dynamic_index_in_dim(x, layer, 0) for x in cache]
        layer = 0
    specs = [rows_of(x, swapped) for x, swapped in zip(
        cache, rows_last + (False,))]
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n_slots, n_heads, d_v), q[0].dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(schedule.visits,),
            in_specs=[lane(x) for x in q] + specs + [lane(m0), lane(acc0)],
            out_specs=pl.BlockSpec((None, n_heads, d_v), at_slot),
            scratch_shapes=[pltpu.VMEM((n_heads, 1), f32),
                            pltpu.VMEM((n_heads, 1), f32),
                            pltpu.VMEM((n_heads, d_v), f32)],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="decode_attention",
    )(schedule.slot, schedule.lo, schedule.fetch_slot, schedule.fetch_row,
      pos.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      *q, *cache, m0, acc0)
