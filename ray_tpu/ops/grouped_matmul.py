"""Grouped matrix product for dropless routed experts: a Pallas TPU kernel.

``rows`` [m, k] are sorted by group (expert); group g owns the rows
``offsets[g]:offsets[g + 1]`` and multiplies them by its own [k, n] matrix.
A decode step has a few rows a group (32 lanes x 4 choices over ~54 of 64
experts), so the product is a STREAM of the touched experts' weights and
what matters is the piece they are read in: the whole contraction by
several hundred columns, megabytes a DMA, where the compiler's kernel for
``lax.ragged_dot`` reads 512 x 512.

The schedule (``group_schedule``) lists the (non-empty group, row tile)
pairs in the sorted rows' order and is scalar-prefetched; the grid is
(column tiles, visits) with the number of visits dynamic, so an empty
group costs nothing. Consecutive visits of one group (a group that
straddles row tiles) keep its weight tile in fast memory: the pipeline
fetches a block only when its index changes. The weights are read where
they lie, [layers, E, k, n] indexed by (layer, group, 0, column tile):
nothing slices a layer out of the stack.

Tiles come from the shapes alone (``_row_tile``, ``_column_tile``). Off
the TPU the kernel runs in the Pallas interpreter.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# One weight buffer (all the matrices a visit reads, one column tile): two
# of them in flight, beside the rows, under the fast memory a v5e has.
_WEIGHT_TILE_BYTES = 16 * 2 ** 20
_VMEM_LIMIT_BYTES = 64 * 2 ** 20


class Schedule(NamedTuple):
    group_ids: jax.Array  # [V] group of visit v (V = row tiles + groups - 1)
    tile_ids: jax.Array  # [V] row tile of visit v
    offsets: jax.Array  # [groups + 1] first row of each group
    visits: jax.Array  # () the visits there are: the others are padding


def _row_tile(m: int) -> int:
    """128 rows fill the MXU's pass over a weight tile and cost what one
    row does; more rows a tile only add to the rows a straddling group
    pays for twice. Fewer than 128 rows in all: one tile of them."""
    return 128 if m >= 128 else -(-m // 16) * 16


def _column_tile(k: int, n: int, itemsize: int, n_weights: int) -> int:
    """The widest slice of the columns, in whole lanes of 128, that
    divides ``n`` and keeps one visit's weights under the buffer."""
    if n % 128:
        return n
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and k * t * itemsize * n_weights
            <= _WEIGHT_TILE_BYTES]
    return max(fits, default=128)


def group_schedule(sizes: jax.Array, m: int) -> Schedule:
    """The kernel's visits for ``m`` sorted rows in groups of ``sizes``
    (int32 [groups]; rows behind the last group belong to none)."""
    tm = _row_tile(m)
    tiles_m = -(-m // tm)
    n_groups = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    total = tiles_m + n_groups - 1
    group_ids = jnp.repeat(jnp.arange(n_groups, dtype=jnp.int32), spans,
                           total_repeat_length=total)
    before = jnp.cumsum(spans) - spans  # visits ahead of each group's
    tile_ids = first[group_ids] + jnp.arange(total) - before[group_ids]
    # the padding stays a valid block index, whoever looks ahead at it
    tile_ids = jnp.clip(tile_ids, 0, tiles_m - 1).astype(jnp.int32)
    return Schedule(group_ids, tile_ids, offsets.astype(jnp.int32),
                    spans.sum().astype(jnp.int32))


def grouped_matmul(
    rows: jax.Array,  # [m, k], sorted by group
    weights: Sequence[jax.Array],  # each [layers, groups, k, n]
    schedule: Schedule,  # group_schedule(sizes, m)
    *,
    layer=0,
    act=None,
) -> jax.Array:
    """``rows`` of group g times ``weights[0][layer, g]``: [m, n] in
    ``rows.dtype``, accumulated in float32; ``act`` of it where one is
    given. With two weights the result is ``act(rows @ weights[0]) * (rows
    @ weights[1])``, both products and the activation in float32 in one
    pass over the rows. Rows behind the last group hold nothing defined."""
    m, k = rows.shape
    n = weights[0].shape[-1]
    tm = _row_tile(m)
    tn = _column_tile(k, n, weights[0].dtype.itemsize, len(weights))
    m_pad = -(-m // tm) * tm
    if m_pad != m:
        rows = jnp.pad(rows, ((0, m_pad - m), (0, 0)))

    def kernel(gids, tids, offs, _layer, x_ref, *refs):
        o_ref = refs[-1]
        v = pl.program_id(1)
        g = gids[v]
        row = tids[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offs[g]) & (row < offs[g + 1])
        x = x_ref[...]
        y = [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
             for w in refs[:-1]]
        if act is not None:
            y[0] = act(y[0])
        y = y[0] * y[1] if len(y) == 2 else y[0]
        o_ref[...] = jnp.where(mine, y.astype(o_ref.dtype), o_ref[...])

    def at_rows(j, v, gids, tids, offs, layer):
        return tids[v], 0

    def at_weights(j, v, gids, tids, offs, layer):
        return layer[0], gids[v], 0, j

    def at_out(j, v, gids, tids, offs, layer):
        return tids[v], j

    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m_pad, n), rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, schedule.visits),
            in_specs=[pl.BlockSpec((tm, k), at_rows)] + [
                pl.BlockSpec((None, None, k, tn), at_weights)
                for _ in weights],
            out_specs=pl.BlockSpec((tm, tn), at_out),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() != "tpu",
        name="grouped_matmul",
    )(schedule.group_ids, schedule.tile_ids, schedule.offsets,
      jnp.asarray(layer, jnp.int32).reshape(1), rows, *weights)
    return out[:m]
