"""Selective state-space recurrence with a scalar decay a head (the
"SSD" layer: a state ``H`` [P, N] a head, ``H_t = exp(dt_t A) H_{t-1} +
dt_t x_t B_t^T``, ``y_t = H_t C_t + D x_t``), in the two forms a server
needs:

- ``ssm_chunked``: a whole sequence in chunks (prefill, the uncached
  forward). Inside a chunk the outputs are one masked product of decays
  (``(C B^T) * L`` against ``dt x``: matrix products, which is what the
  chip is fast at); between chunks the state is carried by a ``lax.scan``
  over the chunks. Linear in the sequence, and the same numbers as the
  recurrence in another order of sums.
- ``ssm_step``: the recurrence once, for a decode step's one token a lane,
  in the plain form: what the tests hold the other two to.
- ``ssm_update``: the same step as the served path runs it, a Pallas TPU
  kernel over the slots' WHOLE stacked state leaf [layers, B, H, P, N],
  aliased to its output, with the layer a prefetched scalar: each tile of
  states is read once, written back to its own place, and ``y`` is formed
  from the tile while it is in fast memory. XLA's own code for
  ``ssm_step`` read every new state a second time for ``y`` (PERF.md,
  PR 36). Told which lanes are live it visits their tiles alone
  (``live_tiles_first``: shared with ``ops/kda.kda_update``).

Shapes: ``x`` [B, S, H, P] (``ssm_step``: no S), ``dt`` [B, S, H] (after
its softplus), ``A`` [H] (negative), ``B`` and ``C`` [B, S, G, N] with G
groups of H / G heads sharing one B and C, ``D`` [H]; a state is
[B, H, P, N] in float32. Decays, cumulative sums and every accumulation
are float32; the operands of the large products stay in ``x``'s type
(bf16 on the chip, float32 in the tests). The chunked scan is XLA's own
fusions (PERF.md has their share of a prefill).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

# What one grid step of ``ssm_update`` reads of the states (and writes
# back): the step's fixed cost hides under the tile's DMA, and four tiles
# (in and out, double-buffered) sit well inside fast memory.
_TILE_BYTES = 2 ** 20


def ssm_chunked(x, dt, A, B, C, D, chunk: int,
                state0: Optional[jax.Array] = None,
                valid: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """The recurrence over a whole sequence; returns ``(y [B,S,H,P] in
    x's type, the state after the last token [B,H,P,N] float32)``.

    ``state0``: the state before the first token (zeros where None).
    ``valid`` [B, S] bool marks the tokens that count: where it is false
    ``dt`` is taken as 0, so the decay is 1 and nothing is added, and the
    state after a padded bucket IS the state after its last valid token
    (the outputs at such positions are junk nobody reads). A sequence
    that ``chunk`` does not divide is padded the same way."""
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    r = h // g
    if valid is not None:
        dt = jnp.where(valid[..., None], dt, 0.0)
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                       for a in (x, dt, B, C))
    nc = (s + pad) // chunk
    mm = x.dtype  # the large products' operands
    dt = dt.astype(F32).reshape(b, nc, chunk, g, r)
    xh = x.reshape(b, nc, chunk, g, r, p)
    xdt = (xh.astype(F32) * dt[..., None]).astype(mm)
    Bc = B.reshape(b, nc, chunk, g, n)
    Cc = C.reshape(b, nc, chunk, g, n)
    # log-decay from the chunk's start up to and including each token
    cums = jnp.cumsum(dt * A.astype(F32).reshape(g, r), axis=2)  # [b,c,l,g,r]

    # inside a chunk: y_l += sum_{s<=l} (C_l . B_s) exp(cums_l - cums_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=F32)
    seg = (cums.transpose(0, 1, 3, 4, 2)[..., :, None]
           - cums.transpose(0, 1, 3, 4, 2)[..., None, :])  # [b,c,g,r,l,s]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp",
                   (cb[:, :, :, None] * decay).astype(mm), xdt,
                   preferred_element_type=F32)

    # what each chunk adds to the state at its own end
    to_end = jnp.exp(cums[:, :, -1:] - cums)  # [b,c,l,g,r]
    added = jnp.einsum(
        "bcsgn,bcsgrp->bcgrpn", Bc,
        (xdt.astype(F32) * to_end[..., None]).astype(mm),
        preferred_element_type=F32)
    whole = jnp.exp(cums[:, :, -1])  # [b,c,g,r]: a chunk's total decay

    def carry_state(state, per_chunk):
        keep, add = per_chunk
        return keep[..., None, None] * state + add, state

    first = (jnp.zeros((b, g, r, p, n), F32) if state0 is None
             else state0.astype(F32).reshape(b, g, r, p, n))
    last, before = lax.scan(
        carry_state, first,
        (whole.transpose(1, 0, 2, 3), added.transpose(1, 0, 2, 3, 4, 5)))
    before = before.transpose(1, 0, 2, 3, 4, 5)  # [b,c,g,r,p,n]: at chunk start

    # what the state a chunk starts from gives each of its tokens
    y = y + jnp.einsum("bclgn,bcgrpn->bclgrp", Cc, before.astype(mm),
                       preferred_element_type=F32) * jnp.exp(cums)[..., None]
    y = y + xh.astype(F32) * D.astype(F32).reshape(g, r)[:, :, None]
    y = y.reshape(b, nc * chunk, h, p)[:, :s]
    return y.astype(x.dtype), last.reshape(b, h, p, n)


def ssm_step(state, x, dt, A, B, C, D) -> Tuple[jax.Array, jax.Array]:
    """One token a lane: ``state`` [B,H,P,N] float32, ``x`` [B,H,P], ``dt``
    [B,H], ``B`` and ``C`` [B,G,N]. Returns ``(y [B,H,P] in x's type, the
    new state)``. Elementwise over the state plus one reduction over N.
    The plain form: compiled by XLA it is two fusions, the update and a
    reduce that reads the new state AGAIN for ``y``, so around a layer
    taken out of a stacked leaf it moves every state three times; the
    served path runs ``ssm_update``, which moves it twice."""
    b, h, p = x.shape
    g, n = B.shape[1:]
    r = h // g
    dt = dt.astype(F32).reshape(b, g, r)
    xg = x.astype(F32).reshape(b, g, r, p)
    keep = jnp.exp(dt * A.astype(F32).reshape(g, r))
    new = (keep[..., None, None] * state.reshape(b, g, r, p, n)
           + (dt[..., None] * xg)[..., None]
           * B.astype(F32)[:, :, None, None, :])
    y = (new * C.astype(F32)[:, :, None, None, :]).sum(-1)
    y = y + xg * D.astype(F32).reshape(g, r)[:, :, None]
    return y.reshape(b, h, p).astype(x.dtype), new.reshape(b, h, p, n)


def live_tiles_first(live, n_slots: int, tb: int):
    """The schedule of a state kernel whose slot axis visits LIVE lanes
    only: ``live`` [B] bool or None, ``tb`` slots a tile. Returns
    ``(prefetch, count)``: ``prefetch`` holds ``order`` [tiles] int32, the
    tiles that hold a live slot first, in their own order, and ``count``
    is how many they are. The kernel's grid has ``count`` steps along its
    slot axis (a grid bound read on the device) and step ``i`` names tile
    ``order[i]`` (scalar-prefetched: ``tile_at``), so a tile none of
    whose slots is live is neither read nor written and its body does not
    run; with no lane live the kernel makes no step at all. Without
    ``live`` nothing is prefetched and ``count`` is every tile."""
    if live is None:
        return (), pl.cdiv(n_slots, tb)
    tiles = jnp.pad(live, (0, -n_slots % tb)).reshape(-1, tb).any(1)
    before = jnp.cumsum(tiles)  # live tiles up to and including each
    # the i-th live tile is the first with i + 1 live up to it: as many
    # tiles lie before it as have i or fewer (no sort on the chip)
    order = (before[None, :] <= jnp.arange(tiles.shape[0])[:, None]).sum(1)
    order = jnp.minimum(order, tiles.shape[0] - 1).astype(jnp.int32)
    return (order,), before[-1].astype(jnp.int32)


def tile_at(i, order=None):
    """The tile that step ``i`` of a state kernel's slot axis names:
    ``order[i]`` under ``live_tiles_first``'s schedule (an index map's or
    a kernel's prefetched ref), ``i`` itself without one."""
    return i if order is None else order[i]


def ssm_update(states, layer, x, dt, A, B, C, D, live=None, *,
               tile_bytes: int = _TILE_BYTES) -> Tuple[jax.Array, jax.Array]:
    """``ssm_step`` on layer ``layer`` of the stacked states [layers, B,
    H, P, N] float32, in place: returns ``(y [B,H,P] in x's type, the
    whole leaf with that layer's states stepped)``; ``x``, ``dt``, ``A``,
    ``B``, ``C``, ``D`` as ``ssm_step`` takes them. One Pallas kernel: the
    leaf is aliased to the output and a tile is indexed by (layer, slots,
    group, rows) where it lies, so nothing slices a layer out and every
    other layer's bytes are not touched. The same float32 products and
    sum as ``ssm_step``.

    ``live`` [B] bool names the lanes that count (None: all of them). A
    lane outside it is PARKED: its state is bit for bit what it was and
    its ``y`` is zeros. A tile none of whose slots is live is neither
    read nor written and its body does not run (``live_tiles_first``); a
    parked slot inside a live tile goes through with decay 1 and nothing
    added.

    A group's heads and their P rows lie flat as row blocks of S = P x
    (heads a block) rows, 128 where the shapes allow, so that ``dt x``
    goes in and ``y`` comes out in rows a whole number of lanes wide; a
    tile is ``tile_bytes`` of whole row blocks of one group, over slots
    where a slot's group is smaller. The decay of a (slot, head) is a
    scalar in SMEM; ``dt x`` is turned from lanes to sublanes and ``y``
    back in the kernel, 1/N of the data each. Off the TPU the kernel runs
    in the Pallas interpreter, handed the one layer it touches (the
    interpreter copies every operand whole at every grid step)."""
    n_slots, h, p = x.shape
    g, n = B.shape[1:]
    hg = h // g  # heads a group
    hs = math.gcd(hg, max(128 // p, 1))  # heads a row block
    s, qg = hs * p, hg // hs  # rows a block, blocks a group
    per = max(tile_bytes // (s * n * 4), 1)  # blocks a tile
    tq = qg if qg <= per else max(per // 8 * 8, 8)
    tb = min(max(per // tq, 1), n_slots)
    order, tiles = live_tiles_first(live, n_slots, tb)

    dt = dt.astype(F32)
    keep = jnp.exp(dt * A.astype(F32))  # [B,H]
    dtx = (dt[..., None] * x.astype(F32)).reshape(n_slots, g, qg, s)
    per_group = [a.astype(F32).reshape(n_slots, g, 1, n) for a in (B, C)]
    if live is not None:  # a parked slot inside a live tile
        keep = jnp.where(live[:, None], keep, 1.0)
        dtx = jnp.where(live[:, None, None, None], dtx, 0.0)

    def kernel(_layer, keep, *refs):
        *order, dtx_ref, b_ref, c_ref, h_ref, y_ref, o_ref = refs
        slot0, grp, blk0 = (tile_at(pl.program_id(0), *order) * tb,
                            pl.program_id(1), pl.program_id(2) * tq)
        for b in range(tb):
            # a tile past the last slot or block: its results are dropped
            slot = jnp.minimum(slot0 + b, n_slots - 1)
            for q in range(tq):
                head = grp * hg + jnp.minimum(blk0 + q, qg - 1) * hs
                kept = jnp.concatenate(
                    [keep[slot * h + head + e] * h_ref[b, q, e * p:(e + 1) * p]
                     for e in range(hs)], 0)  # [S, N]
                new = kept + dtx_ref[b, q:q + 1].reshape(s, 1) * b_ref[b]
                o_ref[b, q] = new
                y_ref[b, q:q + 1] = (new * c_ref[b]).sum(-1).reshape(1, s)

    interpret = jax.default_backend() != "tpu"
    stack, at = states, layer
    if interpret:
        stack, at = lax.dynamic_index_in_dim(states, layer, 0), 0
    n_layers = stack.shape[0]

    def tile(i, grp, j, layer, keep, *order):
        return layer[0], tile_at(i, *order), grp, j, 0, 0

    def rows(i, grp, j, layer, keep, *order):
        return tile_at(i, *order), grp, j, 0

    def group(i, grp, j, layer, keep, *order):
        return tile_at(i, *order), grp, 0, 0

    state_spec = pl.BlockSpec((None, tb, None, tq, s, n), tile)
    rows_spec = pl.BlockSpec((tb, None, tq, s), rows)
    group_spec = pl.BlockSpec((tb, None, 1, n), group)
    y, new = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n_slots, g, qg, s), F32),
                   jax.ShapeDtypeStruct((n_layers, n_slots, g, qg, s, n),
                                        F32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2 + len(order),
            grid=(tiles, g, pl.cdiv(qg, tq)),
            in_specs=[rows_spec, group_spec, group_spec, state_spec],
            out_specs=[rows_spec, state_spec],
        ),
        input_output_aliases={5 + len(order): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3),
        interpret=interpret,
        name="ssm_update",
    )(jnp.asarray(at, jnp.int32).reshape(1), keep.reshape(-1), *order, dtx,
      *per_group, stack.reshape(n_layers, n_slots, g, qg, s, n))
    new = new.reshape(stack.shape)
    if interpret:
        new = lax.dynamic_update_index_in_dim(states, new[0], layer, 0)
    y = y.reshape(n_slots, h, p) + x.astype(F32) * D.astype(F32)[:, None]
    if live is not None:  # an unvisited tile's y is whatever the buffer held
        y = jnp.where(live[:, None, None], y, 0.0)
    return y.astype(x.dtype), new


def causal_conv(x, w, bias, tail: Optional[jax.Array] = None):
    """Depthwise causal convolution over a sequence: ``x`` [B,S,C], ``w``
    [K,C] (tap K-1 is the token's own), ``bias`` [C] or None; the K-1 inputs
    before the first token are ``tail`` [B,K-1,C] (zeros where None).
    Returns the convolved [B,S,C] in x's type (float32 sums)."""
    k = w.shape[0]
    s = x.shape[1]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    window = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    out = 0.0 if bias is None else bias.astype(F32)
    for j in range(k):
        out = out + window[:, j:j + s].astype(F32) * w[j].astype(F32)
    return out.astype(x.dtype)
