"""The two state kernels ALONE at their cells' shapes, on the chip:
``ops/kda.kda_update`` over Kimi-Linear's leaf (6 layers x 96 slots x 32
heads x 128 x 128 float32) and ``ops/ssm.ssm_update`` over granite's (36
x 48 x 64 x 64 x 128), as ``decode_block`` calls them: the layers cycled
inside one ``fori_loop`` (the host's dispatch amortised), the leaf donated,
``live`` passed as the engine passes it. Beside each, the COPY ablation:
the same leaf through the same pipeline (tiles of ``tile_bytes``, the leaf
aliased, the layer and the live tiles' order prefetched) with a body that
moves the tile and does nothing else, which is the ceiling a state kernel
can reach on this chip (~78 % of 819 GB/s, PERF.md section 6).

    chiprun -- python3 tools/state_kernel_micro.py            # both kernels
    chiprun -- python3 tools/state_kernel_micro.py kda_update --live-share 0.5
    python3 tools/state_kernel_micro.py --tiny                # here: the walk

One JSON line a measurement (ms a call, GB/s of states read and written,
the share of the chip's peak) and the lot in
``chiprun_out/state_kernel_micro.json``. ``--tiny`` walks the same code at
a toy size through the Pallas interpreter and reports no rate: a time off
the chip is no device number. A tool: no cell and no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from benchmarks.common import peaks_for
from ray_tpu.ops.kda import _divisor_at_most, kda_update
from ray_tpu.ops.ssm import live_tiles_first, ssm_update, tile_at

F32, BF16 = jnp.float32, jnp.bfloat16

# name -> the leaf of the cell that runs the kernel (layers, slots, heads,
# rows, lanes): benchmarks/configs/kimi-linear-l8-e64-bf16-serve.json and
# granite4-h-micro-bf16-serve.json
CELL = {"kda_update": (6, 96, 32, 128, 128),
        "ssm_update": (36, 48, 64, 64, 128)}
TINY = {"kda_update": (2, 4, 2, 16, 16), "ssm_update": (2, 4, 4, 8, 16)}


def kda_inputs(key, slots, heads, dk, dv):
    """What ``decode_block`` hands ``kda_update`` beside the leaf: ``q``,
    ``k`` L2-normalised, a decay a channel in about 0.2-0.999."""
    ks = jax.random.split(key, 5)
    unit = lambda a: (a / jnp.linalg.norm(a, axis=-1, keepdims=True))
    q = unit(jax.random.normal(ks[0], (slots, heads, dk))).astype(BF16)
    k = unit(jax.random.normal(ks[1], (slots, heads, dk))).astype(BF16)
    v = jax.random.normal(ks[2], (slots, heads, dv), BF16)
    g = -jax.random.uniform(ks[3], (slots, heads, dk), F32, 0.001, 1.6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (slots, heads)))
    return q, k, v, g, beta


def ssm_inputs(key, slots, heads, p, n):
    ks = jax.random.split(key, 6)
    x = jax.random.normal(ks[0], (slots, heads, p), BF16)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (slots, heads)) - 3.0)
    a = -jax.random.uniform(ks[2], (heads,), F32, 1.0, 16.0)
    b = jax.random.normal(ks[3], (slots, 1, n), BF16)
    c = jax.random.normal(ks[4], (slots, 1, n), BF16)
    d = jax.random.normal(ks[5], (heads,))
    return x, dt, a, b, c, d


def copy_update(states, layer, live=None, *, tile_bytes=2 ** 20):
    """The ablation: layer ``layer`` of the leaf [layers, B, ...] moved
    tile by tile through the kernels' pipeline and written back where it
    lay, the leaf aliased; a tile is ``tile_bytes`` of one slot's rows,
    over slots where a slot is smaller. Returns (a row a slot, the leaf);
    a slot's rows are 8 or more."""
    n_layers, n_slots, lanes = states.shape[0], states.shape[1], states.shape[-1]
    flat = states.reshape(n_layers, n_slots, -1, lanes)
    rows = flat.shape[2]
    per = max(tile_bytes // (lanes * 4), 1)  # rows a tile
    tr = _divisor_at_most(rows, per)
    tb = _divisor_at_most(n_slots, per // tr)
    order, tiles = live_tiles_first(live, n_slots, tb)

    def kernel(_layer, *refs):
        s_ref, o_ref, new_ref = refs[len(order):]
        new_ref[...] = s_ref[...]
        o_ref[...] = s_ref[:, 0:8, :][:, None]

    def tile(i, j, layer, *order):
        return layer[0], tile_at(i, *order), j, 0

    interpret = jax.default_backend() != "tpu"
    o, new = pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n_slots, rows // tr, 8, lanes), F32),
                   jax.ShapeDtypeStruct(flat.shape, F32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(order),
            grid=(tiles, rows // tr),
            in_specs=[pl.BlockSpec((None, tb, tr, lanes), tile)],
            out_specs=[pl.BlockSpec((tb, 1, 8, lanes),
                                    lambda i, j, layer, *order:
                                    (tile_at(i, *order), j, 0, 0)),
                       pl.BlockSpec((None, tb, tr, lanes), tile)]),
        input_output_aliases={1 + len(order): 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 2),
        interpret=interpret,
        name="copy_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), *order, flat)
    return o[:, 0, 0], new.reshape(states.shape)


# name -> (the kernel, what it takes beside the leaf, which of those its
# output is fed back into: ``o`` into ``v``, ``y`` into ``x``)
KERNELS = {"kda_update": (kda_update, kda_inputs, 2),
           "ssm_update": (ssm_update, ssm_inputs, 0)}


def steps(name, update=None, tile_bytes=None):
    """``(make, step)`` for a kernel by name: ``make(key, leaf shape)``
    gives ``(leaf, carried, fixed)`` and ``step(leaf, layer, carried,
    fixed, live)`` the leaf stepped and the next ``carried`` (the kernel's
    output fed back into one of its inputs, so that no call of the loop is
    dead code). ``update`` replaces the repo's kernel (a scratch variant
    of the same signature); ``tile_bytes`` its default tile."""
    kernel, inputs, fed = KERNELS[name]
    fn = update or kernel
    extra = {} if tile_bytes is None else {"tile_bytes": tile_bytes}

    def make(key, shape):
        k0, k1 = jax.random.split(key)
        args = list(inputs(k1, *shape[1:]))
        carried = args.pop(fed)
        return jax.random.normal(k0, shape, F32) * 0.1, carried, tuple(args)

    def step(leaf, layer, carried, fixed, live):
        args = fixed[:fed] + (carried,) + fixed[fed:]
        out, leaf = fn(leaf, layer, *args, live, **extra)
        return leaf, (carried + 0.001 * out).astype(carried.dtype)
    return make, step


def copy_steps(tile_bytes=None):
    extra = {} if tile_bytes is None else {"tile_bytes": tile_bytes}

    def step(leaf, layer, carried, fixed, live):
        o, leaf = copy_update(leaf, layer, live, **extra)
        return leaf, carried + (0.001 * o.mean()).astype(carried.dtype)
    return step


def measure(make, step, shape, live_share=1.0, reps=60, rounds=3, seed=0):
    """``reps`` calls inside one compiled loop, layers cycled, the leaf
    donated; the best of ``rounds``. Returns ms a call, the seconds the
    first call took (the compile), and the bytes a call moves: the live
    slots' states of one layer, read and written."""
    leaf, carried, fixed = make(jax.random.key(seed), shape)
    n_slots = shape[1]
    n_live = max(int(round(live_share * n_slots)), 1)
    live = jnp.arange(n_slots) < n_live  # decode_block always passes one

    def many(leaf, carried, fixed, live):
        def body(i, c):
            return step(c[0], i % shape[0], c[1], fixed, live)
        return jax.lax.fori_loop(0, reps, body, (leaf, carried))

    loop = jax.jit(many, donate_argnums=(0,))
    t0 = time.perf_counter()
    leaf, carried = jax.block_until_ready(loop(leaf, carried, fixed, live))
    first_s = time.perf_counter() - t0
    ms = []
    for _ in range(rounds):
        t0 = time.perf_counter()
        leaf, carried = jax.block_until_ready(loop(leaf, carried, fixed, live))
        ms.append((time.perf_counter() - t0) / reps * 1e3)
    moved = 2 * 4 * n_live
    for n in shape[2:]:
        moved *= n
    return {"ms_a_call": min(ms), "ms_all": ms, "first_call_s": first_s,
            "bytes_a_call": moved, "live_slots": n_live}


def report(row, on_chip: bool):
    """A measurement's line: the rate and its share of the peak only where
    the device is the chip."""
    if on_chip:
        kind = jax.devices()[0].device_kind
        rate = row["bytes_a_call"] / (row["ms_a_call"] * 1e-3)
        row.update(gb_per_s=rate / 1e9, share_of_peak=100 * rate
                   / peaks_for(kind)["hbm_bytes_per_s"])
    else:  # a time off the chip is no device number
        for key in ("ms_a_call", "ms_all", "first_call_s"):
            row.pop(key)
    row["device"] = jax.devices()[0].device_kind
    print(json.dumps(row), flush=True)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("kernels", nargs="*", default=[],
                    help="kda_update ssm_update (default: both)")
    ap.add_argument("--tiny", action="store_true",
                    help="a toy leaf through the Pallas interpreter")
    ap.add_argument("--live-share", type=float, nargs="*", default=[1.0])
    ap.add_argument("--tile-bytes", type=int, nargs="*", default=[None])
    ap.add_argument("--reps", type=int, default=60)
    args = ap.parse_args(argv)
    on_chip = jax.default_backend() == "tpu"
    if not (on_chip or args.tiny):
        print("no chip here: --tiny walks the code, rates come from chiprun",
              file=sys.stderr)
        return 2
    shapes, reps = (TINY, 2) if args.tiny else (CELL, args.reps)
    rows = []
    for name in args.kernels or list(KERNELS):
        for tile in args.tile_bytes:
            for share in args.live_share:
                make, step = steps(name, tile_bytes=tile)
                for what, fn in ((name, step), ("copy", copy_steps(tile))):
                    row = measure(make, fn, shapes[name], share,
                                  reps=reps, rounds=1 if args.tiny else 3)
                    rows.append(report(
                        {"kernel": what, "leaf_of": name, "tile_bytes": tile,
                         "leaf": list(shapes[name]), **row}, on_chip))
    if on_chip:
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/state_kernel_micro.json", "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
