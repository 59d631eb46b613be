"""``ops/kda.kda_chunked`` ALONE at the shapes a Kimi admission calls it
with, on the chip: one sequence, 32 heads of 128 x 128, chunks of 64, bf16
operands and float32 log-decays, the prompt's length a traced scalar as
``generation._prefill_kda`` passes it (``valid = arange < prompt_len``).

    chiprun -- python3 tools/kda_chunk_micro.py              # the four lengths
    chiprun -- python3 tools/kda_chunk_micro.py --tokens 1792 --filled 0.837
    python3 tools/kda_chunk_micro.py --tiny                   # here: the walk

A layer-call's time is the host's clock over ``--calls`` calls dispatched
back to back and waited out once (a call outlasts its dispatch, so the
device never waits), best of three. Beside it what the call must move (q,
k, v, o in bf16, g in float32: 12 B x 128 a head-token) over that time as a
share of the chip's bandwidth. ``--check`` compares the form at 1,024
tokens with ``kda_step`` token by token in float32 (the largest error of
``o`` and of the end state over the largest value). One JSON line a
measurement and the lot in ``chiprun_out/kda_chunk_micro.json``. ``--tiny``
walks the same code at a toy size through the Pallas interpreter and
reports no rate: a time off the chip is no device number. A tool: no cell
and no metric reads it; it runs from any tree whose ``kda_chunked`` has
this signature (``PYTHONPATH=<tree>``).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.common import peaks_for
from ray_tpu.ops.kda import kda_chunked, kda_step

F32, BF16 = jnp.float32, jnp.bfloat16
HEADS, WIDTH, CHUNK = 32, 128, 64  # kimi-linear-l8-e64-bf16-serve.json
TOKENS = (1024, 1792, 4096, 8192)


def inputs(seed, tokens, heads, width):
    """What a "kda" layer hands the chunked form: unit keys, queries over
    sqrt(width), a decay a channel in about 0.2-0.999, a write strength in
    0-1."""
    ks = jax.random.split(jax.random.key(seed), 5)
    shape = (1, tokens, heads, width)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = (unit(jax.random.normal(ks[0], shape)) * width ** -0.5).astype(BF16)
    k = unit(jax.random.normal(ks[1], shape)).astype(BF16)
    v = jax.random.normal(ks[2], shape, BF16)
    g = -jax.random.uniform(ks[3], shape, F32, 0.001, 1.6)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:-1]))
    return q, k, v, g, beta


@functools.partial(jax.jit, static_argnames="chunk")
def layer_call(q, k, v, g, beta, prompt_len, chunk=CHUNK):
    valid = (jnp.arange(q.shape[1]) < prompt_len)[None]
    return kda_chunked(q, k, v, g, beta, chunk, valid=valid)


@jax.jit
def token_by_token(q, k, v, g, beta):
    q, k, v = (a.astype(F32) for a in (q, k, v))

    def step(state, x):
        o, state = kda_step(state, *x)
        return state, o

    first = jnp.zeros((1, q.shape[2], q.shape[3], v.shape[3]), F32)
    last, o = lax.scan(step, first, tuple(
        jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tokens", type=int, nargs="*", default=list(TOKENS))
    p.add_argument("--filled", type=float, nargs="*", default=[1.0, 0.837],
                   help="the prompt's length over the bucket's")
    p.add_argument("--calls", type=int, default=20)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--check", action="store_true")
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args()
    heads, width, chunk = (2, 16, 8) if args.tiny else (HEADS, WIDTH, CHUNK)
    tokens = [24, 40] if args.tiny else args.tokens
    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit("a rate needs the chip; --tiny walks the code here")
    rows = []
    for n in tokens:
        x = inputs(args.seed, n, heads, width)
        for filled in args.filled:
            prompt_len = jnp.int32(max(int(n * filled), 1))
            jax.block_until_ready(layer_call(*x, prompt_len, chunk))
            best = float("inf")
            for _ in range(3):
                t = time.perf_counter()
                out = [layer_call(*x, prompt_len, chunk)
                       for _ in range(args.calls)]
                jax.block_until_ready(out)
                best = min(best, (time.perf_counter() - t) / args.calls)
            row = {"tokens": n, "prompt_len": int(prompt_len),
                   "device": dev.device_kind}
            if not args.tiny:
                moved = 12 * width * heads * n
                row.update(ms_a_call=1e3 * best, bytes=moved,
                           hbm_share=100 * moved / best
                           / peaks_for(dev.device_kind)["hbm_bytes_per_s"])
            rows.append(row)
            print(json.dumps(row), flush=True)
    if args.check or args.tiny:
        n = tokens[0]
        x = inputs(args.seed + 1, n, heads, width)
        want_o, want = token_by_token(*x)
        for prompt_len in (n, n - n // 3):
            got_o, got = layer_call(*x, jnp.int32(prompt_len), chunk)
            if prompt_len < n:
                want_o, want = token_by_token(*(a[:, :prompt_len] for a in x))
            err = lambda a, b: float(jnp.abs(a.astype(F32) - b).max()
                                     / jnp.abs(b).max())
            row = {"check_tokens": n, "prompt_len": prompt_len,
                   "o_err": err(got_o[:, :prompt_len], want_o),
                   "state_err": err(got, want)}
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/kda_chunk_micro.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
