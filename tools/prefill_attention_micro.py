"""``ops/attention.blocked_causal_attention`` ALONE at the shapes an
admission calls it with, on the chip: one sequence, bf16 operands, MiMo's
full layers (64 query heads over 4 KV heads, keys 192 wide, values 128) and
Kimi's latent layers as ``mla_expand`` hands them over (32 heads of their
own), the prompt's length a traced scalar as ``generation._prefill_attn``
passes it.

    chiprun -- python3 tools/prefill_attention_micro.py --check   # the table
    chiprun -- python3 tools/prefill_attention_micro.py --dense   # one product
    chiprun -- python3 tools/prefill_attention_micro.py --chosen  # under a mask
    chiprun -- python3 tools/prefill_attention_micro.py --slot    # GPT-J's buckets
    chiprun -- python3 tools/prefill_attention_micro.py --eva     # EvaByte's windows
    python3 tools/prefill_attention_micro.py --tiny                # here

A layer-call's time is the host's clock over ``--calls`` calls dispatched
back to back and waited out once (a call outlasts its dispatch), best of
three. Beside it two rates: the operations of the TILES THE FORM COMPUTES
(``2 (D + Dv)`` a head, query and row of every block it multiplies) and of
the CAUSAL WORK the prompt needs (rows s <= t < length alone), each over
that time, against the chip's peak. ``--dense`` times ``causal_attention``
(every score of the bucket in one product: the form a prefill under
``ops/attention.PREFILL_SCORE_BYTES`` takes) at 1,024 and 2,048 tokens.
``--check`` compares the form at 1,024 tokens with ``causal_attention`` in
float32 (largest error over the largest value; rows past the length must be
zeros where the form takes one). One JSON line a measurement and the lot in
``chiprun_out/prefill_attention_micro.json``. ``--chosen`` times the kernel under a
choice's mask instead, at GLM-5.2's group of 16 heads (keys and values 256
wide, as ``generation._prefill_attn_chosen`` calls it): a random causal mask
of about 2,048 rows a query, buckets of 8,192 and 12,288, the second also
with a prompt of 8,600; beside it the kernel without the mask, and from a
tree that still has ``generation._attend_masked`` that tile loop in its
place. ``--slot`` is the table behind ``ops/attention.PREFILL_SCORE_BYTES``
for heads of their own at short buckets: GPT-J's geometry (16 heads, keys
and values 256 wide) at its buckets of 64-1,024 tokens with the prompts its
cells send, the kernel with each ``--blocks`` pair (queries x rows, put in
``prefill_blocks``' place for the call; ``rule``: what the tree itself
gives), beside ``slot_dense``, this tool's own copy of the form an
admission took until PR 59: the bucket's queries against all 1,024 rows of
the slot in one product (whose float32 scores, 64 MiB, the compiler keeps
in fast memory: PERF.md, section 6, PR 58 / 59), and ``one_product``,
``causal_attention`` over the prompt alone, the form it takes since. There a call is one of 28 in
one program, each one's output the next one's queries: a call of 0.1 ms is
under a dispatch of its own. ``--eva`` is EvaByte's
admission (32 heads of their own, 128 wide, windows of 2,048 in chunks of
16; 4,096 / 8,192 / 24,576 bytes, each whole and with a prompt of 0.85 of
it): ``ops/eva.eva_attention`` (its kernel: a window's queries over the
closed windows' summaries and the window's rows up to the diagonal;
``--blocks`` pairs here too, ``QxKxP`` with a block of P summaries) beside ``eva_blocks_of_256``, this tool's own copy
of the form it took until PR 65 (blocks of 256 queries against their whole
window and every summary of the bucket, in ``jnp``), with the (query, row
or summary) pairs each form multiplies a head against the pairs the prompt
needs. ``--tiny`` walks the same
code at a toy size through the Pallas interpreter and reports no rate: a
time off the chip is no device number. A tool: no cell and no metric reads
it. It runs from the parent's tree too (``PYTHONPATH=<tree>``, run from
that tree's root): where the function takes no ``length`` it is the tile
loop, which computes 1,024 x 1,024 tiles up to the diagonal over the whole
bucket.
"""

from __future__ import annotations

import argparse
import functools
import inspect
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.common import peaks_for
from ray_tpu.ops import attention
from ray_tpu.ops.attention import blocked_causal_attention, causal_attention

F32, BF16 = jnp.float32, jnp.bfloat16
# heads, KV heads, key width, value width; the share of a bucket a cell's
# mean prompt fills (codeagent-saturated.json, longreason-saturated.json)
GEOMETRIES = {"mimo": (64, 4, 192, 128, 0.913),
              "kimi": (32, 32, 192, 128, 0.837)}
TOKENS = (1024, 4096, 5120, 8192, 16384)
DENSE_TOKENS = (1024, 2048)  # where a prefill goes as one product
TAKES_LENGTH = "length" in inspect.signature(
    blocked_causal_attention).parameters


def inputs(seed, tokens, heads, kv_heads, d, dv, dtype=BF16):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (1, tokens, heads, d), dtype),
            jax.random.normal(ks[1], (1, tokens, kv_heads, d), dtype),
            jax.random.normal(ks[2], (1, tokens, kv_heads, dv), dtype))


@jax.jit
def layer_call(q, k, v, prompt_len):
    if TAKES_LENGTH:
        return blocked_causal_attention(q, k, v, prompt_len)
    return blocked_causal_attention(q, k, v)


def pairs_computed(tokens, prompt_len, heads_a_kv_head):
    """(query, row) pairs a head multiplies: the kernel's blocks of queries
    up to the prompt's last, each against its blocks of rows up to the
    diagonal's; the tile loop's 1,024 tiles over the whole bucket."""
    if not TAKES_LENGTH:
        blk = attention.block_of(tokens, 1024)
        n = tokens // blk
        return blk * blk * n * (n + 1) // 2
    bq, bk = attention.prefill_blocks(tokens, heads_a_kv_head, 1024)
    return sum(bq * (i * bq // bk + 1) * bk
               for i in range(-(-prompt_len // bq)))


# GLM-5.2's prefill under a choice: heads of a group, key and value width,
# rows a query attends, (bucket, prompt) pairs
CHOSEN = (16, 256, 256, 2048, ((8192, 8192), (12288, 8600), (12288, 12288)))
TAKES_MASK = "mask" in inspect.signature(blocked_causal_attention).parameters


def random_choice(seed, tokens, topk):
    """A causal mask [tokens, tokens] (bool): every row s <= t where t <
    ``topk``, else each with chance topk / (t + 1), and the query's own."""
    t = jnp.arange(tokens)[:, None]
    s = jnp.arange(tokens)[None, :]
    u = jax.random.uniform(jax.random.key(seed), (tokens, tokens))
    return (s == t) | ((s < t) & (u * (t + 1) < topk))


def chosen(args, dev, say):
    """The attention under a choice, one group of heads a call."""
    from ray_tpu.models import generation as gen

    heads, d, dv, topk, sizes = CHOSEN
    if args.tiny:
        heads, d, dv, topk, sizes = 2, 24, 16, 16, ((48, 48), (80, 50))
    flop = 2 * (d + dv) * heads
    peak = None if args.tiny else peaks_for(dev.device_kind)["flops_bf16"]
    forms = {}
    if TAKES_MASK:
        forms["kernel_masked"] = jax.jit(
            lambda q, k, v, n, m: blocked_causal_attention(
                q, k, v, n, mask=m))
        forms["kernel"] = lambda q, k, v, n, m: layer_call(q, k, v, n)
    if hasattr(gen, "_attend_masked"):
        forms["tiles_masked"] = jax.jit(
            lambda q, k, v, n, m: gen._attend_masked(q[0], k[0], v[0], m))
    for tokens, length in sizes:
        x = inputs(args.seed, tokens, heads, heads, d, dv)
        mask = random_choice(args.seed, tokens, topk)
        for form, fn in forms.items():
            m = mask if form == "tiles_masked" else mask.astype(jnp.int8)
            best = timed(fn, *x, jnp.int32(length), m,
                         calls=1 if args.tiny else args.calls)
            row = {"geometry": "glm52", "form": form, "tokens": tokens,
                   "prompt_len": length, "device": dev.device_kind}
            if not args.tiny:
                whole = attention.block_of(tokens, 1024)
                pairs = (whole * whole * (tokens // whole)
                         * (tokens // whole + 1) // 2
                         if form == "tiles_masked" else
                         pairs_computed(tokens, length, 1))
                row.update(ms_a_call=1e3 * best,
                           tflops_computed=flop * pairs / best / 1e12,
                           peak_share_computed=100 * flop * pairs / best / peak)
            say(row)
    n, length = (48, 40) if args.tiny else (2048, 1500)
    x = inputs(args.seed + 1, n, heads, heads, d, dv)
    mask = random_choice(args.seed + 1, n, topk // 4)
    q, k, v = (a.astype(F32) for a in x)
    with jax.default_matmul_precision("highest"):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
        want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
            jnp.where(mask, scores, -jnp.inf), -1), v)
    for form, fn in forms.items():
        if form == "kernel":
            continue
        m = mask if form == "tiles_masked" else mask.astype(jnp.int8)
        got = fn(*x, jnp.int32(length), m).astype(F32).reshape(want.shape)
        upto = n if form == "tiles_masked" else length
        row = {"geometry": "glm52", "form": form, "check_tokens": n,
               "prompt_len": length,
               "err": float(jnp.abs(got - want)[:, :upto].max()
                            / jnp.abs(want).max())}
        if form == "kernel_masked":
            row["past_length_all_zero"] = not bool(got[:, length:].any())
        say(row)


# GPT-J's admission: heads (each its own KV head), key and value width, the
# slot's rows, (bucket, prompt) pairs of the chat and the document cells
SLOT = (16, 256, 256, 1024,
        ((64, 40), (128, 120), (256, 120), (256, 200), (512, 400),
         (1024, 640), (1024, 800), (1024, 960), (1024, 1024)))


SLOT_LAYERS = 28


def slot_dense(q, ck, cv, prompt_len):
    """The prompt's queries q [1,S,H,D] against ALL rows of the slot, ck /
    cv [1,S_max,H,D], under the causal mask and the rows the prompt fills:
    one product, one softmax (what ``generation._attend_prefill`` was)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, ck,
                        preferred_element_type=F32) * q.shape[-1] ** -0.5
    rows = jnp.arange(ck.shape[1])
    seen = (jnp.arange(q.shape[1])[:, None] >= rows) & (rows < prompt_len)
    probs = jax.nn.softmax(jnp.where(seen, scores, attention.NEG_INF), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), cv)


def with_blocks(pair):
    """The kernel with (queries, rows) blocks of ``pair`` (None: the
    tree's own rule), a jit of its own: the blocks are read at the trace."""
    rule = attention.prefill_blocks

    def blocks(s, heads_a_kv_head, block):
        return rule(s, heads_a_kv_head, block) if pair is None else (
            min(pair[0], s), min(pair[1], s))

    def call(q, k, v, n):
        attention.prefill_blocks = blocks
        try:
            return blocked_causal_attention.__wrapped__(q, k, v, n)
        finally:
            attention.prefill_blocks = rule

    return call, blocks


def layers_of(fn, layers):
    """``layers`` calls of ``fn`` in ONE program, each one's output the
    next one's queries (keys as wide as values): a call of 0.1 ms is under
    a dispatch of its own, so the host's clock reads a stack of them."""
    @jax.jit
    def stack(q, k, v, n):
        return lax.fori_loop(0, layers, lambda _i, x: fn(x, k, v, n), q)

    return stack


def slot(args, dev, say):
    """A full layer's attention of an admission into a slot, by block."""
    heads, d, dv, s_max, sizes = SLOT
    if args.tiny:
        heads, d, dv, s_max, sizes = 2, 16, 16, 96, ((48, 30), (96, 70))
    flop = 2 * (d + dv) * heads
    peak = None if args.tiny else peaks_for(dev.device_kind)["flops_bf16"]
    pairs = [None] + [tuple(int(n) for n in b.split("x"))
                      for b in args.blocks]
    if args.tiny:
        pairs = [None, (16, 16), (16, 48)]
    calls = 1 if args.tiny else args.calls
    layers = 1 if args.tiny else SLOT_LAYERS
    forms = {}
    for pair in pairs:
        fn, blocks = with_blocks(pair)
        forms["rule" if pair is None else "%dx%d" % pair] = (
            layers_of(fn, layers), blocks)
    dense = layers_of(slot_dense, layers)
    # the prompt alone as one product: what an admission takes since PR 59
    alone = layers_of(lambda q, k, v, n: causal_attention(q, k, v), layers)
    want = None
    for tokens, length in sizes:
        q, k, v = inputs(args.seed, tokens, heads, heads, d, dv)
        rest = inputs(args.seed + 7, s_max - tokens, heads, heads, d, dv)[1:]
        ck, cv = (jnp.concatenate([a, b], 1) for a, b in zip((k, v), rest))
        n = jnp.int32(length)
        row = {"geometry": "gptj", "tokens": tokens, "prompt_len": length,
               "device": dev.device_kind}
        best = timed(dense, q, ck, cv, n, calls=calls) / layers
        if args.tiny:
            want = slot_dense(q, ck, cv, n).astype(F32)
        else:
            say({**row, "form": "slot_dense", "ms_a_call": 1e3 * best,
                 "tflops_computed": flop * tokens * s_max / best / 1e12})
            best = timed(alone, q, k, v, n, calls=calls) / layers
            say({**row, "form": "one_product", "ms_a_call": 1e3 * best,
                 "tflops_computed": flop * tokens * tokens / best / 1e12})
        for name, (fn, blocks) in forms.items():
            bq, bk = blocks(tokens, 1, 1024)
            if bk % bq:
                continue
            best = timed(fn, q, k, v, n, calls=calls) / layers
            done = flop * sum(bq * (i * bq // bk + 1) * bk
                              for i in range(-(-length // bq)))
            out = {**row, "form": "kernel", "blocks": name,
                   "bq": bq, "bk": bk}
            if args.tiny:  # the dense form's answer, and zeros past it
                got = fn(q, k, v, n).astype(F32)
                out["err"] = float(jnp.abs(got - want)[:, :length].max()
                                   / jnp.abs(want).max())
                out["past_length_all_zero"] = not bool(got[:, length:].any())
            else:
                out.update(ms_a_call=1e3 * best,
                           tflops_computed=done / best / 1e12,
                           peak_share_computed=100 * done / best / peak)
            say(out)


# EvaByte's admission: heads (each its own KV head), width, window, chunk,
# buckets, the share of a bucket the shorter prompt fills
EVA = (32, 128, 2048, 16, (4096, 8192, 24576), 0.85)


def eva_blocks_of_256(q, k, v, ks, vs, *, window, chunk, block=256):
    """What ``ops/eva.eva_attention`` was until PR 65: q [B, S, H, D]
    against k, v and the summaries ks, vs [B, NS, H, D], a block of
    queries at a time against the ONE window it lies in (the rows past a
    query masked) and ALL summaries (those of its own and later windows
    masked), float32 scores, one softmax. No ``length``: a bucket's
    padding is attended like the prompt."""
    B, S, H, D = q.shape
    scale = D ** -0.5
    blk = attention.block_of(math.gcd(S, window), block)
    per = window // chunk
    n_sum = ks.shape[1]
    tail = ((0, 0), (0, -k.shape[1] % window), (0, 0), (0, 0))
    kp, vp = jnp.pad(k, tail), jnp.pad(v, tail)

    def queries(i):
        first = i * blk
        w = first // window
        qb = lax.dynamic_slice_in_dim(q, first, blk, 1)
        kb = lax.dynamic_slice_in_dim(kp, w * window, window, 1)
        vb = lax.dynamic_slice_in_dim(vp, w * window, window, 1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb,
                       preferred_element_type=F32) * scale
        t = (first + jnp.arange(blk))[:, None]
        seen = w * window + jnp.arange(window)[None, :] <= t
        s = jnp.where(seen, s, attention.NEG_INF)
        m = s.max(-1)  # [B,H,blk]
        if n_sum:
            ss = jnp.einsum("bqhd,bchd->bhqc", qb, ks,
                            preferred_element_type=F32) * scale
            closed = jnp.arange(n_sum) < per * w
            ss = jnp.where(closed, ss, attention.NEG_INF)
            m = jnp.maximum(m, ss.max(-1))
        p = jnp.where(seen, jnp.exp(s - m[..., None]), 0.0)
        total = p.sum(-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vb,
                         preferred_element_type=F32)
        if n_sum:
            ps = jnp.where(closed, jnp.exp(ss - m[..., None]), 0.0)
            total = total + ps.sum(-1)
            out = out + jnp.einsum("bhqc,bchd->bqhd", ps.astype(q.dtype),
                                   vs, preferred_element_type=F32)
        return (out / total.transpose(0, 2, 1)[..., None]).astype(q.dtype)

    out = lax.map(queries, jnp.arange(S // blk))  # [S/blk,B,blk,H,D]
    return out.transpose(1, 0, 2, 3, 4).reshape(B, S, H, D)


def eva_with_blocks(triple, window, chunk):
    """``eva_attention`` with (queries, rows, summaries) blocks of
    ``triple`` (None: the tree's own rule), a jit of its own, and the
    (query, row or summary) pairs it then multiplies a head for
    ``length`` real tokens of ``tokens``."""
    from ray_tpu.ops import eva

    rule = eva._blocks

    def blocks(s, summaries, window, block):
        n, bq, bk, bp, ahead = rule(s, summaries, window, block)
        if triple is not None:
            bq, bk = min(triple[0], window), min(triple[1], window)
            bp = min(triple[2] if len(triple) > 2 else bk, summaries)
            ahead = -(-summaries // bp) if bp else 0
        return n, bq, bk, bp, ahead

    def call(q, k, v, ks, vs, n):
        # the kernel's own body, not its jit: the blocks are read at the
        # trace, and a jit would hand back another triple's trace
        eva._blocks = blocks
        try:
            return eva.eva_attention.__wrapped__(
                q, k, v, ks, vs, n, window=window, chunk=chunk)
        finally:
            eva._blocks = rule

    def pairs(tokens, length):
        per = window // chunk
        _, bq, bk, bp, _ = blocks(tokens, tokens // window * per, window, 1024)
        done = 0
        for w in range(-(-tokens // window)):
            real = min(max(length - w * window, 0), window)
            for i in range(-(-real // bq)):
                done += bq * ((i * bq // bk + 1) * bk
                              + -(-per * w // max(bp, 1)) * bp)
        return done, (bq, bk, bp)

    return jax.jit(call), pairs


def eva(args, dev, say):
    """A layer's attention of an EvaByte admission, by form."""
    from ray_tpu.ops.eva import eva_attention, eva_pool

    heads, d, window, chunk, sizes, fill = EVA
    if args.tiny:
        heads, d, window, chunk, sizes = 2, 16, 32, 4, (48, 96)
    per = window // chunk
    flop = 4 * d * heads  # a (query, row) pair, all heads, both products
    peak = None if args.tiny else peaks_for(dev.device_kind)["flops_bf16"]
    triples = [None] + [tuple(int(n) for n in b.split("x"))
                        for b in args.blocks]
    if args.tiny:
        triples = [None, (8, 8), (8, 16, 16)]
    forms = {}
    if "length" in inspect.signature(eva_attention).parameters:
        forms = {"rule" if t is None else "x".join(map(str, t)):
                 eva_with_blocks(t, window, chunk) for t in triples}
    before = jax.jit(functools.partial(
        eva_blocks_of_256, window=window, chunk=chunk))
    calls = 1 if args.tiny else args.calls
    for tokens in sizes:
        q, k, v = inputs(args.seed, tokens, heads, heads, d, d)
        ws = jax.random.split(jax.random.key(args.seed + 3), 2)
        whole = tokens // window * window
        ks, vs = eva_pool(k[:, :whole], v[:, :whole], *(
            jax.random.normal(w, (heads, d)) * d ** -0.5 for w in ws), chunk)
        row = {"geometry": "eva", "tokens": tokens, "device": dev.device_kind}
        best = timed(before, q, k, v, ks, vs, calls=calls)
        every = tokens * (window + ks.shape[1])
        want = before(q, k, v, ks, vs).astype(F32) if args.tiny else None
        if not args.tiny:
            say({**row, "form": "eva_blocks_of_256", "prompt_len": tokens,
                 "ms_a_call": 1e3 * best, "pairs_computed": every,
                 "tflops_computed": flop * every / best / 1e12,
                 "peak_share_computed": 100 * flop * every / best / peak})
        for length in (tokens, max(int(tokens * fill), 1)):
            need = sum(t % window + 1 + per * (t // window)
                       for t in range(length))
            for name, (fn, pairs) in forms.items():
                done, (bq, bk, bp) = pairs(tokens, length)
                if bk % bq or window % bk:
                    continue
                best = timed(fn, q, k, v, ks, vs, jnp.int32(length),
                             calls=calls)
                out = {**row, "form": "kernel", "blocks": name, "bq": bq,
                       "bk": bk, "bp": bp, "prompt_len": length,
                       "pairs_computed": done, "pairs_useful": need}
                if args.tiny:  # the block loop's answer, and zeros past it
                    got = fn(q, k, v, ks, vs, jnp.int32(length)).astype(F32)
                    out["err"] = float(jnp.abs(got - want)[:, :length].max()
                                       / jnp.abs(want).max())
                    out["past_length_all_zero"] = not bool(
                        got[:, length:].any())
                else:
                    out.update(ms_a_call=1e3 * best,
                               tflops_computed=flop * done / best / 1e12,
                               peak_share_computed=100 * flop * done / best
                               / peak,
                               peak_share_useful=100 * flop * need / best
                               / peak)
                say(out)


def timed(fn, *args, calls):
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready([fn(*args) for _ in range(calls)])
        best = min(best, (time.perf_counter() - t) / calls)
    return best


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--geometry", nargs="*", default=list(GEOMETRIES))
    p.add_argument("--tokens", type=int, nargs="*", default=list(TOKENS))
    p.add_argument("--calls", type=int, default=10)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--check", action="store_true")
    p.add_argument("--dense", action="store_true")
    p.add_argument("--chosen", action="store_true")
    p.add_argument("--slot", action="store_true")
    p.add_argument("--eva", action="store_true")
    p.add_argument("--blocks", nargs="*",
                   default=["256x256", "512x512", "1024x1024"])
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit("a rate needs the chip; --tiny walks the code here")
    form = "kernel" if TAKES_LENGTH else "tiles"
    rows = []

    def say(row):
        rows.append(row)
        print(json.dumps(row), flush=True)

    if args.chosen:
        chosen(args, dev, say)
    if args.slot:
        slot(args, dev, say)
    if args.eva:
        eva(args, dev, say)
    for name in () if args.chosen or args.slot or args.eva else args.geometry:
        heads, kv_heads, d, dv, fill = GEOMETRIES[name]
        if args.tiny:
            heads, kv_heads, d, dv = heads // 8, max(kv_heads // 8, 1), 24, 16
        flop = 2 * (d + dv) * heads  # a (query, row) pair, all heads
        peak = None if args.tiny else peaks_for(dev.device_kind)["flops_bf16"]
        for n in ([48, 80] if args.tiny else args.tokens):
            x = inputs(args.seed, n, heads, kv_heads, d, dv)
            for filled in (1.0, fill):
                length = max(int(n * filled), 1)
                row = {"geometry": name, "form": form, "tokens": n,
                       "prompt_len": length, "device": dev.device_kind}
                best = timed(layer_call, *x, jnp.int32(length),
                             calls=1 if args.tiny else args.calls)
                if not args.tiny:
                    done = flop * pairs_computed(n, length, heads // kv_heads)
                    need = flop * length * (length + 1) // 2
                    row.update(
                        ms_a_call=1e3 * best,
                        tflops_computed=done / best / 1e12,
                        tflops_causal=need / best / 1e12,
                        peak_share_computed=100 * done / best / peak,
                        peak_share_causal=100 * need / best / peak)
                say(row)
        for n in (DENSE_TOKENS if args.dense and not args.tiny else ()):
            x = inputs(args.seed, n, heads, kv_heads, d, dv)
            best = timed(jax.jit(causal_attention), *x, calls=args.calls)
            say({"geometry": name, "form": "one_product", "tokens": n,
                 "prompt_len": n, "device": dev.device_kind,
                 "ms_a_call": 1e3 * best,
                 "tflops_computed": flop * n * n / best / 1e12,
                 "tflops_causal": flop * n * (n + 1) // 2 / best / 1e12})
        if args.check or args.tiny:
            n = 48 if args.tiny else 1024
            x = inputs(args.seed + 1, n, heads, kv_heads, d, dv)
            with jax.default_matmul_precision("highest"):
                want = causal_attention(*(a.astype(F32) for a in x))
            for length in (n, n - n // 3):
                got = layer_call(*x, jnp.int32(length)).astype(F32)
                row = {"geometry": name, "form": form, "check_tokens": n,
                       "prompt_len": length,
                       "err": float(jnp.abs(got - want)[:, :length].max()
                                    / jnp.abs(want).max())}
                if TAKES_LENGTH:
                    row["past_length_all_zero"] = not bool(
                        got[:, length:].any())
                say(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/prefill_attention_micro.json", "w") as f:
        json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
