"""One configuration's admission program (``generation.prefill_into_slot``
with the lanes, as ``LLMEngine._admit`` launches it) ALONE on the chip
under the profiler, at each of the configuration's prefill buckets: the
program's device time and its busiest operations, from the weights the
benchmark makes, in the layouts ``lay_out_for_decode`` leaves them in, into
a cache of the cell's size.

    chiprun -- python3 tools/admission_profile.py --config gptj-6b-int8-serve
    chiprun -- python3 tools/admission_profile.py \\
        --config glm47flash-l8-bf16-serve --min-bucket 1024 \\
        --score-bytes 0 1000000000000 --repeats 3
    chiprun -- python3 tools/admission_profile.py \\
        --config evabyte-l8-bf16-serve --buckets 4096 8192 24576
    python3 tools/admission_profile.py --config gptj-6b-int8-serve --tiny

``--score-bytes N [M ...]`` puts N in ``ops/attention.PREFILL_SCORE_BYTES``'
place (0: every full layer through the prefill kernel; a large number:
every one as one product); of several values each one's program is compiled
and held, and they take turns at a bucket, ``--repeats R`` profiles each, a
line a profile: the forms side by side on one chip, and how far one reading
spreads. Bucket by bucket that is the table the constant was set from
(PERF.md, section 6, PR 58 and PR 59). What a kernel
costs INSIDE a program (the copies that lay its operands out, what the
compiler then does to the fusions around it) is not in a kernel's own
micro-run (``tools/prefill_attention_micro.py``): this is the cheaper
instrument than a cell's pair, ~1 chip-minute a configuration, and reads
to 0.01 ms. One line a bucket, with the program's ``--top`` busiest
operations (name, times a program, ms a program), ``int8_moves_ms`` (the
summed ms a program of operations whose result is an ``s8[...]`` array: an
int8 weight copied before it is used; PR 62), and the forty busiest in
``chiprun_out/admission_profile_<config>_<score-bytes>.json``. ``--tiny``
walks the code here at the configuration's rehearsal size without the
profiler and reports no time. A tool: no cell and no metric reads it. It
runs from an older tree too (copy it under that tree's ``tools/``): where
the constant still lives in ``generation`` it is moved there.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace
from ray_tpu.models import generation as gen
from ray_tpu.ops import attention

# where each configuration's ``transformer_config`` and weights are made
MODELS = {"gptj-6b-int8-serve": "benchmarks.runners.serve",
          "glm47flash-l8-bf16-serve": "benchmarks.mla_moe_model",
          "glm52-l6-e16-bf16-serve": "benchmarks.dsa_moe_model",
          "granite4-h-micro-bf16-serve": "benchmarks.ssm_model",
          "mimo-v2-flash-l7-e16-bf16-serve": "benchmarks.swa_moe_model",
          "kimi-linear-l8-e64-bf16-serve": "benchmarks.kda_moe_model",
          "phi4-mini-flash-bf16-serve": "benchmarks.sambay_model",
          "evabyte-l8-bf16-serve": "benchmarks.eva_model"}


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True, choices=sorted(MODELS))
    p.add_argument("--score-bytes", type=int, nargs="+", default=[None])
    p.add_argument("--min-bucket", type=int, default=0)
    p.add_argument("--max-bucket", type=int, default=None)
    p.add_argument("--buckets", type=int, nargs="*", default=None)
    p.add_argument("--repeats", type=int, default=1)
    p.add_argument("--fill", type=float, default=0.8)
    p.add_argument("--calls", type=int, default=4)
    p.add_argument("--top", type=int, default=8)
    p.add_argument("--seed", type=int, default=5)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)
    dev = jax.devices()[0]
    if not args.tiny and dev.platform != "tpu":
        raise SystemExit("a time needs the chip; --tiny walks the code here")
    # the module whose constant the tree's prefill reads
    rule = attention if hasattr(attention, "PREFILL_SCORE_BYTES") else gen
    forms = [rule.PREFILL_SCORE_BYTES if b is None else b
             for b in args.score_bytes]
    with open(os.path.join(
            ROOT, "benchmarks", "configs", args.config + ".json")) as f:
        model = json.load(f)
    if args.tiny:
        model.update(model["rehearsal"])
    made = importlib.import_module(MODELS[args.config])
    cfg = made.transformer_config(model)
    make = getattr(made, "make_bf16_params", None) or made.make_int8_params
    eng = model["run"]["engine"]
    slots, s_max = eng["max_slots"], eng["max_len"]
    params, cfg = gen.prepare_for_inference(make(cfg, args.seed), cfg)
    params, _, _ = gen.lay_out_for_decode(params, cfg, slots, s_max, 2)
    state = {"cache": gen.init_kv_cache(cfg, slots, s_max),
             "lanes": tuple(jnp.zeros(slots, t) for t in (
                 jnp.int32, jnp.int32, jnp.float32, jnp.int32, jnp.int32))}
    rng = np.random.default_rng(args.seed)
    out = {}
    for bucket in eng["prefill_buckets"]:
        if bucket < args.min_bucket or (
                args.max_bucket and bucket > args.max_bucket) or (
                args.buckets and bucket not in args.buckets):
            continue
        n = max(int(bucket * args.fill), 1)
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :n] = rng.integers(1, cfg.vocab_size, n)

        def operands(slot):
            return (params, padded, np.int32(n), np.int32(slot),
                    state["cache"], state["lanes"], np.float32(0),
                    np.int32(1))

        # a form's program is compiled once and held: the forms then take
        # turns in one process, on one chip, minutes apart at most
        built = {}
        for form in forms:
            rule.PREFILL_SCORE_BYTES = form
            jax.clear_caches()  # the bucket's trace under another form
            t = time.perf_counter()
            head = operands(0)
            built[form] = (gen.prefill_into_slot.lower(
                *head[:5], cfg, *head[5:]).compile(), )
            built[form] += (time.perf_counter() - t,)

        def admit(form, slot):
            first, state["cache"], state["lanes"], _stats = built[form][0](
                *operands(slot))
            return first

        for repeat in range(args.repeats):
            for form in forms:
                jax.block_until_ready(admit(form, 0))
                row = {"config": args.config, "bucket": bucket,
                       "prompt_len": n, "score_bytes": form,
                       "repeat": repeat, "device": dev.device_kind,
                       "build_s": built[form][1]}
                if args.tiny:
                    print(json.dumps(row), flush=True)
                    continue
                where = os.path.join(
                    os.environ.get("TMPDIR", "/tmp"),
                    "admission_profile_%d_%d_%d_%d" % (
                        os.getpid(), bucket, form, repeat))
                trace.start(where)
                jax.block_until_ready(
                    [admit(form, i % slots) for i in range(args.calls)])
                jax.profiler.stop_trace()
                seen = trace.load(trace.find_xplane(where))["devices"]
                seen = seen[sorted(seen)[0]]
                runs = [r for r in seen["programs"]
                        if "prefill_into_slot" in r["name"]]
                ops = {}
                for o in seen["ops"]:
                    if not trace.is_container(o["name"]):
                        r = ops.setdefault(trace.short_op(o["name"]),
                                           [0, 0.0, o["name"][:300]])
                        r[0] += 1
                        r[1] += o["end"] - o["start"]
                row.update(
                    program_ms=1e3 * sum(r["end"] - r["start"] for r in runs)
                    / max(len(runs), 1),
                    kernel_ms=1e3 * sum(
                        s for k, (_c, s, _t) in ops.items()
                        if k.startswith(("prefill_attention",
                                         "eva_attention"))) / args.calls,
                    # operations whose RESULT is an int8 array: a weight
                    # sliced out of its stack or laid out again before it
                    # is dequantised (0.0 where every product reads its
                    # weight where it lies)
                    int8_moves_ms=1e3 * sum(
                        s for k, (_c, s, _t) in ops.items()
                        if k.partition(":")[2].startswith("s8[")
                    ) / args.calls)
                busiest = [
                    [k, c // args.calls, 1e3 * s / args.calls, text]
                    for k, (c, s, text) in sorted(
                        ops.items(), key=lambda kv: -kv[1][1])[:40]]
                print(json.dumps({**row, "top": [
                    o[:3] for o in busiest[:args.top]]}), flush=True)
                out["%d_%d_%d" % (bucket, form, repeat)] = {
                    **row, "ops": busiest}
    if not args.tiny:
        os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
        with open(os.path.join(
                ROOT, "chiprun_out", "admission_profile_%s_%s.json" % (
                    args.config, "_".join(map(str, forms)))), "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
