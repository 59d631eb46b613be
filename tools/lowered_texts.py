"""A digest of the lowered text of every served program and of a small
train step, for the tree given: ``decode_block`` at both block lengths,
``decode_step_multi`` and both forms of ``prefill_into_slot`` at every
bucket, at the engine sizes the benchmark's configurations state. Nothing
is compiled and no weight is made (shapes alone), so it runs anywhere.

    python3 tools/lowered_texts.py <tree> [name ...] > a.txt
    python3 tools/lowered_texts.py <other tree> [name ...] > b.txt
    diff a.txt b.txt

A PR that means to leave a configuration's programs alone shows it with
an empty diff against its parent (``git archive`` of the parent into a
scratch directory); where lines differ they name the programs to measure.
Names: gptj glm47 glm52 granite mimo kimi phi4flash evabyte nemotron train (default: all
the tree has). ``gptj`` prints its programs twice: from the weights as they are
made, and (``gptj-as-held``, since PR 62) from int8 leaves that say where a
v5e's engine has laid them, which its admissions read and its decode does not.
"""

from __future__ import annotations

import hashlib
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

SERVED = {  # name -> (module of benchmarks/, configuration)
    "glm47": ("mla_moe_model", "glm47flash-l8-bf16-serve"),
    "glm52": ("dsa_moe_model", "glm52-l6-e16-bf16-serve"),
    "granite": ("ssm_model", "granite4-h-micro-bf16-serve"),
    "mimo": ("swa_moe_model", "mimo-v2-flash-l7-e16-bf16-serve"),
    "kimi": ("kda_moe_model", "kimi-linear-l8-e64-bf16-serve"),
    "phi4flash": ("sambay_model", "phi4-mini-flash-bf16-serve"),
    "evabyte": ("eva_model", "evabyte-l8-bf16-serve"),
    "nemotron": ("ssm_moe_model", "nemotron3-super-l11-e128-bf16-serve"),
}


def main() -> int:
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    os.chdir(root)
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import generation as gen

    def digest(lowered):
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype)

    def load(name):
        with open(f"benchmarks/configs/{name}.json") as f:
            return json.load(f)

    def programs(name, params, cfg, eng):
        slots, rows = eng["max_slots"], eng["max_len"]
        cache = jax.eval_shape(lambda: gen.init_kv_cache(cfg, slots, rows))
        lanes = (arr((slots,)), arr((slots,)), arr((slots,), jnp.float32),
                 arr((slots,)), arr((slots,)))
        for steps in (2, 8):
            print(name, "decode_block", steps, digest(gen.decode_block.lower(
                params, cache, *lanes, cfg, steps)), flush=True)
        print(name, "decode_step_multi", digest(gen.decode_step_multi.lower(
            params, arr((slots,)), cache, arr((slots,)), cfg)))
        for b in eng["prefill_buckets"]:
            args = (params, arr((1, b)), arr(()), arr(()), cache, cfg)
            print(name, "prefill plain", b, digest(
                gen.prefill_into_slot.lower(*args)))
            print(name, "prefill admission", b, digest(
                gen.prefill_into_slot.lower(
                    *args, lanes, arr((), jnp.float32), arr(()))),
                flush=True)

    have = [n for n, (_, c) in SERVED.items()
            if os.path.exists(f"benchmarks/configs/{c}.json")]
    which = sys.argv[2:] or ["gptj", *have, "train"]
    if "gptj" in which:
        from benchmarks.runners import serve
        model = load("gptj-6b-int8-serve")
        cfg = serve.transformer_config(model)
        params = jax.eval_shape(lambda: serve.make_int8_params(cfg, 1))
        params = jax.eval_shape(
            lambda p: gen.prepare_for_inference(p, cfg)[0], params)
        cfg = gen.prepare_for_inference({}, cfg)[1]
        programs("gptj", params, cfg, model["run"]["engine"])
        if hasattr(gen, "told_where_they_lie"):
            # the same programs over the leaves as a v5e's engine holds
            # them (``lay_out_for_decode``: wq / wk / wv heads-major,
            # tests/test_tpu_compile.py): the admissions read that order
            from ray_tpu.models.quant import QTensor

            attn = params["layers"]["attn"]
            held = {w: QTensor(attn[w].q, attn[w].s, (0, 2, 1, 3))
                    for w in ("wq", "wk", "wv")}
            programs("gptj-as-held", {**params, "layers": {
                **params["layers"], "attn": {**attn, **held}}}, cfg,
                model["run"]["engine"])
    for name in which:
        if name in SERVED:
            mod = importlib.import_module("benchmarks." + SERVED[name][0])
            model = load(SERVED[name][1])
            cfg = mod.transformer_config(model)
            programs(name, jax.eval_shape(
                lambda: mod.make_bf16_params(cfg, 1)), cfg,
                model["run"]["engine"])
    if "train" in which:
        import numpy as np

        import ray_tpu.parallel.mesh as pmesh
        from ray_tpu.models.transformer import TransformerConfig
        from ray_tpu.parallel.train_step import (
            batch_sharding,
            default_optimizer,
            make_sharded_state,
            make_train_step,
        )
        from ray_tpu.train import session
        cfg = TransformerConfig(
            vocab_size=1024, d_model=512, n_layers=2, n_heads=4, d_head=128,
            d_ff=1024, rotary_dim=64, max_seq_len=2048, attn_impl="flash",
            remat=True, remat_policy="dots")
        mesh = session.make_mesh(pmesh.MeshConfig(dp=4, tp=1))
        opt = default_optimizer()
        state, state_sh = make_sharded_state(
            cfg, mesh, opt, jax.random.key(0), pmesh.FSDP_RULES)
        step = make_train_step(cfg, mesh, opt, state_sh, pmesh.FSDP_RULES)
        batch = {"tokens": np.zeros((4, 2048), np.int32),
                 "targets": np.zeros((4, 2048), np.int32),
                 "mask": np.ones((4, 2048), np.float32)}
        batch = session.distribute_batch(
            batch, mesh, spec=batch_sharding(mesh, pmesh.FSDP_RULES).spec)
        print("train", "step", digest(step.lower(state, batch)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
