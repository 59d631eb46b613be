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
from typing import Any, Dict, NamedTuple, Tuple

SERVED = {  # name -> (module of benchmarks/, configuration)
    "gptj": ("runners.serve", "gptj-6b-int8-serve"),
    "glm47": ("mla_moe_model", "glm47flash-l8-bf16-serve"),
    "glm52": ("dsa_moe_model", "glm52-l6-e16-bf16-serve"),
    "granite": ("ssm_model", "granite4-h-micro-bf16-serve"),
    "mimo": ("swa_moe_model", "mimo-v2-flash-l7-e16-bf16-serve"),
    "kimi": ("kda_moe_model", "kimi-linear-l8-e64-bf16-serve"),
    "phi4flash": ("sambay_model", "phi4-mini-flash-bf16-serve"),
    "evabyte": ("eva_model", "evabyte-l8-bf16-serve"),
    "nemotron": ("ssm_moe_model", "nemotron3-super-l11-e128-bf16-serve"),
}


class Served(NamedTuple):
    """What one configuration's cells serve, as shapes: no weight is made."""
    cfg: Any  # the ``TransformerConfig`` the cell's runner builds
    params: Any  # the weights as the runner makes them
    cache: Any  # ``engine["max_slots"]`` slots of ``engine["max_len"]`` rows
    lanes: Tuple  # the engine's five arrays, an entry a slot
    engine: Dict  # the file's ``run.engine``: sizes, buckets, block lengths
    sharding: Any  # what every array above is described on, or None

    @property
    def block_steps(self):
        """The two lengths of ``decode_block``: under a burst, and else."""
        return (self.engine["burst_block_steps"], self.engine["block_steps"])

    def arr(self, shape, dtype="int32"):
        import jax

        return jax.ShapeDtypeStruct(shape, dtype, sharding=self.sharding)

    def lower(self, program: str):
        """``decode_block_<steps>``, ``decode_step_multi``,
        ``prefill_<bucket>`` (``prefill_into_slot`` alone) or
        ``admission_<bucket>`` (the form the engine calls: with the lanes,
        the request's temperature and its seed), lowered."""
        from ray_tpu.models import generation as gen

        arr, slots = self.arr, self.engine["max_slots"]
        if program == "decode_step_multi":
            return gen.decode_step_multi.lower(
                self.params, arr((slots,)), self.cache, arr((slots,)),
                self.cfg)
        kind, size = program.rsplit("_", 1)
        sizes = {"decode_block": self.block_steps,
                 "prefill": self.engine["prefill_buckets"],
                 "admission": self.engine["prefill_buckets"]}[kind]
        if int(size) not in sizes:
            raise ValueError(f"{program}: the configuration's are {sizes}")
        if kind == "decode_block":
            return gen.decode_block.lower(
                self.params, self.cache, *self.lanes, self.cfg, int(size))
        args = (self.params, arr((1, int(size))), arr(()), arr(()),
                self.cache, self.cfg)
        if kind == "admission":
            args += (self.lanes, arr((), "float32"), arr(()))
        return gen.prefill_into_slot.lower(*args)


def served(name: str, sharding=None) -> Served:
    """The configuration ``SERVED[name]`` as its runner serves it, read from
    the benchmark's own file: the model through the benchmark's
    ``transformer_config``, the weights' shapes through its maker (GPT-J's
    int8 leaves as ``prepare_for_inference`` leaves them), the engine's
    sizes from ``run.engine``. ``sharding`` describes every array on a
    device (``tests/test_tpu_compile.py``: a v5e that is not attached)."""
    import jax

    from benchmarks import common
    from ray_tpu.models import generation as gen

    module, config = SERVED[name]
    mod = importlib.import_module("benchmarks." + module)
    with open(os.path.join(common.HERE, "configs", config + ".json")) as f:
        model = json.load(f)
    cfg = mod.transformer_config(model)
    if name == "gptj":
        params = jax.eval_shape(
            lambda: gen.prepare_for_inference(
                mod.make_int8_params(cfg, 1), cfg)[0])
        cfg = gen.prepare_for_inference({}, cfg)[1]
    else:
        params = jax.eval_shape(lambda: mod.make_bf16_params(cfg, 1))
    eng = model["run"]["engine"]
    slots = eng["max_slots"]
    cache = jax.eval_shape(
        lambda: gen.init_kv_cache(cfg, slots, eng["max_len"]))
    lanes = tuple(jax.ShapeDtypeStruct((slots,), dtype) for dtype in (
        "int32", "int32", "float32", "int32", "int32"))
    params, cache, lanes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        (params, cache, lanes))
    return Served(cfg, params, cache, lanes, eng, sharding)


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, root)
    os.chdir(root)
    import jax

    from ray_tpu.models import generation as gen

    def digest(lowered):
        return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]

    def programs(name, s):
        for steps in s.block_steps:
            print(name, "decode_block", steps,
                  digest(s.lower(f"decode_block_{steps}")), flush=True)
        print(name, "decode_step_multi", digest(s.lower("decode_step_multi")))
        for b in s.engine["prefill_buckets"]:
            print(name, "prefill plain", b, digest(s.lower(f"prefill_{b}")))
            print(name, "prefill admission", b,
                  digest(s.lower(f"admission_{b}")), flush=True)

    have = [n for n, (_, c) in SERVED.items()
            if os.path.exists(f"benchmarks/configs/{c}.json")]
    which = sys.argv[2:] or [*have, "train"]
    for name in which:
        if name not in SERVED:
            continue
        s = served(name)
        programs(name, s)
        if name == "gptj" and hasattr(gen, "told_where_they_lie"):
            # the same programs over the leaves as a v5e's engine holds
            # them (``lay_out_for_decode``: wq / wk / wv heads-major,
            # tests/test_tpu_compile.py): the admissions read that order
            from ray_tpu.models.quant import QTensor

            attn = s.params["layers"]["attn"]
            held = {w: QTensor(attn[w].q, attn[w].s, (0, 2, 1, 3))
                    for w in ("wq", "wk", "wv")}
            programs("gptj-as-held", s._replace(params={
                **s.params, "layers": {
                    **s.params["layers"], "attn": {**attn, **held}}}))
    if "train" in which:
        import jax.numpy as jnp
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
