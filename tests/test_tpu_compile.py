"""Ask the chip's compiler before the chip.

The TPU compiler is installed where the tests run and compiles for a chip
that is described, not attached (``jax.experimental.topologies``). The
flash kernel passes every interpret-mode test on CPU and can still be
refused by Mosaic — tiling, VMEM — so it is compiled here, uninterpreted,
at the widths the chip runs: ``bench_400m`` (b8 x 2048, 8 heads x 128) and
the long-context entry (b2 x 8192). Kernels only: nothing runs, and a
compile that passes is not a chip run.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from ray_tpu.ops.flash_attention import flash_attention


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    # Such a compile is written to the persistent cache but cannot be read
    # back without a chip: the next run would warn and compile again.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _loss(q, k, v):
    out = flash_attention(q, k, v, interpret=False)
    return out.astype(jnp.float32).sum()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize(
    "shape", [(8, 2048, 8, 128), (2, 8192, 8, 128)], ids=["s2048", "s8192"]
)
def test_flash_kernel_compiles_for_v5e(v5e, shape, grad):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e)
    fn = jax.grad(_loss, argnums=(0, 1, 2)) if grad else _loss
    hlo = jax.jit(fn).lower(x, x, x).compile().as_text()
    # fwd is one kernel; fwd+bwd adds the dq and the dk/dv kernels
    assert hlo.count("tpu_custom_call") == (3 if grad else 1)


@pytest.mark.parametrize("program", ["decode_block", "prefill_2048"])
def test_glm47_flash_serving_programs_fit_one_v5e(v5e, program):
    """The served cut of GLM-4.7-Flash (8 layers, every width as
    published, bf16) at the benchmark's engine sizes: 32 slots x 4,096
    latent rows. The compiler has to take ``lax.ragged_dot`` at 64 groups
    and the walk over the latent cache, the latent cache has to be
    updated in place, and the program has to leave room on a 16 GB chip
    (ISSUE 28: under 14.5 GiB)."""
    from ray_tpu.models import generation as gen
    from ray_tpu.models.transformer import TransformerConfig, init_params

    cfg = TransformerConfig.glm47_flash(8, param_dtype=jnp.bfloat16)

    def described(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=v5e), tree)

    params = described(jax.eval_shape(
        lambda: init_params(cfg, jax.random.key(0))))
    cache = described(jax.eval_shape(
        lambda: gen.init_kv_cache(cfg, 32, 4096)))

    def arr(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)

    if program == "decode_block":
        low = gen.decode_block.lower(
            params, cache, arr((32,)), arr((32,)), arr((32,), jnp.float32),
            arr((32,)), arr((32,)), cfg, 8)
    else:
        low = gen.prefill_into_slot.lower(
            params, arr((1, 2048)), arr(()), arr(()), cache, cfg)
    compiled = low.compile()
    mem = compiled.memory_analysis()
    cache_bytes = 8 * 32 * 4096 * 576 * 2
    assert mem.alias_size_in_bytes >= cache_bytes  # no copy of the cache
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 14.5 * 2 ** 30
    hlo = compiled.as_text()
    assert hlo.count("ragged-dot") >= 3  # the experts' grouped products
    assert "raytpu.moe.experts" in hlo and "raytpu.mla.attend" in hlo
