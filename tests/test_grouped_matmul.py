"""The routed experts' grouped product (``ops/grouped_matmul.py``) in the
Pallas interpreter against ``lax.ragged_dot``: the schedules a decode step
and a prompt hand it, the stacked weights read in place, and the counter
of its visits."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.grouped_matmul import group_schedule, grouped_matmul
from ray_tpu.ops.moe import routed_ffn


def _sizes(rng, groups, touched, rows):
    """``rows`` rows over ``touched`` of ``groups`` groups, each >= 1."""
    out = np.zeros(groups, np.int32)
    idx = rng.choice(groups, touched, replace=False)
    out[idx] = 1 + rng.multinomial(rows - touched, np.ones(touched) / touched)
    return out


CASES = {
    # a decode step: 128 rows, most of 64 groups empty
    "decode_128_rows_sparse": dict(m=128, k=256, n=384, E=64, touched=20),
    # [layers, E, ...] read in place: the other layers' weights are NaN
    "stacked_first": dict(m=128, k=128, n=256, E=16, touched=9, L=3, li=0),
    "stacked_middle": dict(m=128, k=128, n=256, E=16, touched=9, L=3, li=1),
    "stacked_last": dict(m=128, k=128, n=256, E=16, touched=9, L=3, li=2),
    # a prompt: groups straddle the 128-row tiles
    "many_rows_straddling": dict(m=1024, k=128, n=256, E=16, touched=16),
    "one_expert_takes_all": dict(m=512, k=128, n=128, E=8,
                                 sizes=[0, 0, 0, 512, 0, 0, 0, 0]),
    # masked lanes: 37 rows behind the last group belong to none
    "rows_behind_last_group": dict(m=256, k=128, n=128, E=8,
                                   sizes=[30, 0, 99, 1, 0, 60, 29, 0]),
    "fewer_rows_than_a_tile": dict(m=20, k=32, n=24, E=8,
                                   sizes=[3, 0, 5, 0, 0, 7, 1, 2]),
    # the served widths, both ways round
    "k2048_n1536": dict(m=32, k=2048, n=1536, E=4, sizes=[9, 0, 20, 3]),
    "k1536_n2048": dict(m=32, k=1536, n=2048, E=4, sizes=[0, 31, 0, 1]),
    # gate and up in one pass
    "gated_pair_decode": dict(m=128, k=256, n=384, E=64, touched=20, pair=True),
    "gated_pair_straddling": dict(m=1024, k=128, n=256, E=16, touched=16,
                                  L=2, li=1, pair=True),
}


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_grouped_product_matches_ragged_dot(case):
    m, k, n, E = (case[x] for x in "mknE")
    L, li, pair = case.get("L", 1), case.get("li", 0), case.get("pair", False)
    rng = np.random.default_rng(m + k + n + E + li)
    sizes = np.asarray(case["sizes"], np.int32) if "sizes" in case else (
        _sizes(rng, E, case["touched"], m))
    ks = jax.random.split(jax.random.key(m + E), 3)
    x = jax.random.normal(ks[0], (m, k)).astype(jnp.bfloat16)
    ws = [(jax.random.normal(key, (E, k, n)) * k ** -0.5).astype(jnp.bfloat16)
          for key in ks[1:2 + pair]]
    stacks = [jnp.full((L, E, k, n), jnp.nan, jnp.bfloat16).at[li].set(w)
              for w in ws]
    got = jax.jit(lambda x, stacks, sizes, li: grouped_matmul(
        x, stacks, group_schedule(sizes, m), layer=li,
        act=jax.nn.silu if pair else None))(
            x, stacks, jnp.asarray(sizes), li)
    assert got.shape == (m, n) and got.dtype == jnp.bfloat16
    want = [jax.lax.ragged_dot(x, w, jnp.asarray(sizes),
                               preferred_element_type=jnp.float32)
            for w in ws]
    want = jax.nn.silu(want[0]) * want[1] if pair else want[0]
    rows = int(sizes.sum())  # behind them nothing is defined
    np.testing.assert_allclose(
        np.asarray(got[:rows], np.float32), np.asarray(want[:rows]),
        rtol=2 ** -7, atol=2 ** -7)


def test_weight_visits_counts_group_tile_pairs():
    """A decode step's 128 rows sit in one tile, so every touched expert
    is visited once; four experts of 96 rows each over three tiles are
    1 + 2 + 2 + 1 visits."""
    sched = group_schedule(jnp.asarray([100, 0, 60, 200, 24], jnp.int32), 384)
    assert int(sched.visits) == 1 + 2 + 2 + 1
    v = int(sched.visits)
    assert sched.group_ids[:v].tolist() == [0, 2, 2, 3, 3, 4]
    assert sched.tile_ids[:v].tolist() == [0, 0, 1, 1, 2, 2]

    d, E, f = 16, 8, 12
    ks = jax.random.split(jax.random.key(8), 5)
    wp = {"router": jax.random.normal(ks[0], (d, E)),
          "bias": jnp.zeros(E),
          "wg": jax.random.normal(ks[1], (E, d, f)) * d ** -0.5,
          "wi": jax.random.normal(ks[2], (E, d, f)) * d ** -0.5,
          "wo": jax.random.normal(ks[3], (E, f, d)) * f ** -0.5}
    x = jax.random.normal(ks[4], (32, d))  # 32 lanes x 4 choices
    _, stats = routed_ffn(x, wp, top_k=4)
    assert int(stats["moe_weight_visits"]) == int(
        stats["moe_experts_touched"]) > 4
    same = jnp.tile(x[:1], (96, 1)) * (1 + 1e-3 * jnp.arange(96)[:, None])
    _, stats = routed_ffn(same, wp, top_k=4)
    assert int(stats["moe_experts_touched"]) == 4
    assert int(stats["moe_weight_visits"]) == 6
