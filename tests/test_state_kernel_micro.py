"""``tools/state_kernel_micro.py`` is a tool for the chip; here it is
imported and walked at a toy size through the Pallas interpreter, so that
the next PR that needs a state kernel alone finds it working."""

import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "tools",
                     "state_kernel_micro.py")


@pytest.fixture(scope="module")
def micro():
    spec = importlib.util.spec_from_file_location("state_kernel_micro", _PATH)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.mark.parametrize("kernel", ["kda_update", "ssm_update"])
def test_the_tiny_walk_runs_the_kernel_and_its_copy_and_reports_no_rate(
        micro, kernel, capsys):
    assert micro.main([kernel, "--tiny", "--live-share", "1.0", "0.5"]) == 0
    rows = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["kernel"] for r in rows] == [kernel, "copy"] * 2
    assert [r["live_slots"] for r in rows] == [4, 4, 2, 2]
    for r in rows:  # a time off the chip is no device number
        assert not {"ms_a_call", "gb_per_s", "share_of_peak"} & set(r)
        assert r["bytes_a_call"] == 2 * 4 * r["live_slots"] * int(
            np.prod(micro.TINY[kernel][2:]))


def test_the_copy_ablation_moves_the_live_tiles_and_nothing_else(micro):
    leaf = jax.random.normal(jax.random.key(0), (2, 4, 2, 16, 16))
    live = jnp.array([True, False, True, False])
    _, new = micro.copy_update(leaf, 1, live, tile_bytes=2 * 16 * 16 * 4)
    np.testing.assert_array_equal(new, leaf)


def test_off_the_chip_without_tiny_it_measures_nothing(micro):
    assert micro.main(["kda_update"]) == 2
