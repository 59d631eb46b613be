"""CPU tests of the yardstick. Not collected by the repo's tier-1 run
(that runs ``tests/``); run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import collections
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmarks import common, loadgen, trace  # noqa: E402


def _mix(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# -- traffic -----------------------------------------------------------------

@pytest.mark.parametrize("name", ["chat-steady", "chat-saturated",
                                  "doc-burst"])
def test_offered_load_does_not_depend_on_the_seed(name):
    mix = _mix(name)
    a = loadgen.quantile_open_loop(mix, 50.0, 1, 50432)
    b = loadgen.quantile_open_loop(mix, 50.0, 2147483999, 50432)

    def window(reqs):
        return [(round(r["gap"], 9), r["prompt_len"], r["n_new"])
                for r in reqs if r["counted"]]

    wa, wb = window(a), window(b)
    off_a, off_b = loadgen.offered(a), loadgen.offered(b)
    for key in ("requests", "prompt_tokens", "answer_tokens"):
        assert off_a[key] == off_b[key]
    # the same multiset of gaps and lengths, in another order: a rotation
    assert collections.Counter(wa) == collections.Counter(wb)
    assert wa != wb
    k = next(i for i in range(len(wb)) if wb[i:] + wb[:i] == wa)
    assert k % mix["arrivals"].get("burst", 1) == 0
    assert sum(g for g, _p, _n in wa) == pytest.approx(50.0)
    assert all(0 <= r["due"] < 50.0 for r in a if r["counted"])
    pre = [r for r in a if not r["counted"]]
    assert pre and all(-mix["preroll_s"] <= r["due"] < 0 for r in pre)
    assert [r["due"] for r in a] == sorted(r["due"] for r in a)
    # the token ids do come from the seed
    assert any((x["prompt"][:16] != y["prompt"][:16]).any()
               for x, y in zip(a, b))
    again = loadgen.quantile_open_loop(mix, 50.0, 1, 50432)
    assert all((x["prompt"] == y["prompt"]).all() and x["due"] == y["due"]
               for x, y in zip(a, again))


def test_quantile_midpoints_hand_worked():
    xs = loadgen.quantile_midpoints({"dist": "uniform", "lo": 0, "hi": 8}, 4)
    assert xs == [1.0, 3.0, 5.0, 7.0]
    ln = loadgen.quantile_midpoints(
        {"dist": "lognormal", "median": 96, "sigma": 0.7, "lo": 16,
         "hi": 384}, 201)
    assert abs(ln[100] - 96) < 1e-9 and min(ln) >= 16 and max(ln) <= 384


# -- metric arithmetic -------------------------------------------------------

def test_percentile_and_tpot_on_hand_made_samples():
    xs = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert common.percentile(xs, 50) == 30.0
    assert common.percentile(xs, 90) == pytest.approx(46.0)
    assert common.percentile([7.0], 90) == 7.0
    times = [1.0 + 0.02 * i for i in range(16)]
    assert common.tpot_ms(times) == pytest.approx(20.0)
    assert common.tpot_ms(times[:15]) is None  # under 16 tokens
    assert common.quartile_spread([100, 101, 102, 103, 104, 105]) == \
        pytest.approx((104.25 - 100.75) / 102.5)


GPTJ = {"d_model": 4096, "n_heads": 16, "n_kv_heads": 16, "d_head": 256,
        "d_ff": 16384, "vocab_size": 50432}


def test_flops_and_bytes_for_one_gptj_layer():
    assert common.layer_matmul_params(GPTJ) == 201_326_592
    # 8 layers, seq 2048: 6 x (8 x 201,326,592 + 4096 x 50432) matmul
    # + 8 x 3 x 0.5 x 4 x 2048 x 16 x 256 attention
    assert common.train_flops_per_token(dict(GPTJ, n_layers=8), 2048) == \
        10_903_093_248 + 402_653_184
    # 28 layers, empty cache: int8 weights + scales + norms + bf16 head
    assert common.decode_step_bytes(dict(GPTJ, n_layers=28), 0) == \
        5_637_144_576 + 4_128_768 + 237_568 + 413_138_944
    # one cached token: K and V rows of 28 layers, 16 x 256, bf16
    assert common.decode_step_bytes(dict(GPTJ, n_layers=28), 1) - \
        common.decode_step_bytes(dict(GPTJ, n_layers=28), 0) == 458_752
    fwd = common.flash_call_cost("fwd", 64, 2048, 256)
    assert fwd["flops"] == 137_438_953_472
    assert fwd["bytes"] == 268_435_456 + 524_288
    assert common.flash_call_cost("dkv", 64, 2048, 256)["flops"] == \
        2 * fwd["flops"]


def test_unknown_device_kind_is_an_error():
    assert common.peaks_for("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(common.BenchFailure):
        common.peaks_for("cpu")


# -- trace reduction ---------------------------------------------------------

def test_union_gaps_and_owners():
    busy = trace.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (6.0, 7.0)])
    assert busy == [(0.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert trace.total(busy) == 4.0
    idle = trace.gaps(busy, 0.0, 8.0)
    assert idle == [(2.0, 3.0), (4.0, 6.0), (7.0, 8.0)]
    owners = trace.gaps_by_owner(
        idle, lambda a, b: "long" if b - a > 1.5 else "short")
    assert owners == {"short": 2.0, "long": 2.0}
    assert trace.clip([(0.0, 5.0)], 1.0, 2.0) == [(1.0, 2.0)]


def test_serve_owner_reads_the_engine_state():
    from benchmarks.runners.serve import serve_owner

    marks = [
        {"start": 0.0, "stats": {"live": 0, "firsts": 0, "pending": 0}},
        {"start": 1.0, "stats": {"live": 1, "firsts": 1, "pending": 0}},
        {"start": 2.0, "stats": {"live": 3, "firsts": 0, "pending": 2}},
    ]
    owner = serve_owner(marks)
    assert owner(0.5, 0.6) == "no-request-in-replica"
    assert owner(1.5, 1.6) == "engine-unattributed:first-token-pending"
    assert owner(2.5, 2.6) == "engine-unattributed:decoding"


FIXTURE = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded trace beside the tests")
def test_reduction_of_a_recorded_tpu_trace():
    """A trace recorded on a v5e chip (see benchmarks/README.md): the
    expected numbers were read once by hand from ``trace.describe``."""
    with open(os.path.join(HERE, "data", "tiny_tpu.expected.json")) as f:
        want = json.load(f)
    red = trace.reduce(trace.load(FIXTURE))
    assert len(red["per_device"]) == want["devices"]
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert red["window_s"] == pytest.approx(want["window_s"], rel=1e-6)
    assert red["busy_s"] < red["window_s"]
    progs = collections.Counter(
        trace.program_of(p["name"])
        for p in red["per_device"][0]["programs"])
    assert dict(progs) == want["programs"]
    assert red["device_ops"][0][0] == want["top_op"]
    assert sum(v for _k, v in red["idle_gaps"]) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)


# -- the harness is driven by data -------------------------------------------

def test_a_new_config_mix_metric_and_cell_are_files_only(tmp_path):
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        tmp_path / "benchmarks" / sub)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    b = tmp_path / "benchmarks"
    cfg = json.loads((b / "configs" / "gptj-6b-int8-serve.json").read_text())
    cfg["name"] = "other-model-serve"
    (b / "configs" / "other-model-serve.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat-steady.json").read_text())
    mix["generator"] = "every_second"
    (b / "traffic" / "chat-tick.json").write_text(json.dumps(mix))
    (b / "generators").mkdir()
    (b / "generators" / "every_second.py").write_text(
        "from benchmarks.common import generator\n"
        "@generator('every_second')\n"
        "def every_second(mix, seconds, seed, vocab):\n"
        "    return []\n")
    (b / "layer_metrics" / "engine.admitted.json").write_text(json.dumps(
        {"name": "engine.admitted", "reader": "count_blocks", "params": {}}))
    (b / "readers").mkdir()
    (b / "readers" / "count_blocks.py").write_text(
        "from benchmarks.common import reader\n"
        "@reader('count_blocks')\n"
        "def count_blocks(facts, params):\n"
        "    return facts['scalars'].get('blocks')\n")
    doc["configs"].append({
        "name": "other-model-serve", "source": "https://example.org/x",
        "file": "benchmarks/configs/other-model-serve.json",
        "reduced": [], "why": "test"})
    doc["workloads"].append({
        "name": "serve-other-tick", "config": "other-model-serve",
        "traffic": "chat-tick", "chips": 1, "why": "test"})
    doc["per_layer"].append({
        "name": "engine.admitted", "unit": "blocks", "better": "higher",
        "source": "program_counter", "layer": "serving engine",
        "moves": "ttft_p50_ms", "workloads": ["serve-other-tick"]})
    for m in doc["end_to_end"]:
        if m["name"] == "ttft_p50_ms":
            m["workloads"].append("serve-other-tick")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list",
         "--manifest", str(tmp_path / "BENCHMARK.json")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    new = rows["serve-other-tick"]
    assert new["config"] == "other-model-serve"
    assert new["generator"] == "every_second"
    assert new["per_layer"] == {"engine.admitted": "count_blocks"}
    assert "ttft_p50_ms" in new["end_to_end"]
    assert set(rows) == {w["name"] for w in doc["workloads"]}


def test_flash_kernels_are_told_apart_by_what_they_return():
    common.load_plugins(BENCH)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "train_readers", os.path.join(BENCH, "readers", "train.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    # the result types of the four calls in a traced step (PR 24, v5e)
    assert mod.flash_kind(
        "(bf16[32,2048,256]{2,1,0}, f32[32,1,2048]{2,1,0})") == "fwd"
    assert mod.flash_kind(
        "(bf16[32,2048,256]{2,1,0}, bf16[32,2048,256]{2,1,0})") == "dkv"
    assert mod.flash_kind("bf16[32,2048,256]{2,1,0:T(8,128)(2,1)}") == "dq"
