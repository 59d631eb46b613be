"""CPU tests of what the ``serve_mla_moe`` kind adds to the yardstick: the
byte function, the runner's reductions, the time-by-scope reader on a trace
recorded on a v5e, and the manifest's five cells. Not collected by tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, mla_moe_model  # noqa: E402
from benchmarks.runners import serve_mla_moe as runner  # noqa: E402

common.load_plugins(BENCH)
CELL = "serve-glm-reason-saturated"


def _config():
    with open(os.path.join(BENCH, "configs",
                           "glm47flash-l8-bf16-serve.json")) as f:
        return json.load(f)


def _dims():
    return mla_moe_model.dims(mla_moe_model.transformer_config(_config()))


def test_configuration_holds_the_catalog_row_and_cuts_only_depth():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "GLM-4.7-Flash")
        assert cfg["source"] == row["source_url"]
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differs == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == 8
    assert cfg["published"]["num_hidden_layers"] == 47
    assert "num_nextn_predict_layers" in cfg["not_run"]
    c = mla_moe_model.transformer_config(cfg)
    assert c.n_dense_layers == 1 and c.n_expert_layers == 7
    assert c.param_count() * 2 == 10_332_496_768  # 10.33 GB of bf16


def test_decode_step_bytes_hand_worked():
    """ISSUE 28's arithmetic: attention 21.76 M and an expert 9.44 M
    parameters; with every expert touched and no latent row a step reads
    all weights but the embedding."""
    d = _dims()
    everything = mla_moe_model.decode_step_bytes(d, 0, 7 * 64)
    assert everything == (5_166_248_384 - 154880 * 2048) * 2
    one_expert = 3 * 2048 * 1536 * 2
    assert everything - mla_moe_model.decode_step_bytes(
        d, 0, 7 * 64 - 10) == 10 * one_expert
    assert mla_moe_model.decode_step_bytes(d, 1000, 0) - \
        mla_moe_model.decode_step_bytes(d, 0, 0) == 1000 * 8 * 576 * 2
    # the floor: with 55.5 of 64 experts touched in each of 7 layers
    assert 8.2e9 < mla_moe_model.decode_step_bytes(d, 0, 7 * 55.5) < 8.6e9


def _trace(steps_marks, touched, steps):
    return {
        "busy_s": 2.5, "window_s": 3.0,
        "programs": {
            "decode_block": [
                {"id": "jit_decode_block(1)", "start": t, "end": t + 0.1}
                for t in (0.0, 0.2, 0.4)],
            "prefill_into_slot": [
                {"id": "jit_prefill_into_slot(2)", "start": 1.0,
                 "end": 1.05}],
        },
        "marks": [{"name": "bench.dispatch", "stats": m}
                  for m in steps_marks] + [
            {"name": "bench.prefill", "stats": {"tokens": 900}}],
        "stretch_stats": {"steps": steps, "moe_experts_touched": touched},
    }


def test_trace_scalars_charge_touched_experts_and_latent_rows():
    eng = {"block_steps": 8, "burst_block_steps": 2, "max_slots": 32}
    marks = [{"steps": 8, "live": 32, "kv_rows": 32000}] * 3
    s = runner.trace_scalars(_trace(marks, 380 * 24, 24), _dims(), eng)
    assert s["decode_steps"] == 24
    assert s["decode_device_s"] == pytest.approx(0.3)
    assert s["prefill_device_s"] == pytest.approx(0.05)
    rows = 32000 + 0.5 * 7 * 32
    assert s["decode_latent_rows"] == pytest.approx(rows)
    assert s["decode_experts_touched_per_step"] == pytest.approx(380)
    assert s["decode_bytes"] == pytest.approx(
        24 * mla_moe_model.decode_step_bytes(_dims(), rows, 380))
    facts = {"scalars": s, "peaks": common.PEAKS["TPU v5 lite"]}
    share = common.READERS["decode_hbm_share"](facts, {})
    assert share == pytest.approx(
        100 * s["decode_bytes"] / (0.3 * 819e9))
    # a program without the counters: no byte count, the metric is left out
    tr = _trace(marks, 0, 0)
    tr["stretch_stats"] = {"steps": 24}
    s = runner.trace_scalars(tr, _dims(), eng)
    assert "decode_bytes" not in s


def test_moe_scalars_and_their_metrics():
    mid = {"moe_assignments": 1000, "moe_experts_capacity": 6400,
           "moe_max_load": 90, "moe_experts_touched": 3000}
    end = {"moe_assignments": 1000 + 128 * 70, "moe_max_load": 90 + 8 * 70,
           "moe_experts_capacity": 6400 + 64 * 70,
           "moe_experts_touched": 3000 + 56 * 70}
    s = runner.moe_scalars({"mid": mid, "end": end}, {"moe_experts": 64})
    assert s == {"moe_mean_load": 2.0, "moe_fullest_load": 8.0}
    facts = {"scalars": s, "backlog": {"mid": mid, "end": end}}

    def metric(name):
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        return common.READERS[spec["reader"]](facts, spec["params"])

    assert metric("model.moe_load_imbalance") == 4.0
    assert metric("engine.moe_expert_read_share") == 87.5
    # a program that publishes no such counters: nothing, not an error
    assert runner.moe_scalars({"mid": {}, "end": {}}, {"moe_experts": 64}) \
        == {}
    facts = {"scalars": {}, "backlog": {"mid": {}, "end": {}}}
    assert metric("model.moe_load_imbalance") is None
    assert metric("engine.moe_expert_read_share") is None


def test_scope_time_share_on_hand_made_seconds():
    per = {"decode_block": {"total": 10.0, "raytpu.moe.experts": 2.0,
                            "raytpu.moe.route": 1.0, "ragged-dot": 4.0,
                            "raytpu.mla.attend": 1.5, "-": 1.5}}
    read = common.READERS["scope_time_share"]
    facts = {"trace": {"scope_s": per}}
    assert read(facts, {"program": "decode_block",
                        "prefixes": ["raytpu.moe.", "ragged-dot"]}) == 70.0
    assert read(facts, {"program": "decode_block",
                        "prefixes": ["raytpu.mla."]}) == 15.0
    assert read(facts, {"program": "prefill_into_slot",
                        "prefixes": ["raytpu.moe."]}) is None
    assert read({"trace": {}}, {"program": "decode_block",
                                "prefixes": ["raytpu.moe."]}) is None
    assert read({}, {"program": "decode_block", "prefixes": ["x"]}) is None


FIXTURE = os.path.join(HERE, "data", "tiny_scopes.xplane.pb")


@pytest.mark.skipif(not os.path.exists(FIXTURE),
                    reason="no recorded trace beside the tests")
def test_scope_seconds_of_a_recorded_tpu_trace():
    """Recorded on a v5e chip by ``record_scope_fixture.py``: one program
    named decode_block, a fusion under raytpu.mla.attend, a ragged_dot,
    and operations outside every scope."""
    from benchmarks.readers import scope_time

    with open(os.path.join(HERE, "data", "tiny_scopes.expected.json")) as f:
        want = json.load(f)
    with open(os.path.join(HERE, "data", "tiny_scopes.hlo.txt")) as f:
        text = f.read()
    # a variant with other instruction numbers must lose to the real one
    other = text.replace("%fusion", "%other_fusion")
    got = scope_time.scope_seconds(FIXTURE, {"decode_block": [other, text]})
    assert set(got) == set(want) == {"decode_block"}
    per = got["decode_block"]
    assert per == pytest.approx(want["decode_block"], rel=1e-6)
    # read by hand from the description of the trace and the compiled text:
    # an iteration runs fusion.11 (the tanh attention, op_name under
    # raytpu.mla.attend), ragged-dot-metadata + ragged-dot-none (named by
    # the compiler, no scope) and add_multiply_fusion.2 (the silu and add
    # of the experts' scope fused into the multiply behind it, whose
    # op_name is the multiply's, under no scope), between copy-start/-done
    assert set(per) == {"total", "raytpu.mla.attend", "ragged-dot", "-"}
    assert per["ragged-dot"] > per["raytpu.mla.attend"] > 0
    assert per["total"] == pytest.approx(
        sum(v for k, v in per.items() if k != "total"), rel=1e-9)
    share = common.READERS["scope_time_share"](
        {"trace": {"scope_s": got}},
        {"program": "decode_block",
         "prefixes": ["raytpu.moe.", "ragged-dot"]})
    assert 0 < share < 100


def test_the_manifest_resolves_five_cells():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    assert len(rows) >= 5  # later PRs add cells
    new = rows[CELL]
    assert new["runner"] == "serve_mla_moe" and new["chips"] == 1
    assert new["traffic"] == "reason-saturated"
    assert new["generator"] == "quantile_open_loop"
    assert "tpot_p50_ms" in new["end_to_end"]
    assert "setup_s" in new["end_to_end"]
    assert new["per_layer"]["kernel.decode_hbm_share.mla_moe"] == \
        "decode_hbm_share"
    assert "kernel.decode_hbm_share" not in new["per_layer"]


def test_traffic_is_the_issues_mix():
    with open(os.path.join(BENCH, "traffic", "reason-saturated.json")) as f:
        mix = json.load(f)
    assert mix["generator"] == "quantile_open_loop"
    assert mix["arrivals"] == {"dist": "exponential"}
    assert mix["prompt"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.7, "lo": 128, "hi": 2048}
    assert mix["answer"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.6, "lo": 64, "hi": 1024}
    assert (mix["preroll_s"], mix["drain_s"], mix["client_threads"],
            mix["trace_at_s"], mix["trace_s"], mix["on_window_end"]) == (
        10, 10, 400, 15, 3, "cancel")
    assert mix["warm_buckets"] == [256, 512, 1024, 2048]
    eng = _config()["run"]["engine"]
    assert mix["prompt"]["hi"] <= max(eng["prefill_buckets"])
    assert mix["prompt"]["hi"] + mix["answer"]["hi"] <= eng["max_len"]
