"""CPU tests of what the ``serve_ssm_moe`` kind adds to the yardstick: the
configuration against the catalog, the byte functions against ISSUE 60's
arithmetic, the runner's reduction of a traced stretch, the new metrics'
readers, the limits' table, and the manifest's new cell. Not collected by
tier-1:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != BENCH]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, ssm_moe_model  # noqa: E402
from benchmarks.runners import serve_ssm_moe as runner  # noqa: E402

common.load_plugins(BENCH)
CONFIG = "nemotron3-super-l11-e128-bf16-serve"
CELL = "serve-nemotron3-multiagent-saturated"
NEW = ("model.moe_latent_proj_share", "kernel.decode_hbm_share.ssm_moe",
       "kernel.grouped_matmul_roofline_share.latent")
REDUCED = ["num_hidden_layers", "hybrid_override_pattern",
           "n_routed_experts", "vocab_size"]


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _dims():
    return ssm_moe_model.dims(ssm_moe_model.transformer_config(_config()))


def test_configuration_holds_the_catalog_row_but_the_four_cuts():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f) if r["name"] == (
                "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"))
        assert cfg["source"] == row["source_url"]
        assert sorted(k for k, v in row["config"].items()
                      if cfg.get(k) != v) == sorted(REDUCED)
        assert cfg["published"] == {k: row["config"][k] for k in REDUCED}
        # the cut is the published pattern's first period
        assert row["config"]["hybrid_override_pattern"].startswith(
            cfg["hybrid_override_pattern"])
    assert cfg["reduced"] == REDUCED
    c = ssm_moe_model.transformer_config(cfg)
    assert (c.n_layers, c.n_ssm_layers, c.n_expert_layers, c.n_attn_layers
            ) == (11, 5, 5, 1)
    assert (c.moe_experts, c.experts_held, c.moe_top_k, c.vocab_size) == (
        512, 128, 22, 32768)
    assert c.param_count() == 4_648_163_712  # 9.30 GB of bf16
    assert c.param_count() == ssm_moe_model.param_count(_dims())["total"]
    for group in ("assumed", "departures", "not_run", "deployment",
                  "correctness", "rehearsal"):
        assert cfg[group], group
    assert "multi_token_prediction" in cfg["not_run"]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cfg["name"])
    assert entry["reduced"] == REDUCED and entry["source"] == cfg["source"]
    # every limit the runner holds a reading to is in the file, twice
    for limit in runner._LIMITS.values():
        assert limit in cfg["correctness"], limit
        assert limit in cfg["rehearsal"]["correctness"], limit


def test_the_byte_functions_are_the_issues_arithmetic():
    d = _dims()
    n = ssm_moe_model.param_count(d)
    assert n["expert"] == 2 * 1024 * 2688  # 5.505 M
    assert n["routed"] - 128 * n["expert"] == 54_526_464  # 54.5 M
    assert n["ssm"] == 109_635_968 and n["attn"] == 35_651_584  # 109.6 M, 35.7 M
    assert ssm_moe_model.slot_state_bytes(d) == 5 * (
        128 * 64 * 128 * 4 + 3 * 10240 * 2)  # 21.3 MB a slot
    assert ssm_moe_model.slot_row_bytes(d) == 1024  # 1 KB a cached token
    fixed = ssm_moe_model.decode_step_bytes(d, 0, 0, 0)
    # M 1.10 GB + E outside the experts 0.55 GB + * 0.07 GB + head 0.27 GB
    assert 1.95e9 < fixed < 2.0e9
    full = ssm_moe_model.decode_step_bytes(d, 0.94 * 640, 64, 64 * 1500)
    assert full == fixed + 2 * 0.94 * 640 * n["expert"] + (
        64 * 1500 * 1024) + 2 * 64 * ssm_moe_model.slot_state_bytes(d)
    assert 11.2e9 < full < 11.6e9  # ISSUE 60: ~11.4 GB, 13.9 ms at 819 GB/s
    cost = ssm_moe_model.grouped_products_cost(d, 600, 1760)
    assert cost["bytes"] == 2 * (600 * n["expert"] + 1760 * 2 * 3712)
    assert cost["flops"] == 1760 * 4 * 1024 * 2688


def _trace(steps):
    return {
        "busy_s": 2.9, "window_s": 3.0,
        "programs": {"decode_block": [
            {"id": "jit_decode_block(1)", "start": t, "end": t + 0.12}
            for t in (0.0, 0.2, 0.4)]},
        "marks": [{"name": "bench.dispatch", "stats": {
            "steps": 8, "live": 64, "kv_rows": 90000}}] * 3,
        "stretch_stats": {
            "steps": steps, "moe_experts_touched": 600 * steps,
            "moe_assignments": 1760 * steps,
            "state_slots_updated": 64 * 5 * steps,
            "attn_rows_read": 96000 * steps},
        "kernel_calls": {"grouped_matmul": 240},
        "kernel_s": {"grouped_matmul": 0.18},
    }


def test_trace_scalars_charge_experts_states_and_rows_from_the_counters():
    d = _dims()
    eng = _config()["run"]["engine"]
    out = runner.trace_scalars(_trace(48), d, eng)
    assert out["decode_steps"] == 24
    assert out["decode_experts_touched_per_step"] == 600
    assert out["decode_slots_updated_per_step"] == 64
    assert out["decode_bytes"] == 24 * ssm_moe_model.decode_step_bytes(
        d, 600, 64, 96000)
    # the traced steps' half of the stretch's experts and pairs
    assert out["grouped_matmul_bytes"] == (
        ssm_moe_model.grouped_products_cost(d, 24 * 600, 24 * 1760)["bytes"])
    # a program the trace's edge cuts has events for some of its calls
    # alone: the bytes are those of the calls whose seconds were summed
    cut = _trace(48)
    cut["kernel_calls"]["grouped_matmul"] = 200  # of 24 x 5 x 2
    cut = runner.trace_scalars(cut, d, eng)
    assert cut["grouped_matmul_calls_of_steps"] == 240
    assert cut["grouped_matmul_bytes"] == (
        ssm_moe_model.grouped_products_cost(d, 20 * 600, 20 * 1760)["bytes"])
    facts = {"scalars": out, "peaks": common.PEAKS["TPU v5 lite"]}
    values = {}
    for name in NEW[1:]:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        values[name] = common.READERS[spec["reader"]](facts, spec["params"])
    assert 90 < values[NEW[1]] < 95  # 11.3 GB in 15 ms
    assert 105 < values[NEW[2]] < 110  # hand-made seconds: 6.6 GB in 7.5 ms
    # a program without the counters or the kernel: the metrics are left out
    tr = _trace(48)
    del tr["stretch_stats"]["moe_experts_touched"], tr["kernel_calls"]
    out = runner.trace_scalars(tr, d, eng)
    assert "decode_bytes" not in out and "grouped_matmul_bytes" not in out
    with open(os.path.join(BENCH, "layer_metrics", NEW[2] + ".json")) as f:
        spec = json.load(f)
    assert common.READERS[spec["reader"]](
        {"scalars": out, "peaks": facts["peaks"]}, spec["params"]) is None


def test_the_latent_scope_reads_hand_made_seconds():
    facts = {"trace": {"scope_s": {"decode_block": {
        "total": 2.0, "raytpu.moe.experts": 1.0, "raytpu.moe.latent": 0.05,
        "raytpu.ssm.update": 0.6, "-": 0.35}}}}
    with open(os.path.join(BENCH, "layer_metrics", NEW[0] + ".json")) as f:
        spec = json.load(f)
    read = common.READERS[spec["reader"]]
    assert read(facts, spec["params"]) == 2.5
    assert read({}, spec["params"]) is None
    # the accepted routed share takes the new scope in
    with open(os.path.join(BENCH, "layer_metrics",
                           "model.moe_time_share.json")) as f:
        spec = json.load(f)
    assert read(facts, spec["params"]) == 52.5


def test_the_manifest_resolves_the_new_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    assert len(rows) == 12 and sum(r["chips"] == 4 for r in rows.values()) == 1
    new = rows[CELL]
    assert new["runner"] == "serve_ssm_moe" and new["chips"] == 1
    assert new["config"] == CONFIG
    assert new["traffic"] == "multiagent-saturated"
    assert new["generator"] == "quantile_open_loop"
    assert new["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    for name in NEW + ("model.decode_step_ms", "device.idle_share.serve",
                       "engine.kv_read_share", "model.ssm_time_share",
                       "model.moe_time_share", "engine.state_skip_share",
                       "engine.moe_expert_read_share", "jit.backend_s"):
        assert name in new["per_layer"], name


def test_traffic_is_the_issues_mix():
    with open(os.path.join(BENCH, "traffic",
                           "multiagent-saturated.json")) as f:
        mix = json.load(f)
    assert mix["generator"] == "quantile_open_loop"
    assert mix["arrivals"] == {"dist": "exponential"}
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.8, "lo": 256, "hi": 6144}
    assert mix["answer"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "lo": 192, "hi": 2048}
    eng = _config()["run"]["engine"]
    assert mix["prompt"]["hi"] + mix["answer"]["hi"] <= eng["max_len"]
    assert mix["warm_buckets"] == eng["prefill_buckets"]
    assert (mix["preroll_s"], mix["on_window_end"], mix["drain_s"],
            mix["client_threads"], mix["trace_at_s"], mix["trace_s"]) == (
        15, "cancel", 10, 400, 15, 3)
