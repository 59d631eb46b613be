"""CPU tests of what the ``serve_ssm`` kind adds to the yardstick: the
configuration against the catalog, the byte function against ISSUE 35's
arithmetic, the runner's reduction of a traced stretch, the new metrics'
readers, and the manifest's new cell. Not collected by tier-1:

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

from benchmarks import common, ssm_model  # noqa: E402
from benchmarks.runners import serve_ssm as runner  # noqa: E402

common.load_plugins(BENCH)
CELL = "serve-granite-agent-saturated"
NEW = ("model.ssm_time_share", "model.prefill_ssm_scan_share",
       "engine.state_live_share", "kernel.decode_hbm_share.ssm")


def _config():
    with open(os.path.join(BENCH, "configs",
                           "granite4-h-micro-bf16-serve.json")) as f:
        return json.load(f)


def _dims():
    return ssm_model.dims(ssm_model.transformer_config(_config()))


def test_configuration_holds_the_catalog_row_and_cuts_nothing():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "granite-4.0-h-micro")
        assert cfg["source"] == row["source_url"]
        assert [k for k, v in row["config"].items() if cfg.get(k) != v] == []
    c = ssm_model.transformer_config(cfg)
    assert (c.n_layers, c.n_ssm_layers, c.vocab_size) == (40, 36, 100352)
    assert c.param_count() * 2 == 6_382_792_192  # 6.38 GB of bf16
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cfg["name"])
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]


def test_decode_step_bytes_is_the_issues_arithmetic():
    d = _dims()
    n = ssm_model.param_count(d)
    assert n == {"ssm_layer": 76_182_976, "attn_layer": 60_821_504,
                 "ends": 205_522_944}
    assert ssm_model.slot_state_bytes(d) == 76_437_504  # 76.4 MB a slot
    weights = 2 * 3_191_396_096
    assert ssm_model.decode_step_bytes(d, 0, 0) == weights
    # 48 live lanes: every state in and out (7.34 GB), 8 KB a cached row
    full = ssm_model.decode_step_bytes(d, 48, 48 * 1100)
    assert full == weights + 2 * 48 * 76_437_504 + 48 * 1100 * 8192
    assert 14.0e9 < full < 14.3e9  # 17.1-17.5 ms at 819 GB/s


def _trace(slots_updated, rows, steps):
    return {
        "busy_s": 2.9, "window_s": 3.0,
        "programs": {"decode_block": [
            {"id": "jit_decode_block(1)", "start": t, "end": t + 0.16}
            for t in (0.0, 0.2, 0.4)]},
        "marks": [{"name": "bench.dispatch", "stats": {
            "steps": 8, "live": 48, "kv_rows": 50000}}] * 3,
        "stretch_stats": {"steps": steps,
                          "state_slots_updated": slots_updated,
                          "attn_rows_read": rows},
    }


def test_trace_scalars_charge_states_and_rows_from_the_counters():
    d = _dims()
    eng = _config()["run"]["engine"]
    out = runner.trace_scalars(_trace(48 * 36 * 24, 24 * 52800, 24), d, eng)
    assert out["decode_steps"] == 24
    assert out["decode_slots_updated_per_step"] == 48
    assert out["decode_bytes"] == 24 * ssm_model.decode_step_bytes(
        d, 48, 52800)
    facts = {"scalars": out, "peaks": common.PEAKS["TPU v5 lite"]}
    share = common.READERS["decode_hbm_share"](facts, {})
    assert 85 < share < 90  # 14.15 GB in 20 ms
    # a program without the counters: the metric is left out
    tr = _trace(0, 0, 24)
    del tr["stretch_stats"]["state_slots_updated"]
    assert "decode_bytes" not in runner.trace_scalars(tr, d, eng)


def test_state_live_share_reads_the_engines_counters():
    with open(os.path.join(BENCH, "layer_metrics",
                           "engine.state_live_share.json")) as f:
        spec = json.load(f)
    mid = {"slot_steps": 1000, "state_slots_updated": 48 * 36 * 30}
    end = {"slot_steps": 1000 + 47 * 80,
           "state_slots_updated": 48 * 36 * 110}
    read = common.READERS[spec["reader"]]
    assert abs(read({"backlog": {"mid": mid, "end": end}}, spec["params"])
               - 100 * 47 / 48) < 1e-9
    assert read({"backlog": {"mid": {}, "end": {}}}, spec["params"]) is None


def test_scope_metrics_read_hand_made_seconds():
    facts = {"trace": {"scope_s": {
        "decode_block": {"total": 2.0, "raytpu.ssm.update": 0.9,
                         "raytpu.ssm.project": 0.5, "-": 0.6},
        "prefill_into_slot": {"total": 1.0, "raytpu.ssm.scan": 0.2,
                              "raytpu.ssm.project": 0.3, "-": 0.5}}}}
    values = {}
    for name in NEW[:2]:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        values[name] = common.READERS[spec["reader"]](facts, spec["params"])
        assert common.READERS[spec["reader"]]({}, spec["params"]) is None
    assert values == {"model.ssm_time_share": 70.0,
                      "model.prefill_ssm_scan_share": 20.0}


def test_the_manifest_resolves_the_new_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    new = rows[CELL]
    assert new["runner"] == "serve_ssm" and new["chips"] == 1
    assert new["traffic"] == "agent-saturated"
    assert new["generator"] == "quantile_open_loop"
    assert new["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    for name in NEW + ("model.decode_step_ms", "device.idle_share.serve",
                       "engine.kv_read_share"):
        assert name in new["per_layer"]
    assert new["per_layer"]["kernel.decode_hbm_share.ssm"] == \
        "decode_hbm_share"


def test_traffic_is_the_issues_mix():
    with open(os.path.join(BENCH, "traffic", "agent-saturated.json")) as f:
        mix = json.load(f)
    assert mix["generator"] == "quantile_open_loop"
    assert mix["arrivals"] == {"dist": "exponential"}
    assert mix["prompt"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "lo": 256, "hi": 2048}
    assert mix["answer"] == {"dist": "lognormal", "median": 384,
                             "sigma": 0.5, "lo": 96, "hi": 1024}
    assert (mix["preroll_s"], mix["drain_s"], mix["on_window_end"]) == (
        15, 10, "cancel")
    assert mix["warm_buckets"] == [256, 512, 1024, 2048]
    eng = _config()["run"]["engine"]
    assert mix["prompt"]["hi"] <= max(eng["prefill_buckets"])
    assert mix["prompt"]["hi"] + mix["answer"]["hi"] <= eng["max_len"]
