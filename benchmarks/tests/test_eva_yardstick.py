"""CPU tests of what the ``serve_eva`` kind adds to the yardstick: the
configuration against the catalog, the byte function against ISSUE 55's
arithmetic, the runner's reduction of a traced stretch, the new metrics'
readers, the manifest's new cell and its rehearsal. Not collected by
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

from benchmarks import common, eva_model  # noqa: E402
from benchmarks.runners import serve_eva as runner  # noqa: E402

common.load_plugins(BENCH)
CELL = "serve-evabyte-bytedoc-saturated"
NEW = ("model.eva_time_share", "model.prefill_eva_share",
       "engine.summary_rows_share", "kernel.decode_hbm_share.eva")
JOINED = ("model.decode_step_ms", "device.idle_share.serve",
          "engine.kv_read_share", "engine.step_interval_ms",
          "engine.clean_step_interval_ms", "engine.tpot_mean_ms")


def _config():
    with open(os.path.join(BENCH, "configs",
                           "evabyte-l8-bf16-serve.json")) as f:
        return json.load(f)


def _dims():
    return eva_model.dims(eva_model.transformer_config(_config()))


def test_configuration_holds_the_catalog_row_and_cuts_the_depth_alone():
    cfg = _config()
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "EvaByte")
        assert cfg["source"] == row["source_url"]
        assert [k for k, v in row["config"].items() if cfg.get(k) != v] == [
            "num_hidden_layers"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 32}
    for item in ("pooling_scale", "summary", "pooling_after_rotary",
                 "pooling_init", "rotary"):  # ISSUE 55's list, item by item
        assert item in cfg["assumed"]
    c = eva_model.transformer_config(cfg)
    assert (c.n_layers, c.vocab_size, c.n_heads, c.d_head) == (8, 320, 32,
                                                               128)
    assert c.param_count() == 8 * 202_391_552 + 1_310_720 + 4096 + 10_485_760
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cfg["name"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]


def test_decode_step_bytes_is_the_issues_arithmetic():
    d = _dims()
    n = eva_model.param_count(d)
    assert n == {"layer": 202_391_552, "ends": 1_310_720 + 4096 + 10_485_760}
    assert eva_model.row_bytes(d) == 16384  # 16 KB: a byte is a row
    # a slot-layer at the published 32,768 positions: 15 closed windows'
    # 1,920 summaries and the open window's 2,048 rows, 65 MB
    assert eva_model.slot_rows(d, 32768) == 3968
    assert eva_model.slot_rows(d, 32768) * 16384 == 65_011_712
    assert eva_model.slot_rows(d, 2048) == 2048
    assert eva_model.slot_rows(d, 2049) == 128 + 2048
    # every weight once but the embedding: 3.26 GB
    weights = 2 * (8 * 202_391_552 + 4096 + 10_485_760)
    assert eva_model.decode_step_bytes(d, 0, 0) == weights
    assert 3.25e9 < weights < 3.27e9
    # 16 lanes of ~10.6 k bytes: 640 summaries and ~1,024 open rows a
    # lane a layer: 3.5 GB of EVA cache, over half of the step
    full = eva_model.decode_step_bytes(d, 8 * 16 * 1024, 8 * 16 * 640)
    assert full == weights + 8 * 16 * 1664 * 16384
    assert 0.51 < (full - weights) / full < 0.53
    assert 8.1e-3 < full / 819e9 < 8.3e-3  # ISSUE 55: 8.2 ms at 819 GB/s


def _trace(window_rows, summary_rows, steps):
    return {
        "busy_s": 2.9, "window_s": 3.0,
        "programs": {"decode_block": [
            {"id": "jit_decode_block(1)", "start": t, "end": t + 0.08}
            for t in (0.0, 0.1, 0.2)]},
        "marks": [{"name": "bench.dispatch", "stats": {
            "steps": 8, "live": 16, "kv_rows": 170000}}] * 3,
        "stretch_stats": {"steps": steps,
                          "eva_window_rows_read": window_rows,
                          "eva_summary_rows_read": summary_rows,
                          "eva_windows_closed": 8},
    }


def test_trace_scalars_charge_the_rows_the_counters_read():
    d = _dims()
    eng = _config()["run"]["engine"]
    out = runner.trace_scalars(
        _trace(24 * 8 * 16 * 1024, 24 * 8 * 16 * 640, 24), d, eng)
    assert out["decode_steps"] == 24
    assert out["decode_window_rows_per_step"] == 8 * 16 * 1024
    assert out["decode_summary_rows_per_step"] == 8 * 16 * 640
    assert out["decode_windows_closed"] == 8
    assert out["decode_bytes"] == 24 * eva_model.decode_step_bytes(
        d, 8 * 16 * 1024, 8 * 16 * 640)
    facts = {"scalars": out, "peaks": common.PEAKS["TPU v5 lite"]}
    share = common.READERS["decode_hbm_share"](facts, {})
    assert 80 < share < 85  # 6.75 GB in 10 ms
    # a program without the counters: the metric is left out
    tr = _trace(0, 0, 24)
    del tr["stretch_stats"]["eva_summary_rows_read"]
    assert "decode_bytes" not in runner.trace_scalars(tr, d, eng)


def test_summary_rows_share_reads_the_engines_counters():
    with open(os.path.join(BENCH, "layer_metrics",
                           "engine.summary_rows_share.json")) as f:
        spec = json.load(f)
    mid = {"eva_summary_rows_read": 1000, "eva_window_rows_read": 4000}
    end = {"eva_summary_rows_read": 1000 + 640,
           "eva_window_rows_read": 4000 + 1024}
    read = common.READERS[spec["reader"]]
    assert abs(read({"backlog": {"mid": mid, "end": end}}, spec["params"])
               - 100 * 640 / 1664) < 1e-9
    assert read({"backlog": {"mid": {}, "end": {}}}, spec["params"]) is None


def test_scope_metrics_read_hand_made_seconds():
    facts = {"trace": {"scope_s": {
        "decode_block": {"total": 2.0, "raytpu.eva.attend": 0.9,
                         "raytpu.eva.project": 0.4, "raytpu.eva.pool": 0.1,
                         "-": 0.6},
        "prefill_into_slot": {"total": 1.0, "raytpu.eva.attend": 0.15,
                              "raytpu.eva.pool": 0.05,
                              "raytpu.eva.project": 0.3, "-": 0.5}}}}
    values = {}
    for name in NEW[:2]:
        with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
            spec = json.load(f)
        values[name] = common.READERS[spec["reader"]](facts, spec["params"])
        assert common.READERS[spec["reader"]]({}, spec["params"]) is None
    assert values == {"model.eva_time_share": 70.0,
                      "model.prefill_eva_share": 20.0}


def test_the_probes_refuse_a_missing_or_a_wide_reading():
    model = {"run": {"probe": {"decode_steps": 16}},
             "correctness": {v: 0.1 for v in runner._LIMITS.values()}}

    class Handle:
        def __init__(self, rows):
            self.rows = iter(rows)

        def remote(self, *_a):
            row = next(self.rows)
            return type("R", (), {"result": lambda self, timeout: row})()

    base = {"prefill_rel": 0.02, "first_rel": 0.03, "decode_rel": 0.04,
            "replayed": True, "top2_gap": [0.1], "tokens": 9}
    served = {"prompts": [__import__("numpy").zeros(3, int)] * 2,
              "ids": [[1, 2]] * 2}
    good = [dict(base, summary_prefill=0.01), dict(base, summary_decode=0.02)]
    out = runner.probes(Handle(good), model, served)
    assert out["ok"] and out["refused_by"] == []
    assert (out["summary_prefill"], out["summary_decode"]) == (0.01, 0.02)
    # no prompt closed a window in its replay: that reading is missing
    out = runner.probes(Handle([good[0], dict(base)]), model, served)
    assert not out["ok"] and out["refused_by"] == ["summary_decode"]
    wide = [good[0], dict(good[1], decode_rel=0.2)]
    out = runner.probes(Handle(wide), model, served)
    assert not out["ok"] and out["refused_by"] == ["decode_rel"]


def test_the_manifest_resolves_the_new_cell():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    new = rows[CELL]
    assert new["runner"] == "serve_eva" and new["chips"] == 1
    assert new["traffic"] == "bytedoc-saturated"
    assert new["generator"] == "quantile_open_loop"
    assert new["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    assert sorted(new["per_layer"]) == sorted(NEW + JOINED)
    assert new["per_layer"]["kernel.decode_hbm_share.eva"] == \
        "decode_hbm_share"


def test_traffic_is_the_issues_mix():
    with open(os.path.join(BENCH, "traffic", "bytedoc-saturated.json")) as f:
        mix = json.load(f)
    assert mix["generator"] == "quantile_open_loop"
    # exponential gaps, and the cycle entered where the seed picks, as in
    # every cell of this generator: no key groups the entry points
    n = round(mix["rate_rps"] * 50)
    assert mix["arrivals"] == {"dist": "exponential"} and n == 65
    from benchmarks import loadgen

    a, b = ([(r["prompt_len"], r["n_new"]) for r in
             loadgen.quantile_open_loop(mix, 50.0, seed, 320)
             if r["counted"]] for seed in (5500003001, 7))
    assert len(a) == n and a != b and sorted(a) == sorted(b)
    k = b.index(a[0])  # the same cycle, entered elsewhere
    assert a == b[k:] + b[:k]
    # a shorter window or a lower rate walks the same mix (the knee's
    # sweep: 30 s windows from 0.7 requests/s)
    assert loadgen.offered(loadgen.quantile_open_loop(
        dict(mix, rate_rps=0.7), 30.0, 3, 320))["requests"] == 21
    assert mix["prompt"] == {"dist": "lognormal", "median": 8192,
                             "sigma": 0.7, "lo": 2048, "hi": 28672}
    assert mix["answer"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.5, "lo": 256, "hi": 4096}
    assert (mix["preroll_s"], mix["drain_s"], mix["on_window_end"]) == (
        15, 10, "cancel")
    eng = _config()["run"]["engine"]
    assert mix["warm_buckets"] == eng["prefill_buckets"]
    assert mix["prompt"]["hi"] <= max(eng["prefill_buckets"])
    assert mix["prompt"]["hi"] + mix["answer"]["hi"] <= eng["max_len"]
    assert (eng["max_slots"], eng["max_len"]) == (16, 32768)


def test_the_cell_rehearses_on_the_host_and_exits_10():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 11), "--seconds", "6", "--trace", "1",
         "--rehearse-cpu"],
        capture_output=True, text=True, timeout=870, cwd=ROOT,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert out.returncode == 10, out.stdout[-3000:] + out.stderr[-3000:]
    walked = next(line for line in out.stdout.splitlines()
                  if line.startswith("readers walked"))
    values = json.loads(walked.split(": ", 1)[1])
    assert sorted(values) == sorted(NEW + JOINED)
    assert 0 < values["engine.summary_rows_share"] < 100
