"""CPU tests of what the ``serve_kda_moe`` kind adds to the yardstick: the
configuration against the catalog, the byte and FLOP functions against
ISSUE 44's hand counts, the runner's reduction of a traced stretch, the
new metrics' readers on hand-made facts, the manifest's nine cells and
the new cell's host rehearsal. Not collected by tier-1:

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

from benchmarks import common, kda_moe_model  # noqa: E402
from benchmarks.runners import serve_kda_moe as runner  # noqa: E402

common.load_plugins(BENCH)
CELL = "serve-kimi-longreason-saturated"
CONFIG = "kimi-linear-l8-e64-bf16-serve"
NEW = ("model.kda_time_share", "model.prefill_kda_chunk_share",
       "engine.state_live_share.kda", "kernel.decode_hbm_share.kda_moe",
       "kernel.kda_update_roofline_share")


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _dims():
    return kda_moe_model.dims(kda_moe_model.transformer_config(_config()))


def _metric(name):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        return json.load(f)


def test_configuration_holds_the_catalog_row_but_for_what_it_lists():
    cfg = _config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == cfg["name"])
    assert entry["reduced"] == ["num_hidden_layers", "num_experts",
                                "vocab_size", "linear_attn_config"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert cfg["source"] == entry["source"] == row["source_url"]
        assert sorted(k for k, v in row["config"].items()
                      if cfg.get(k) != v) == sorted(entry["reduced"])
        # the widths inside the changed group are the source's
        for k in ("head_dim", "num_heads", "short_conv_kernel_size"):
            assert cfg["linear_attn_config"][k] == \
                row["config"]["linear_attn_config"][k]
        for k in entry["reduced"][:3]:
            assert cfg["published"][k] == row["config"][k]
    c = kda_moe_model.transformer_config(cfg)
    assert c.layer_types == ("kda", "kda", "kda", "attention") * 2
    assert c.param_count() == 3_772_368_832  # 7.54 GB of bf16


def test_the_counts_are_the_issues_table():
    d = _dims()
    n = kda_moe_model.param_count(d)
    assert n["kda"] == 39_514_272 and n["attn_full"] == 29_114_880
    assert n["dense_ffn"] == 63_700_992 and n["routed"] == 460_652_800
    assert n["ends"] == 188_745_984 and n["total"] == 3_772_368_832
    assert kda_moe_model.slot_bytes(d) == {
        "row": 2304, "state": 6 * (2_097_152 + 73_728)}
    # every weight that is no routed expert, nothing touched, read or live
    fixed = kda_moe_model.decode_step_bytes(d, 0, 0, 0)
    assert fixed == 2 * (n["total"] - 7 * 64 * n["expert"] - 40960 * 2304)
    # 96 live lanes of ~2,500 rows, ~95 % of 448 experts: the issue's count
    full = kda_moe_model.decode_step_bytes(d, 426, 96 * 2500, 96)
    assert full == fixed + 426 * 2 * 7_077_888 + 96 * 2500 * 2304 \
        + 96 * 6 * 2 * 2_097_152
    assert 9.6e9 < full < 10.4e9
    cost = kda_moe_model.kda_update_cost(d, 96)
    assert cost["bytes"] == 96 * 2 * 2_097_152
    # bound by bytes: 0.49 ms of traffic against 0.02 ms of arithmetic
    peaks = common.PEAKS["TPU v5 lite"]
    assert cost["bytes"] / peaks["hbm_bytes_per_s"] > 10 * (
        cost["flops"] / peaks["flops_bf16"])
    # a chunk a head: 2 x (4 x 64 x 64 x 128 + 3 x 64 x 128 x 128)
    assert kda_moe_model.kda_chunk_flops(d, 64) == 32 * 2 * (
        4 * 64 * 64 * 128 + 3 * 64 * 128 * 128)
    assert kda_moe_model.kda_chunk_flops(d, 65) == \
        2 * kda_moe_model.kda_chunk_flops(d, 64)


def _trace(steps, calls=None, live=96):
    """A traced stretch of ``steps`` steps with ``live`` of the 96 lanes
    live: the engine counts the live lanes' states alone, and the kernel,
    which steps no other, takes their share of a full house's time."""
    tr = {
        "busy_s": 2.99, "window_s": 3.0,
        "programs": {"decode_block": [
            {"id": "jit_decode_block(1)", "start": t, "end": t + 0.13}
            for t in (0.0, 0.2, 0.4)]},
        "marks": [{"name": "bench.dispatch", "stats": {
            "steps": 8, "live": live, "kv_rows": 240000}}] * 3,
        "stretch_stats": {"steps": steps, "moe_experts_touched": 426 * steps,
                          "attn_rows_read": 240000 * steps,
                          "slot_steps": live * steps,
                          "state_slots_updated": live * 6 * steps},
    }
    if calls:
        tr["kernel_calls"] = {"kda_update": calls}
        tr["kernel_s"] = {"kda_update": calls * 0.00065 * live / 96}
    return tr


def _roofline(scalars):
    spec = _metric("kernel.kda_update_roofline_share")
    facts = {"scalars": scalars, "peaks": common.PEAKS["TPU v5 lite"]}
    return common.READERS[spec["reader"]](facts, spec["params"])


def test_trace_scalars_charge_what_the_counters_say():
    d = _dims()
    eng = _config()["run"]["engine"]
    out = runner.trace_scalars(_trace(24, calls=144), d, eng)
    assert out["decode_steps"] == 24
    assert out["decode_bytes"] == 24 * kda_moe_model.decode_step_bytes(
        d, 426, 240000, 96)
    # a call is charged the states the stretch's counters say it stepped
    assert out["kda_update_states_per_call"] == 96
    assert out["kda_update_bytes"] == 144 * 96 * 2 * 2_097_152
    facts = {"scalars": out, "peaks": common.PEAKS["TPU v5 lite"]}
    spec = _metric("kernel.decode_hbm_share.kda_moe")
    share = common.READERS[spec["reader"]](facts, spec["params"])
    assert 70 < share < 80  # 10.0 GB in 16.25 ms
    full = _roofline(out)
    assert abs(full - 100 * 96 * 2 * 2_097_152 / (0.00065 * 819e9)) < 1e-6
    # a trace without the kernel, a program without the counters: left out
    bare = runner.trace_scalars(_trace(24), d, eng)
    assert _roofline(bare) is None
    tr = _trace(24)
    del tr["stretch_stats"]["slot_steps"]
    assert "decode_bytes" not in runner.trace_scalars(tr, d, eng)


def test_the_kernels_share_does_not_follow_the_lanes_live():
    """A quarter of the house parked (72 of 96 lanes live, whatever
    ``max_slots`` says): the kernel steps 72 states a call in 72/96 of
    the time and reads the full house's share, not 96/72 of it."""
    d = _dims()
    eng = _config()["run"]["engine"]
    full = runner.trace_scalars(_trace(24, calls=144), d, eng)
    thin = runner.trace_scalars(_trace(24, calls=144, live=72), d, eng)
    assert thin["kda_update_states_per_call"] == 72
    assert thin["kda_update_bytes"] == 144 * 72 * 2 * 2_097_152
    assert abs(_roofline(thin) - _roofline(full)) < 1e-9
    assert _roofline(thin) < 100


def test_the_kernels_share_is_left_out_without_the_counters():
    """A program whose engine does not count the states it stepped: no
    ``kda_update_bytes``, so the share is left out of the line (never a
    fall back to ``max_slots``); the kernel's own time and calls stay."""
    d = _dims()
    eng = _config()["run"]["engine"]
    for gone in ("state_slots_updated", "steps"):
        tr = _trace(24, calls=144, live=72)
        del tr["stretch_stats"][gone]
        out = runner.trace_scalars(tr, d, eng)
        assert "kda_update_bytes" not in out
        assert out["kda_update_calls"] == 144
        assert _roofline(out) is None
    tr = _trace(24, calls=144)
    del tr["stretch_stats"]
    assert "kda_update_bytes" not in runner.trace_scalars(tr, d, eng)


def test_state_live_share_reads_the_engines_counters():
    spec = _metric("engine.state_live_share.kda")
    mid = {"slot_steps": 1000, "state_slots_updated": 96 * 6 * 30}
    end = {"slot_steps": 1000 + 93 * 80,
           "state_slots_updated": 96 * 6 * 110}
    read = common.READERS[spec["reader"]]
    assert abs(read({"backlog": {"mid": mid, "end": end}}, spec["params"])
               - 100 * 93 / 96) < 1e-9
    assert read({"backlog": {"mid": {}, "end": {}}}, spec["params"]) is None


def test_scope_metrics_read_hand_made_seconds():
    facts = {"trace": {"scope_s": {
        "decode_block": {"total": 2.0, "raytpu.kda.update": 0.5,
                         "raytpu.kda.project": 0.25, "raytpu.moe.experts": 1.0,
                         "raytpu.mla.attend": 0.1, "-": 0.3},
        "prefill_into_slot": {"total": 1.0, "raytpu.kda.chunk": 0.3,
                              "raytpu.kda.project": 0.3, "-": 0.4}}}}
    values = {}
    for name in NEW[:2]:
        spec = _metric(name)
        values[name] = common.READERS[spec["reader"]](facts, spec["params"])
        assert common.READERS[spec["reader"]]({}, spec["params"]) is None
    assert values == {"model.kda_time_share": 37.5,
                      "model.prefill_kda_chunk_share": 30.0}


def test_scope_reduction_of_the_recorded_fixture_knows_the_new_scopes():
    """The recorded trace and compiled text the scope reader is tested on
    (``tiny_scopes``): the reduction still reads it, and a text labelled
    with this kind's scopes gives its instructions those labels."""
    from benchmarks.readers import scope_time

    data = os.path.join(HERE, "data")
    with open(os.path.join(data, "tiny_scopes.hlo.txt")) as f:
        text = f.read()
    with open(os.path.join(data, "tiny_scopes.expected.json")) as f:
        expected = json.load(f)
    got = scope_time.scope_seconds(
        os.path.join(data, "tiny_scopes.xplane.pb"),
        {prog: [text] for prog in expected})
    for prog, per in expected.items():
        assert abs(got[prog]["total"] - per["total"]) < 1e-9
    line = ('  %fusion.7 = f32[4]{0} fusion(%p), kind=kLoop, metadata={'
            'op_name="jit(decode_block)/while/body/raytpu.kda.update/mul"}')
    assert scope_time.labels_of(line) == {"fusion.7": "raytpu.kda.update"}


def test_the_manifest_resolves_nine_cells():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--list"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    assert len(rows) >= 9
    new = rows[CELL]
    assert new["runner"] == "serve_kda_moe" and new["chips"] == 1
    assert (new["config"], new["traffic"]) == (CONFIG, "longreason-saturated")
    assert new["generator"] == "quantile_open_loop"
    assert new["end_to_end"] == ["tpot_p50_ms", "setup_s"]
    for name in NEW + ("model.decode_step_ms", "device.idle_share.serve",
                       "engine.kv_read_share", "model.mla_time_share",
                       "model.moe_time_share", "model.moe_load_imbalance",
                       "engine.prefill_live_pair_share"):
        assert name in new["per_layer"]
    assert "engine.moe_expert_read_share" not in new["per_layer"]


def test_traffic_is_the_issues_mix():
    with open(os.path.join(BENCH, "traffic",
                           "longreason-saturated.json")) as f:
        mix = json.load(f)
    assert mix["generator"] == "quantile_open_loop"
    assert mix["prompt"] == {"dist": "lognormal", "median": 1024,
                             "sigma": 0.9, "lo": 128, "hi": 8192}
    assert mix["answer"] == {"dist": "lognormal", "median": 768,
                             "sigma": 0.5, "lo": 192, "hi": 2048}
    assert (mix["preroll_s"], mix["drain_s"], mix["on_window_end"]) == (
        15, 10, "cancel")
    assert (mix["trace_at_s"], mix["trace_s"]) == (15, 3)
    eng = _config()["run"]["engine"]
    assert mix["warm_buckets"] == eng["prefill_buckets"]
    assert mix["prompt"]["hi"] <= max(eng["prefill_buckets"])
    assert mix["prompt"]["hi"] + mix["answer"]["hi"] <= eng["max_len"]


def test_the_new_cell_rehearses_on_the_host():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2 ** 31 + 7), "--seconds", "6", "--trace", "1",
         "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env={k: v for k, v in os.environ.items() if k != "XLA_FLAGS"})
    assert out.returncode == 10, out.stdout[-3000:] + out.stderr[-3000:]
