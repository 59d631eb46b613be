"""The benchmark's readers of ``LLMEngine.stats()`` snapshots
(``benchmarks/readers/engine_stats.py``) on hand-made snapshots, and the
per-layer metrics that name them. No JAX in this process."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402

common.load_plugins(os.path.join(ROOT, "benchmarks"))
percentile = common.READERS["stats_delta_hist_percentile"]
ratio = common.READERS["stats_delta_ratio"]

BOUNDS = [1.0, 10.0, 100.0, 1000.0]  # five buckets: <1, 1-10, ..., >=1000
CADENCE = ["engine.step_interval_ms", "engine.clean_step_interval_ms",
           "engine.tpot_mean_ms"]
ADMIT = ["engine.admit_ms_per_request", "engine.admit_after_launch_share",
         "engine.first_ahead_share"]
NEW_METRICS = {
    "serve-chat-steady": [
        "engine.queue_wait_p50_ms", "engine.queue_wait_p90_ms.chat",
        "engine.admit_to_first_p50_ms", "engine.admit_to_first_p90_ms.chat",
        "engine.prefill_pad_share", "engine.kv_read_share"] + CADENCE + ADMIT,
    "serve-doc-burst": [
        "engine.queue_wait_p50_ms", "engine.admit_to_first_p50_ms",
        "engine.prefill_pad_share"] + ADMIT,
    "serve-chat-saturated": ["engine.loop_host_share",
                             "engine.kv_read_share"] + CADENCE,
    "train4-gptj-seq2048": [],
}


def _facts(mid_counts, end_counts, **scalars):
    def snap(counts, which):
        s = {"steps": 0, "active": 0, "pending": 0,
             "hist_bounds_ms": BOUNDS,
             "h": {"counts": counts, "sum": 0.0, "count": sum(counts)}}
        s.update({k: v[which] for k, v in scalars.items()})
        return s
    return {"backlog": {"mid": snap(mid_counts, 0),
                        "end": snap(end_counts, 1)}}


@pytest.mark.parametrize("mid,end,q,want", [
    # ten in [10, 100): rank 5 of 10 is half way through the bucket
    ([0, 0, 0, 0, 0], [0, 0, 10, 0, 0], 50, 55.0),
    # four in [1, 10), six in [10, 100): rank 9 is 5/6 through the second
    ([0, 0, 0, 0, 0], [0, 4, 6, 0, 0], 90, 10.0 + 90.0 * 5 / 6),
    # rank 5 of 10 falls on the last of the first bucket's five: its edge
    ([0, 0, 0, 0, 0], [5, 5, 0, 0, 0], 50, 1.0),
    # the first bucket starts at 0
    ([0, 0, 0, 0, 0], [4, 0, 0, 0, 0], 50, 0.5),
    # the last bucket has no upper edge: its lower one
    ([0, 0, 0, 0, 0], [0, 0, 0, 0, 3], 50, 1000.0),
    # only the difference counts: what mid already held is not in it
    ([7, 7, 0, 0, 0], [7, 7, 0, 2, 0], 50, 550.0),
    # nothing observed between the snapshots
    ([1, 2, 3, 0, 0], [1, 2, 3, 0, 0], 50, None),
])
def test_hist_percentile_of_the_difference(mid, end, q, want):
    got = percentile(_facts(mid, end), {"hist": "h", "q": q})
    assert got == (None if want is None else pytest.approx(want))


@pytest.mark.parametrize("facts", [
    # a commit before the counters existed: the old three keys only
    {"backlog": {"mid": {"steps": 1, "active": 0, "pending": 0},
                 "end": {"steps": 9, "active": 0, "pending": 0}}},
    {"backlog": {"mid": _facts([0] * 5, [1] * 5)["backlog"]["mid"]}},
    {"backlog": {}},
    {},
])
def test_readers_leave_the_metric_out_where_a_key_is_missing(facts):
    assert percentile(facts, {"hist": "h", "q": 50}) is None
    assert ratio(facts, {"num": ["a"], "den": ["b"], "scale": 100.0}) is None


def test_ratio_of_summed_differences_with_subtracted_keys():
    facts = _facts([0] * 5, [0] * 5, padded=(1000, 1640), real=(700, 1100),
                   loop_s=(10.0, 30.0), firsts_sync_s=(1.0, 3.0),
                   block_sync_s=(4.0, 12.0), idle_wait_s=(2.0, 6.0),
                   frozen=(5, 5))
    pad = {"num": ["padded", "-real"], "den": ["padded"], "scale": 100.0}
    assert ratio(facts, pad) == pytest.approx(100.0 * (640 - 400) / 640)
    host = {"num": ["loop_s", "-firsts_sync_s", "-block_sync_s",
                    "-idle_wait_s"],
            "den": ["loop_s", "-idle_wait_s"], "scale": 100.0}
    assert ratio(facts, host) == pytest.approx(100.0 * (20 - 2 - 8 - 4) / 16)
    assert ratio(facts, {"num": ["real"], "den": ["padded"]}) == (
        pytest.approx(400 / 640))
    assert ratio(facts, {"num": ["real"], "den": ["frozen"]}) is None
    assert ratio(facts, {"num": ["real", "nope"], "den": ["padded"]}) is None


def _metric(name):
    with open(os.path.join(ROOT, "benchmarks", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    return common.READERS[spec["reader"]], spec["params"]


# (mid, end) of each key a metric reads, and what it makes of them
@pytest.mark.parametrize("name,scalars,want", [
    ("engine.step_interval_ms",
     {"block_interval_s": (2.0, 14.5), "block_interval_steps": (100, 1100)},
     12.5),
    ("engine.clean_step_interval_ms",
     {"block_interval_clean_s": (1.0, 6.7),
      "block_interval_clean_steps": (80, 680)}, 9.5),
    ("engine.tpot_mean_ms",
     {"decode_gap_s": (10.0, 59.0), "decode_gap_tokens": (1000, 5000)},
     12.25),
    ("engine.admit_ms_per_request",
     {"admit_s": (3.0, 6.08), "requests_admitted": (100, 210)}, 28.0),
    ("engine.admit_after_launch_share",
     {"admit_first_s": (0.5, 0.94), "admit_lanes_s": (1.0, 2.1),
      "admit_s": (3.0, 6.08)}, 50.0),
    ("engine.first_ahead_share",
     {"firsts_ahead": (40, 139), "requests_first_emitted": (100, 210)},
     90.0),
    # a share's admissions: 8,100 live pairs in 9,216 rows of whole tiles
    ("engine.prefill_live_pair_share",
     {"prefill_moe_assignments": (52_000, 60_100),
      "prefill_moe_pair_rows": (61_440, 70_656)}, 100.0 * 8100 / 9216),
])
def test_cadence_and_admission_metrics_on_hand_made_snapshots(
        name, scalars, want):
    read, params = _metric(name)
    assert read(_facts([0] * 5, [0] * 5, **scalars), params) == (
        pytest.approx(want))
    # nothing of the divisor's between the two snapshots: no number
    den = params["den"][0]
    still = dict(scalars, **{den: (scalars[den][1],) * 2})
    assert read(_facts([0] * 5, [0] * 5, **still), params) is None
    # a program without the counters: the metric is left out
    old = {k: v for k, v in scalars.items() if k != params["num"][0]}
    assert read(_facts([0] * 5, [0] * 5, **old), params) is None


def test_the_live_pair_share_lists_the_routed_cells_and_comes_last():
    """ISSUE 43: one entry, appended: the cell that holds a share (the
    mechanism) and the cell that holds every expert (the control), both
    judged on ``tpot_p50_ms``; a data file for the reader that is there."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    entry = next(m for m in doc["per_layer"]
                 if m["name"] == "engine.prefill_live_pair_share")
    # a later configuration that holds a share appends its cell (PR 44)
    assert entry == {
        "name": "engine.prefill_live_pair_share", "unit": "%",
        "better": "higher", "source": "program_counter",
        "layer": "serving engine", "moves": "tpot_p50_ms",
        "workloads": ["serve-glm-reason-saturated",
                      "serve-mimo-codeagent-saturated"]
        + entry["workloads"][2:]}
    judged = next(m for m in doc["end_to_end"] if m["name"] == "tpot_p50_ms")
    assert set(entry["workloads"]) <= set(judged["workloads"])
    read, params = _metric(entry["name"])
    assert read is ratio and params == {
        "num": ["prefill_moe_assignments"], "den": ["prefill_moe_pair_rows"],
        "scale": 100.0}


def test_each_engine_metric_lists_only_cells_that_report_what_it_moves():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    cells = [w["name"] for w in doc["workloads"]]
    reports = {m["name"]: set(m.get("workloads", cells))
               for m in doc["end_to_end"]}
    mine = [m for m in doc["per_layer"] if m["name"] in CADENCE + ADMIT]
    assert [m["name"] for m in mine] == CADENCE + ADMIT
    at = doc["per_layer"].index(mine[0])  # appended together, none moved
    assert doc["per_layer"][at:at + len(mine)] == mine
    for m in mine:
        assert set(m["workloads"]) <= reports[m["moves"]], m["name"]
        assert (m["layer"], m["source"]) == (
            "serving engine", "program_counter")
        assert _metric(m["name"])[0] is ratio
    on = {m["name"]: m["workloads"] for m in mine}
    # later cells join (PR 39: the window / full attention model's)
    assert all(on[n] == on[CADENCE[0]] and len(on[n]) >= 4 for n in CADENCE)
    assert all(on[n] == on[ADMIT[0]] and len(on[n]) == 3 for n in ADMIT)


def test_the_engine_stats_metrics_resolve_in_their_cells():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--list"], capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rows = {r["cell"]: r for r in map(json.loads, out.stdout.splitlines())}
    mine = ("stats_delta_hist_percentile", "stats_delta_ratio")
    for cell, names in NEW_METRICS.items():
        got = [k for k, rd in rows[cell]["per_layer"].items() if rd in mine]
        assert got == names, cell


@pytest.mark.phase_limit(600)  # half a minute alone
def test_cpu_rehearsal_walks_the_new_readers(rehearsal_manifest):
    """The whole control flow on the host at rehearsal sizes: the engine's
    counters reach the readers through the runner's two snapshots. Exit
    code 10: never a result. A third of the cell's 4.55 requests/s for
    twice the seconds: as many requests in the window, at a rate the host
    serves with the suite's other workers on its cores."""
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--manifest", rehearsal_manifest("chat-steady", 1.5),
         "--workload", "serve-chat-steady", "--seed", "2147483999",
         "--seconds", "12", "--trace", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=570, env=env)
    assert out.returncode == common.REHEARSAL_RC, out.stdout[-3000:]
    walked = next(json.loads(ln.split(": ", 1)[1])
                  for ln in out.stdout.splitlines()
                  if ln.startswith("readers walked on the host"))
    # the cadence and admission metrics are walked too; whether they find
    # a block, an ended request or an admission in the window's second
    # half is the host's pace, and no assertion hangs on it
    assert set(NEW_METRICS["serve-chat-steady"]) <= set(walked)
    for name in set(NEW_METRICS["serve-chat-steady"]) - set(CADENCE + ADMIT):
        assert isinstance(walked[name], float), (name, walked[name])
    assert 0.0 <= walked["engine.prefill_pad_share"] < 100.0
    assert 0.0 < walked["engine.kv_read_share"] <= 100.0
    assert walked["engine.queue_wait_p50_ms"] <= (
        walked["engine.queue_wait_p90_ms.chat"])
