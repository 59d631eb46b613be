"""A model of ``LLMEngine``'s loop under a saturated serving mix: how far
``tpot_p50_ms`` moves with the seed, before any chip time is spent on it.

    python3 tools/saturated_cell_model.py codeagent-saturated [rate ...]
    python3 tools/saturated_cell_model.py longreason-saturated 6.5 \
        --slots=96 --step=15.5,0.02 --prefill=6,24,1.2

``benchmarks/loadgen.quantile_open_loop`` offers every seed the same cycle
of requests, entered at a place the seed picks, so a cell has only n
distinct windows. This walks every one of them through a loop that admits
first come first served into ``SLOTS`` slots (each admission a prefill that
every lane waits out), then decodes a block of 2 steps while anything
waits (8 otherwise), and prints the quartile spread and the range of the
median token gap over all entry points: what two sets of six runs will
spread by, less the chip's own ~0.5-0.9 %. The three times are read off a
traced run of the cell (PR 39, `serve-mimo-codeagent-saturated`: a step
8.0 ms + 0.067 ms a live lane; a prefill 4 ms + 25 ms a thousand padded
tokens + 0.45 ms a thousand squared); with them the model gave that
cell's per-seed readings to +-0.3 ms after a +0.5 ms offset (PERF.md
section 6, PR 39). Another cell gives its own: ``--slots=`` the engine's
slots, ``--step=`` a step's fixed and per-live-lane ms, ``--prefill=``
a prefill's fixed ms, ms a thousand padded tokens and ms a thousand
squared. No JAX, no chip.
"""

from __future__ import annotations

import copy
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks import loadgen  # noqa: E402

SLOTS, SECONDS = 48, 50.0
STEP_MS = (8.0, 0.067)  # a decode step: fixed, a live lane
PREFILL_MS = (4.0, 25.0, 0.45)  # fixed, a thousand tokens, a thousand squared


def prefill_s(tokens: int, buckets) -> float:
    k = min(b for b in buckets if b >= tokens) / 1000.0
    return (PREFILL_MS[0] + PREFILL_MS[1] * k + PREFILL_MS[2] * k * k) / 1e3


def window(mix, k: int):
    """The requests of the window entered at cycle position ``k``, with
    the pre-roll before them (``loadgen.quantile_open_loop``'s)."""
    cyc = loadgen.cycle(mix, SECONDS)
    n, reqs, t = len(cyc), [], 0.0
    for j in range(n):
        c = cyc[(k + j) % n]
        reqs.append({"due": t, "counted": True, **c})
        t += c["gap"]
    pre, t = [], 0.0
    for j in range(1, n + 1):
        c = cyc[(k - j) % n]
        t -= c["gap"]
        if t < -float(mix["preroll_s"]):
            break
        pre.append({"due": t, "counted": False, **c})
    return pre[::-1] + reqs


def tpot_p50_ms(mix, k: int) -> float:
    reqs, buckets = window(mix, k), mix["warm_buckets"]
    queue, live, gaps, i, t = [], [], [], 0, reqs[0]["due"]
    while t < SECONDS + 10:
        while i < len(reqs) and reqs[i]["due"] <= t:
            queue.append(reqs[i])
            i += 1
        if not live and not queue:
            if i >= len(reqs):
                break
            t = reqs[i]["due"]
            continue
        admitted = False
        while queue and len(live) < SLOTS:
            r = queue.pop(0)
            t += prefill_s(r["prompt_len"], buckets)
            live.append([r["n_new"] - 1, t, r["counted"], r["n_new"]])
            admitted = True
        for _ in range(2 if queue or admitted else 8):
            t += (STEP_MS[0] + STEP_MS[1] * len(live)) / 1e3
            for lane in live:
                lane[0] -= 1
        for left, first, counted, n_new in live:
            if left <= 0 and counted and t <= SECONDS and n_new >= 16:
                gaps.append((t - first) / (n_new - 1))
        live = [lane for lane in live if lane[0] > 0]
    return 1e3 * statistics.median(gaps)


def main() -> int:
    global SLOTS, STEP_MS, PREFILL_MS
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    for opt in (a for a in sys.argv[1:] if a.startswith("--")):
        key, _, value = opt[2:].partition("=")
        times = tuple(float(x) for x in value.split(","))
        if key == "slots":
            SLOTS = int(times[0])
        elif key == "step" and len(times) == 2:
            STEP_MS = times
        elif key == "prefill" and len(times) == 3:
            PREFILL_MS = times
        else:
            raise SystemExit(f"unknown or ill-formed option {opt!r}")
    name, rates = args[0], [float(r) for r in args[1:]]
    with open(os.path.join(ROOT, "benchmarks", "traffic", name + ".json")) as f:
        mix = json.load(f)
    for rate in rates or [mix["rate_rps"]]:
        m = copy.deepcopy(mix)
        m["rate_rps"] = rate
        n = len(loadgen.cycle(m, SECONDS))
        vals = [tpot_p50_ms(m, k) for k in range(n)]
        q, med = statistics.quantiles(vals, n=4), statistics.median(vals)
        print(f"{rate:.2f} requests/s, {n} entry points: tpot_p50_ms "
              f"{med:.2f}, quartile spread {100 * (q[2] - q[0]) / med:.2f} %,"
              f" range {100 * (max(vals) - min(vals)) / med:.2f} %")
    return 0


if __name__ == "__main__":
    sys.exit(main())
