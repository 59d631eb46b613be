"""Find the knee of a serving mix: one replica, many short windows.

    chiprun --timeout 2400 -- python3 benchmarks/sweep.py \
        --traffic chat-steady --rates 3.5,4.5,5.5,6.5 --seeds 1,2 --seconds 30

Not part of a check and never a result line: it prints one JSON row for
each (rate, seed) with the completed share, the backlog at mid-window and
at its end, TTFT p50/p90, tokens/s and the long-block share. Run once when
a cell is defined; see README.md.
"""

from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, ROOT)

from benchmarks import common  # noqa: E402
from benchmarks.run import Manifest  # noqa: E402
from benchmarks.runners import serve as runner  # noqa: E402


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", default="gptj-6b-int8-serve")
    p.add_argument("--traffic", default="chat-steady")
    p.add_argument("--rates", required=True)
    p.add_argument("--seeds", default="1,2")
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--rehearse-cpu", action="store_true")
    args = p.parse_args()
    man = Manifest(os.path.join(ROOT, "BENCHMARK.json"))
    cfg, mix = man.config(args.config), man.traffic(args.traffic)
    common.prepare_env(args.rehearse_cpu)

    def check_device(rep):
        if not args.rehearse_cpu:
            common.peaks_for(rep["kind"])

    ctx = {"t_start": T_START, "seed": 0, "rehearsal": args.rehearse_cpu,
           "chips": 1, "check_device": check_device}
    import ray_tpu

    handle, rep, session_dir, _model = runner.start_replica(
        ctx, cfg, mix["warm_buckets"])
    try:
        for rate in map(float, args.rates.split(",")):
            for seed in map(int, args.seeds.split(",")):
                m = runner.measure(
                    handle, dict(ctx, seed=seed),
                    dict(mix, rate_rps=rate, on_window_end="drain",
                         client_threads=320),
                    rep, args.seconds, False)
                s, sc = m["samples"], m["scalars"]
                print(json.dumps({
                    "rate_rps": rate, "seed": seed,
                    "offered": m["offered"]["requests"],
                    "completed_share": 1 - m["failed"] / m["attempted"],
                    "backlog_mid": m["backlog"].get("mid"),
                    "backlog_end": m["backlog"].get("end"),
                    "ttft_p50_ms": common.percentile(s["ttft_ms"], 50),
                    "ttft_p90_ms": common.percentile(s["ttft_ms"], 90),
                    "tpot_p50_ms": common.percentile(s["tpot_ms"], 50),
                    "tokens_per_s": sc["tokens_per_s"],
                    "long_block_share": sc["long_blocks"]
                    / max(1, sc["blocks"]),
                    "late_p99_ms": common.percentile(s["late_ms"], 99),
                    "device": rep["kind"],
                }), flush=True)
    finally:
        ray_tpu.shutdown()
        runner.wait_session_gone(session_dir)
    return common.REHEARSAL_RC if args.rehearse_cpu else 0


if __name__ == "__main__":
    sys.exit(main())
