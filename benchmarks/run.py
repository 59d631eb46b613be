"""One cell, once:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its configuration
(``benchmarks/configs/<config>.json``, whose ``kind`` picks
``benchmarks/runners/<kind>.py``) and its traffic mix
(``benchmarks/traffic/<traffic>.json``, whose ``generator`` names a
function); each per-layer metric that lists the cell has a
``benchmarks/layer_metrics/<name>.json`` naming a ``reader``. Functions
register themselves from ``benchmarks/readers/*.py`` and
``benchmarks/generators/*.py``. A later PR adds files and entries and
edits none. See ``benchmarks/README.md``.

The last line of standard output is the result, one JSON object. There is
none, and the exit code is not 0, when no TPU (or another count of chips
than the cell asks for, or a ``device_kind`` that is not in the table of
peaks) is found, or a phase fails. ``--rehearse-cpu`` walks the same
control flow on the host at the configuration's ``rehearsal`` sizes and
exits with code 10: never a result.
"""

from __future__ import annotations

import time

T_START = time.time()  # process start, for setup_s

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the script's own directory would shadow the standard library's ``trace``
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import common, loadgen  # noqa: E402,F401 (registers)
from benchmarks.common import BenchFailure  # noqa: E402


def load_json(path):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` and the data files it names, resolved against
    the manifest's own directory."""

    def __init__(self, path: str):
        self.root = os.path.dirname(os.path.abspath(path))
        self.doc = load_json(path)
        self.bench_dir = os.path.join(self.root, self.doc["paths"][0])
        common.load_plugins(HERE, self.bench_dir)

    def cell(self, name: str):
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str):
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise SystemExit(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str):
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      name + ".json"))

    def metrics_of(self, group: str, cell: str):
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    def layer_metric(self, name: str):
        return load_json(os.path.join(self.bench_dir, "layer_metrics",
                                      name + ".json"))

    def listing(self):
        """What ``--list`` prints: every cell with what it resolves to."""
        out = []
        for w in self.doc["workloads"]:
            cfg, mix = self.config(w["config"]), self.traffic(w["traffic"])
            layer = {}
            for m in self.metrics_of("per_layer", w["name"]):
                rd = self.layer_metric(m["name"])["reader"]
                if rd not in common.READERS:
                    raise SystemExit(f"{m['name']}: no reader {rd!r}")
                layer[m["name"]] = rd
            if mix["generator"] not in common.GENERATORS:
                raise SystemExit(
                    f"{w['traffic']}: no generator {mix['generator']!r}")
            out.append({
                "cell": w["name"], "chips": w["chips"],
                "config": w["config"], "runner": cfg["kind"],
                "traffic": w["traffic"], "generator": mix["generator"],
                "end_to_end": [m["name"] for m in self.metrics_of(
                    "end_to_end", w["name"])],
                "per_layer": layer,
            })
        return out


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=[0, 1], default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="host, rehearsal sizes, exit code 10, no result")
    p.add_argument("--list", action="store_true",
                   help="print what every cell resolves to, and exit")
    p.add_argument("--manifest", default=os.path.join(ROOT,
                                                      "BENCHMARK.json"))
    p.add_argument("--keep-trace", default=None,
                   help="directory for a description of the trace")
    args = p.parse_args()
    man = Manifest(args.manifest)
    if args.list:
        for row in man.listing():
            print(json.dumps(row))
        return 0
    cell = man.cell(args.workload)
    cfg, mix = man.config(cell["config"]), man.traffic(cell["traffic"])
    seconds = args.seconds or float(man.doc["run_seconds"])

    common.prepare_env(args.rehearse_cpu)
    trace_dir = os.path.join(ROOT, ".bench_tmp", f"trace-{os.getpid()}")
    if args.trace:
        os.makedirs(trace_dir, exist_ok=True)

    def check_device(rep):
        want = "cpu" if args.rehearse_cpu else "tpu"
        if rep["platform"] != want or rep["count"] != cell["chips"]:
            raise BenchFailure(
                f"the cell needs {cell['chips']} x {want}; the worker "
                f"found {rep['count']} x {rep['platform']}")
        if not args.rehearse_cpu:
            common.peaks_for(rep["kind"])

    ctx = {
        "t_start": T_START, "seed": args.seed, "seconds": seconds,
        "trace": bool(args.trace), "rehearsal": args.rehearse_cpu,
        "chips": cell["chips"], "config": cfg, "traffic": mix,
        "trace_dir": trace_dir, "keep_trace": args.keep_trace,
        "check_device": check_device,
    }
    runner = importlib.import_module(f"benchmarks.runners.{cfg['kind']}")
    try:
        facts = runner.run(ctx)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if "jax" in sys.modules:
        from jax._src import xla_bridge

        if xla_bridge.backends_are_initialized():
            raise BenchFailure("the driver initialised a JAX backend")
    print(json.dumps({"note": facts["note"], "checks": facts["checks"]}),
          flush=True)
    if args.rehearse_cpu:
        ok = all(facts["checks"].values())
        if args.trace:  # the readers run; what they read is the host's
            facts["peaks"] = next(iter(common.PEAKS.values()))
            walked = {m["name"]: common.READERS[spec["reader"]](
                facts, spec["params"]) for m in man.metrics_of(
                    "per_layer", cell["name"])
                for spec in [man.layer_metric(m["name"])]}
            print("readers walked on the host (no device number): "
                  + json.dumps(walked), flush=True)
        print(f"rehearsal on the host {'passed' if ok else 'FAILED'}: "
              "control flow only, no result", flush=True)
        return common.REHEARSAL_RC if ok else 1

    dev = facts["device"]
    facts["peaks"] = common.peaks_for(dev["kind"])
    metrics = {}
    if args.trace:
        for m in man.metrics_of("per_layer", cell["name"]):
            spec = man.layer_metric(m["name"])
            value = common.READERS[spec["reader"]](facts, spec["params"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in man.metrics_of("end_to_end", cell["name"]):
            if m["name"] not in facts["e2e"]:
                raise BenchFailure(f"the run gave no {m['name']}")
            metrics[m["name"]] = {"value": facts["e2e"][m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": facts["scalars"]["peak_bytes"]}
    line = {"correct": all(facts["checks"].values()),
            "attempted": facts["attempted"], "failed": facts["failed"],
            "metrics": metrics, "device": device}
    if args.trace:
        tr = facts["trace"]
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        line["breakdown"] = {"device_ops": tr["device_ops"][:10],
                             "idle_gaps": tr["idle_gaps"][:10]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
