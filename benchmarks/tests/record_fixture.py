"""Records ``data/tiny_tpu.xplane.pb`` and ``data/tiny_tpu.expected.json``
on a chip: a few executions of two small jitted programs with idle
between them, inside ``bench.window`` marks.

    chiprun -- python3 benchmarks/tests/record_fixture.py chiprun_out/fixture

The expected numbers are what ``trace.reduce`` gave on the day; they were
checked by hand against ``trace.describe`` (see README.md) before being
committed. The test then holds every later version of the reduction to
them.
"""

import collections
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from benchmarks import trace

    @jax.jit
    def square_sum(x):
        return (x @ x).sum()

    @jax.jit
    def scaled(x):
        return jnp.tanh(x) * 2.0

    x = jnp.ones((512, 512), jnp.bfloat16)
    square_sum(x).block_until_ready()
    scaled(x).block_until_ready()
    tmp = os.path.join(out_dir, "raw")
    jax.profiler.start_trace(tmp)
    with TraceAnnotation("bench.window"):
        pass
    for i in range(3):
        with TraceAnnotation("bench.step", live=i, firsts=0, pending=0):
            square_sum(x).block_until_ready()
            scaled(x).block_until_ready()
        time.sleep(0.002)
    with TraceAnnotation("bench.window"):
        pass
    jax.profiler.stop_trace()
    path = trace.find_xplane(tmp)
    kept = os.path.join(out_dir, "tiny_tpu.xplane.pb")
    shutil.copy(path, kept)
    shutil.rmtree(tmp)
    red = trace.reduce(trace.load(kept))
    progs = collections.Counter(trace.program_of(p["name"])
                                for p in red["per_device"][0]["programs"])
    with open(os.path.join(out_dir, "tiny_tpu.expected.json"), "w") as f:
        json.dump({"devices": len(red["per_device"]),
                   "busy_s": red["busy_s"], "window_s": red["window_s"],
                   "programs": dict(progs),
                   "top_op": red["device_ops"][0][0]}, f, indent=1)
    with open(os.path.join(out_dir, "describe.txt"), "w") as f:
        f.write(trace.describe(kept, limit=60))
    print(json.dumps({"size": os.path.getsize(kept)}))


if __name__ == "__main__":
    os.makedirs(sys.argv[1], exist_ok=True)
    main(sys.argv[1])
