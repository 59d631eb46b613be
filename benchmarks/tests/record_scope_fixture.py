"""Records ``data/tiny_scopes.xplane.pb`` and
``data/tiny_scopes.hlo.txt`` (the program's compiled text, which holds
the scopes) and ``data/tiny_scopes.expected.json`` on a chip: a few executions of one
small jitted program, named ``decode_block``, whose operations sit under
``raytpu.moe.*`` and ``raytpu.mla.*`` scopes, inside a loop, with a
``lax.ragged_dot`` (which the compiler names itself) among them.

    chiprun -- python3 benchmarks/tests/record_scope_fixture.py chiprun_out/fixture

The expected numbers are what ``readers/scope_time.scope_seconds`` gave on
the day, checked by hand against ``trace.describe`` before being committed;
``test_mla_moe_yardstick.py`` holds every later version of the reader to
them.
"""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main(out_dir: str) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks import trace
    from benchmarks.readers import scope_time

    def decode_block(x, w, sizes):
        def body(_, x):
            with jax.named_scope("raytpu.mla.attend"):
                x = jnp.tanh(x @ x.T) @ x
            with jax.named_scope("raytpu.moe.experts"):
                y = jax.lax.ragged_dot(x, w, sizes)
                x = x + jax.nn.silu(y)
            return x * 0.5  # outside every scope

        return jax.lax.fori_loop(0, 3, body, x)

    step = jax.jit(decode_block)
    x = jnp.ones((256, 256), jnp.bfloat16)
    w = jnp.ones((4, 256, 256), jnp.bfloat16) * 0.01
    sizes = jnp.asarray([100, 0, 56, 100], jnp.int32)
    step(x, w, sizes).block_until_ready()
    tmp = os.path.join(out_dir, "raw_scopes")
    trace.start(tmp)
    for _ in range(3):
        step(x, w, sizes).block_until_ready()
    jax.profiler.stop_trace()
    kept = os.path.join(out_dir, "tiny_scopes.xplane.pb")
    shutil.copy(trace.find_xplane(tmp), kept)
    shutil.rmtree(tmp)
    with open(os.path.join(out_dir, "tiny_scopes.describe.txt"), "w") as f:
        f.write(trace.describe(kept, limit=40))
    text = step.lower(x, w, sizes).compile().as_text()
    with open(os.path.join(out_dir, "tiny_scopes.hlo.txt"), "w") as f:
        f.write(text)
    per = scope_time.scope_seconds(kept, {"decode_block": [text]})
    with open(os.path.join(out_dir, "tiny_scopes.expected.json"), "w") as f:
        json.dump(per, f, indent=1, sort_keys=True)
    print(json.dumps(per))


if __name__ == "__main__":
    main(sys.argv[1])
