"""Serve the flagship LM with batched generation + HTTP ingress.

python examples/serve_llm.py --size tiny --replicas 1
python examples/serve_llm.py --size small_1b --use-tpu
Then: curl -X POST http://<addr>/LM -d '[1,2,3,4]'  (one prompt per request;
the router groups concurrent requests into step batches)

``--use-tpu`` deploys each replica with ``num_tpus=1``: its worker is the one
process that opens its chip, so a one-chip host takes one replica. Without
it the replicas serve from the host.
"""

import argparse
import json
import urllib.request


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny")
    p.add_argument("--replicas", type=int, default=1)
    p.add_argument("--max-new-tokens", type=int, default=16)
    p.add_argument("--use-tpu", action="store_true")
    args = p.parse_args()

    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=args.replicas + 2)

    @serve.deployment(
        num_replicas=args.replicas, batch_max_size=8,
        batch_wait_timeout_s=0.02,
        ray_actor_options={"num_tpus": 1} if args.use_tpu else None,
    )
    class LM:
        def __init__(self, size, max_new):
            import jax

            from ray_tpu.models.generation import prepare_for_inference
            from ray_tpu.models.transformer import (
                TransformerConfig,
                init_params,
            )

            self.cfg = getattr(TransformerConfig, size)()
            params = jax.jit(
                lambda k: init_params(self.cfg, k)
            )(jax.random.key(0))
            self.params, self.cfg = prepare_for_inference(params, self.cfg)
            self.max_new = max_new

        def __call__(self, prompts):
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models.generation import generate

            width = max(len(p) for p in prompts)
            batch = np.zeros((len(prompts), width), np.int32)
            for i, prm in enumerate(prompts):
                batch[i, -len(prm):] = prm  # left-pad
            out = generate(
                self.params, jnp.asarray(batch), self.cfg,
                max_new_tokens=self.max_new,
            )
            return [np.asarray(r).tolist() for r in out]

    serve.run(LM.bind(args.size, args.max_new_tokens))
    base = serve.start_http_proxy()
    print("serving at", base + "/LM")
    req = urllib.request.Request(
        f"{base}/LM", data=json.dumps([1, 2, 3, 4]).encode()
    )
    print("sample:", json.loads(urllib.request.urlopen(req).read()))


if __name__ == "__main__":
    main()
