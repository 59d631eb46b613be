"""Continuous-batching LLM serving with token streaming over HTTP.

The round-3 serving path: an LLMServer deployment runs the
iteration-level engine (fixed decode-slot pool over a carried KV cache;
requests admitted between compiled multi-step decode blocks), and tokens
stream replica -> handle -> chunked HTTP as they are produced.

    python examples/serve_llm_streaming.py --size tiny
    python examples/serve_llm_streaming.py --size small_1b --use-tpu
    curl -N -X POST http://<addr>/LLM/stream -d '[1,2,3,4,5]'

``--use-tpu`` deploys the replica with ``num_tpus=1``: its worker is the one
process that opens the chip. Without it the replica serves from the host.
"""

import argparse
import json
import urllib.request


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny", choices=["tiny", "small_1b"])
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--use-tpu", action="store_true")
    args = p.parse_args()

    import ray_tpu
    from ray_tpu import serve

    # controller(0.1) + replica(1) + proxy(0.1) must fit
    ray_tpu.init(num_cpus=4)

    size = args.size

    def model_factory(_size=size):
        import jax

        from ray_tpu.models.transformer import TransformerConfig, init_params

        cfg = getattr(TransformerConfig, _size)()
        params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(0))
        return params, cfg

    max_len = 128 if size == "tiny" else 512
    buckets = (16, 32) if size == "tiny" else (128, 256)

    actor_options = {"max_concurrency": 8}
    if args.use_tpu:
        actor_options["num_tpus"] = 1

    @serve.deployment(num_replicas=1, ray_actor_options=actor_options)
    class LLM(serve.LLMServer):
        def __init__(self):
            super().__init__(model_factory, max_slots=2, max_len=max_len,
                             prefill_buckets=buckets)

    handle = serve.run(LLM.bind())
    base = serve.start_http_proxy()
    print(f"serving at {base}/LLM (POST a JSON token list; /stream chunks)")

    # demo request through the streaming HTTP path
    req = urllib.request.Request(
        f"{base}/LLM/stream",
        data=json.dumps([1, 2, 3, 4, 5]).encode(),
        headers={"Content-Type": "application/json"},
    )
    toks = []
    with urllib.request.urlopen(req, timeout=300) as resp:
        for line in resp:
            if line.strip():
                toks.append(json.loads(line)["chunk"])
                print(f"\rtokens: {len(toks)}", end="")
    print(f"\nstreamed {len(toks)} tokens: {toks[:10]}...")
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
