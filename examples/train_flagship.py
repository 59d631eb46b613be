"""Fine-tune the flagship LM with JaxTrainer (the BASELINE north-star shape).

Host only:     python examples/train_flagship.py --size tiny --workers 1
Simulated pod: python examples/train_flagship.py --size tiny --workers 2 \
                   --devices-per-worker 4 --dp 2 --sp 2 --tp 2
One chip:      python examples/train_flagship.py --size bench_400m --use-tpu
Real pod: one worker per TPU VM (the worker group assembles the global mesh
via jax.distributed), again with --use-tpu.

``--use-tpu`` is ``ScalingConfig(use_tpu=True)``: each worker asks for the
``TPU`` resource and is the one process that opens its host's chips. Without
it the workers are pinned to the host's CPU and never touch a chip.
"""

import argparse


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--size", default="tiny",
                   choices=["tiny", "bench_400m", "small_1b", "gptj_6b"])
    p.add_argument("--workers", type=int, default=1)
    p.add_argument("--devices-per-worker", type=int, default=None)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--dp", type=int, default=-1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--use-tpu", action="store_true")
    args = p.parse_args()

    import ray_tpu
    from ray_tpu.parallel.mesh import MeshConfig
    from ray_tpu.train import (
        CheckpointConfig,
        JaxTrainer,
        RunConfig,
        ScalingConfig,
    )

    ray_tpu.init(num_cpus=args.workers + 2)

    def loop(config):
        import jax
        import numpy as np

        from ray_tpu.models.transformer import TransformerConfig
        from ray_tpu.parallel.mesh import MeshConfig
        from ray_tpu.parallel.train_step import (
            batch_sharding,
            default_optimizer,
            make_sharded_state,
            make_train_step,
        )
        from ray_tpu.train import Checkpoint, session

        mesh = session.make_mesh(MeshConfig(**config["mesh"]))
        cfg = getattr(TransformerConfig, config["size"])()
        if config["mesh"]["sp"] > 1:
            import dataclasses

            cfg = dataclasses.replace(cfg, attn_impl="ring")
        opt = default_optimizer()
        state, state_sh = make_sharded_state(cfg, mesh, opt, jax.random.key(0))
        step = make_train_step(cfg, mesh, opt, state_sh)

        rank, world = session.get_world_rank(), session.get_world_size()
        rng = np.random.RandomState(rank)
        local_batch, seq = max(1, 8 // world), min(cfg.max_seq_len, 512)
        for i in range(config["steps"]):
            tokens = rng.randint(
                0, cfg.vocab_size, (local_batch, seq)
            ).astype(np.int32)
            batch = session.distribute_batch(
                {"tokens": tokens, "targets": tokens,
                 "mask": np.ones_like(tokens, np.float32)},
                mesh, spec=batch_sharding(mesh).spec,
            )
            state, m = step(state, batch)
            session.report(
                {"step": i, "loss": float(m["loss"])},
                checkpoint=(
                    Checkpoint.from_dict({"step": i}) if rank == 0 else None
                ),
            )

    result = JaxTrainer(
        loop,
        train_loop_config={
            "size": args.size,
            "steps": args.steps,
            "mesh": {"dp": args.dp, "pp": 1, "ep": 1,
                     "sp": args.sp, "tp": args.tp},
        },
        scaling_config=ScalingConfig(
            num_workers=args.workers,
            devices_per_worker=args.devices_per_worker,
            use_tpu=args.use_tpu,
        ),
        run_config=RunConfig(
            name="flagship",
            checkpoint_config=CheckpointConfig(num_to_keep=2),
        ),
    ).fit()
    print("final:", result.metrics)


if __name__ == "__main__":
    main()
