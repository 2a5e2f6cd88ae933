"""Production training launcher.

Brings up the mesh, shards the TrainState per the logical rules, runs the
jitted train step with F2P gradient compression, writes checkpoints
asynchronously off the critical path, and survives preemption: on restart it
resumes from the last committed step — on a DIFFERENT mesh shape if needed
(elastic rescale; checkpoints are mesh-agnostic host arrays).

    PYTHONPATH=src python -m repro.launch.train --arch xlstm_125m \
        --steps 100 --mesh-shape 2,2 --ckpt-dir /tmp/run1

The mesh spans the first data*model devices. On the CPU, tests give it
virtual host devices via XLA_FLAGS=--xla_force_host_platform_device_count;
on TPU the same script runs the full configs unchanged.
"""
import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm_125m")
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (default: smoke)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--mesh-shape", default="1,1",
                    help="data,model mesh over the first data*model devices")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--die-at-step", type=int, default=-1,
                    help="simulate preemption (exit hard at this step)")
    ap.add_argument("--no-compress", action="store_true")
    args = ap.parse_args()

    shape = tuple(int(x) for x in args.mesh_shape.split(","))

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import full_config, smoke_config
    from repro.data import DataConfig, host_batch
    from repro.launch.shardings import rules_for, train_state_sds
    from repro.models.sharding import logical_rules
    from repro.optim import AdamWConfig, CompressionConfig
    from repro.train import checkpoint, init_train_state, make_train_step
    from repro.train.async_ckpt import AsyncCheckpointer

    from repro.configs import default_policy

    cfg = full_config(args.arch) if args.full else smoke_config(args.arch)
    ocfg = AdamWConfig(lr=1e-3, warmup_steps=10, total_steps=args.steps)
    # formats come from the arch's default FormatPolicy (configs.registry),
    # not inline constants — per-model tuning lives in ONE place
    policy = default_policy(args.arch)
    gfmt, gblock = policy.f2p_for("grad", (CompressionConfig.fmt, 128))
    ccfg = CompressionConfig(enabled=not args.no_compress, min_size=512,
                             fmt=gfmt, block=gblock)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.global_batch)

    from repro.launch.mesh import make_mesh

    mesh = make_mesh(shape, ("data", "model"),
                     devices=jax.devices()[:shape[0] * shape[1]])
    rules = rules_for(cfg, mesh, "train_4k")
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name} "
          f"({cfg.param_count()/1e6:.1f}M params)")

    with logical_rules(rules, mesh):
        state = init_train_state(cfg, ocfg, ccfg, jax.random.PRNGKey(0))
        # shard the freshly-initialized state
        sds, specs = train_state_sds(cfg, ocfg, ccfg, mesh, rules)
        shardings = jax.tree.map(lambda s: s.sharding, sds)
        state = jax.tree.map(
            lambda a, s: jax.device_put(a, s), state, shardings)

        start = checkpoint.latest_step(args.ckpt_dir)
        if start is not None:
            # elastic restore: host arrays -> current mesh shardings
            state, start = checkpoint.restore(args.ckpt_dir, state,
                                              shardings=shardings)
            print(f"resumed from step {start} (elastic remesh ok)")
        else:
            start = 0
            os.makedirs(args.ckpt_dir, exist_ok=True)

        step_fn = jax.jit(make_train_step(cfg, ocfg, ccfg), donate_argnums=0)
        ckpt = AsyncCheckpointer(args.ckpt_dir, keep=3, policy=policy)
        for step in range(start, args.steps):
            if step == args.die_at_step:
                # a preemption notice's grace period lets the checkpoint
                # already in flight commit; then the process dies hard (no
                # final save), so the restart resumes from the last step
                # enqueued before the notice, not from whichever write
                # happened to win the race against the exit
                ckpt.wait()
                print(f"SIMULATED PREEMPTION at step {step}", flush=True)
                os._exit(42)
            batch = host_batch(dcfg, step)
            state, m = step_fn(state,
                               {k: jnp.asarray(v) for k, v in batch.items()})
            if step % 10 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(m['loss']):.4f} "
                      f"gnorm {float(m['grad_norm']):.3f}", flush=True)
            if step > 0 and step % args.ckpt_every == 0:
                ckpt.save(step, state)   # async, off the critical path
        ckpt.save(args.steps, state)
        ckpt.wait()
        print("done.")


if __name__ == "__main__":
    main()
