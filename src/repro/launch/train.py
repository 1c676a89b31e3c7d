"""Training launcher: ``python -m repro.launch.train --arch <id> [--reduced] ...``

End-to-end driver: config → mesh → sharded state → data pipeline → train loop
with async checkpointing, crash-restart, and optional sketched gradient
compression (the paper's technique as a distributed-optimization feature).

On this CPU container use ``--reduced --devices N`` (forced host devices);
on a real cluster drop both and let jax see the TPU slice.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--devices", type=int, default=0, help="force N host devices (CPU)")
    ap.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--grad-compress-gamma", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", "")
        )

    import jax
    import jax.numpy as jnp

    from repro.configs.registry import get_arch
    from repro.core.grad_compress import CompressConfig
    from repro.data.pipeline import SyntheticLMSource
    from repro.launch import compile_cache
    from repro.launch.mesh import make_host_mesh, make_production_mesh
    from repro.models.api import get_api
    from repro.train import checkpoint
    from repro.train.optimizer import OptConfig
    from repro.train.trainer import (TrainerConfig, abstract_state, init_state,
                                     make_dist, make_train_fn, state_shardings)

    compile_cache.enable()

    cfg = get_arch(args.arch, reduced=args.reduced)
    api = get_api(cfg)
    if args.mesh == "host":
        n = len(jax.devices())
        mesh = make_host_mesh(max(1, n // 2), min(2, n)) if n > 1 else None
    else:
        mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))

    compress = None
    if args.grad_compress_gamma > 0:
        compress = CompressConfig(gamma=args.grad_compress_gamma)
    tcfg = TrainerConfig(
        opt=OptConfig(peak_lr=args.lr, warmup_steps=max(1, args.steps // 20),
                      total_steps=args.steps),
        accum_steps=args.accum, compress=compress,
        q_chunk=min(512, args.seq), kv_chunk=min(1024, args.seq),
        sp=mesh is not None,
    )
    key = jax.random.PRNGKey(args.seed)
    dist = make_dist(mesh, cfg, sp=tcfg.sp)
    fn = make_train_fn(api, tcfg, dist, key)

    state_specs = abstract_state(api, tcfg)
    if mesh is not None:
        shardings = state_shardings(state_specs, mesh)
        step_fn = jax.jit(fn, donate_argnums=0, out_shardings=(shardings, None))
        state = jax.device_put(init_state(api, tcfg, key), shardings)
    else:
        shardings = None
        step_fn = jax.jit(fn, donate_argnums=0)
        state = init_state(api, tcfg, key)

    source = SyntheticLMSource(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    start_step = 0
    if args.ckpt_dir:
        try:
            state, extra = checkpoint.restore(args.ckpt_dir, state_specs, shardings)
            start_step = int(extra.get("pipeline", {}).get("step", 0))
            source.state.step = start_step
            print(f"restored checkpoint at step {start_step}")
        except FileNotFoundError:
            pass

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = source.next_batch()
        if cfg.family == "vlm":
            B, S = batch["tokens"].shape
            pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
            batch["positions"] = jnp.broadcast_to(pos[None], (3, B, S))
            batch["vision_embeds"] = jnp.zeros((B, cfg.n_vision_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
        if cfg.family == "audio":
            B, S = batch["tokens"].shape
            fk = jax.random.fold_in(key, step)
            batch["frames"] = 0.1 * jax.random.normal(fk, (B, S, cfg.d_model), jnp.dtype(cfg.dtype))
        state, metrics = step_fn(state, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            loss = float(metrics["loss"])
            print(f"step {step:5d} loss {loss:.4f} gnorm {float(metrics['grad_norm']):.3f} "
                  f"lr {float(metrics['lr']):.2e} ({(time.time()-t0):.1f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            checkpoint.save(args.ckpt_dir, step + 1, state,
                            extra={"pipeline": source.state.to_json()})
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps, state,
                        extra={"pipeline": source.state.to_json()}, async_=False)
    print("done")


if __name__ == "__main__":
    main()
