"""Production training launcher: mesh + sharded init + fault-tolerant loop.

Single entry point for both real clusters and local runs:

  python -m repro.launch.train --arch qwen3-moe-30b-a3b --steps 1000 \
      [--smoke] [--mesh 16x16|2x16x16|host] [--resume]

On a TPU pod slice this process runs per-host under the same jit/SPMD code
the dry-run compiles (jax.distributed.initialize when JAX_COORDINATOR is
set); on this CPU container use --smoke --mesh host.
"""
from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.ckpt import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.data.tokens import DataConfig, batch_at
from repro.dist.sharding import param_shardings, sharding_ctx
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.train import (AdamWConfig, TrainConfig, init_opt_state,
                         make_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="host")
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fsdp", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize()  # multi-host pod entry

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                      global_batch=args.global_batch, seed=0,
                      embed_dim=cfg.d_model if cfg.embedding_inputs else 0,
                      embed_prefix=args.seq_len // 4 if cfg.embedding_inputs else 0)
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-4, total_steps=args.steps),
                       remat=not args.smoke, ckpt_every=50)
    mgr = CheckpointManager(args.ckpt_dir, keep=3)

    with mesh, sharding_ctx(mesh, fsdp=args.fsdp):
        pshapes, axes = tf.abstract_params(cfg)
        pshard = param_shardings(axes, pshapes)
        init_fn = jax.jit(lambda k: tf.init_params(cfg, k)[0],
                          out_shardings=pshard)
        params = init_fn(jax.random.key(0))
        oshapes = jax.eval_shape(lambda p: init_opt_state(p, tcfg.opt), pshapes)
        oshard = type(oshapes)(mu=param_shardings(axes, oshapes.mu),
                               nu=param_shardings(axes, oshapes.nu),
                               step=NamedSharding(mesh, P()))
        opt = jax.jit(lambda p: init_opt_state(p, tcfg.opt),
                      out_shardings=oshard)(params)
        start = 0
        if args.resume and mgr.latest_step() is not None:
            params, opt, start = mgr.restore(None, pshapes, oshapes,
                                             shardings=pshard,
                                             opt_shardings=oshard)
            start += 1
            print(f"resumed from step {start - 1}")

        step_fn = jax.jit(make_train_step(cfg, tcfg), donate_argnums=(0, 1))
        bshard = NamedSharding(mesh, P(
            tuple(a for a in ("pod", "data") if a in mesh.axis_names)))
        for step in range(start, args.steps):
            batch = jax.tree.map(
                lambda x: jax.device_put(jnp.asarray(x), bshard),
                batch_at(dcfg, step))
            params, opt, metrics = step_fn(params, opt, batch)
            if step % tcfg.log_every == 0:
                print(f"step {step} loss {float(metrics['loss']):.4f}")
            if step % tcfg.ckpt_every == 0 or step == args.steps - 1:
                mgr.save(step, params, opt)
        mgr.wait()
        print(f"done; checkpoints: {mgr.steps()}")


if __name__ == "__main__":
    main()
