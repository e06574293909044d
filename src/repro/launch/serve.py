"""Serving launcher: bring up the slot-based engine for an --arch config.

  python -m repro.launch.serve --arch yi-9b --smoke --requests 8

Production path mirrors launch/train.py: mesh + sharded params (TP over
model axis, no FSDP for serving), decode_step jitted once, slots recycled.
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax

from repro import configs
from repro.compile_cache import enable_compile_cache
from repro.dist.sharding import param_shardings, sharding_ctx
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models import transformer as tf
from repro.serve import Request, ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default="host")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--cache-len", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    assert not cfg.embedding_inputs, \
        "embedding-input archs need a frontend driver; use a token arch"
    if args.mesh == "host":
        mesh = make_host_mesh()
    else:
        mesh = make_production_mesh(multi_pod=args.mesh == "2x16x16")

    with mesh, sharding_ctx(mesh, fsdp=False):
        pshapes, axes = tf.abstract_params(cfg)
        pshard = param_shardings(axes, pshapes)
        params = jax.jit(lambda k: tf.init_params(cfg, k)[0],
                         out_shardings=pshard)(jax.random.key(0))
        eng = ServeEngine(cfg, params, batch_slots=args.slots,
                          cache_len=args.cache_len)
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(0, cfg.vocab,
                                            (int(rng.integers(4, 48)),)
                                            ).astype(np.int32),
                        max_new=args.max_new)
                for _ in range(args.requests)]
        t0 = time.perf_counter()
        eng.run(list(reqs))
        dt = time.perf_counter() - t0
        tot = sum(len(r.out) for r in reqs)
        print(f"{args.arch}: {args.requests} reqs, {tot} tokens, "
              f"{dt:.2f}s, {tot / dt:.1f} tok/s")


if __name__ == "__main__":
    main()
