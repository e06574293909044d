#!/usr/bin/env python3
"""Chip smoke test: MinkUNet-42 serves and trains on one TPU.

    python3 chip_smoke.py

Run it from the root of a checkout on a machine with a TPU. It drives the
user-facing entry points once, in this one process, with random weights
from a fixed seed:

* serve — 8 outdoor LiDAR-scale scans (1024 x 1024 x 40 voxel extent) go
  through ``compile_network`` -> ``SpiraSession`` -> ``PointCloudServeEngine``
  in batches of 4, once to warm up and once more. Every request must end
  ``ok`` with finite logits on all of its voxels.
* correctness — one batch through the network as served (Pallas kernels)
  and through the same network with every layer on the XLA backend, both
  at fp32 matmul precision; the logits must agree within ``LOGIT_TOL``.
* train — 3 ``PointCloudTrainer`` steps on 2 labelled outdoor scans; every
  loss must be finite.

The last line of standard output is ``{"ok": true, "device": {...}}``. A
failing phase prints its traceback and the script exits 1 without that
line. Without a TPU it exits 2 before any work: there is no CPU fallback.
Wall times it prints are host-clock times around blocking calls, not
device metrics.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

SERVE_SCENES = dict(kind="outdoor", extent=(1024, 1024, 40), batch=8,
                    overlap=0.3)
SERVE_BATCH = 4
# Two scans of the serving size (2 x ~76k voxels, capacity bucket 262144)
# need 20.0 GB of HBM for a MinkUNet-42 train step by the TPU compiler's
# count, more than the 15.75 GB of a v5e; 768 x 768 scans land in the
# 131072 bucket.
TRAIN_SCENES = dict(kind="outdoor", extent=(768, 768, 40), batch=2,
                    overlap=0.3)
TRAIN_STEPS = 3

# Pallas-vs-XLA logit agreement, as a fraction of the largest reference
# logit. Both paths run at fp32 matmul precision and accumulate in fp32, so
# they differ only in summation order. On CPU, reassociating MinkUNet-42's
# sums (fused vs per-offset XLA GEMMs) moves its logits by 6e-5 of their
# range, and a 1e-6 relative perturbation of the inputs by 2.2e-4: the 42
# layers with BN amplify rounding about 200-fold. 1e-2 leaves that 50-fold
# headroom, while a wrong gather or weight gives O(1) differences and a
# stray single bf16 pass (2^-9 relative per product) gives O(0.1).
LOGIT_TOL = 1e-2


def _log(msg: str) -> None:
    print(msg, flush=True)


def _log_peak_memory(phase: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        _log(f"{phase}: device peak bytes in use {stats['peak_bytes_in_use']}")


def _clouds(scenes, channels):
    from repro.train.pointcloud import scene_features
    return [(sc.coords, scene_features(sc, channels)) for sc in scenes]


def _check_served(reqs, scenes, n_classes) -> None:
    """Every request ``ok`` with finite logits on every voxel of its scene.
    The engine returns normally even when a batch failed (it quarantines
    the requests), so its return says nothing by itself."""
    for i, (r, sc) in enumerate(zip(reqs, scenes)):
        if r.outcome != "ok":
            raise RuntimeError(f"request {i} ended {r.outcome!r}: {r.error}")
        want = (len(np.unique(sc.coords, axis=0)), n_classes)
        if r.logits is None or r.logits.shape != want:
            raise RuntimeError(f"request {i}: logits shape "
                               f"{None if r.logits is None else r.logits.shape}"
                               f", want {want}")
        if not np.isfinite(r.logits).all():
            raise RuntimeError(f"request {i}: non-finite logits")


def serve_phase(net, scenes, *, batch: int, seed: int = 0) -> dict:
    """Serve ``scenes`` twice through the engine over a session compiled for
    ``batch`` scenes per call; returns the session and the timed round."""
    import jax
    from repro.core.sparse_tensor import SparseTensor
    from repro.serve import (PointCloudRequest, PointCloudServeEngine,
                             compile_network)

    session = compile_network(net, scenes[0].layout, batch=batch,
                              key=jax.random.key(seed))
    clouds = _clouds(scenes, net.in_channels)
    # A direct call compiles the bucket and raises if compilation fails;
    # the engine would quarantine the requests and return.
    t = time.perf_counter()
    out = session(SparseTensor.from_point_clouds(clouds[:batch],
                                                 session.layout))
    jax.block_until_ready(out.features)
    _log(f"serve: first call (compile + run) {time.perf_counter() - t:.1f} s "
         "wall")
    engine = PointCloudServeEngine(session)
    for label in ("warm-up", "timed"):
        reqs = [PointCloudRequest(coords=c, features=f) for c, f in clouds]
        t = time.perf_counter()
        engine.run(reqs)
        wall = time.perf_counter() - t
        _check_served(reqs, scenes, net.n_classes)
        _log(f"serve: {label} round, {len(reqs)} requests ok, "
             f"{wall / len(reqs):.3f} s per scene (wall, not a device metric)")
    health = reqs[0].health
    _log(f"serve: voxels per scene {[len(r.logits) for r in reqs]}")
    _log(f"serve: bucket {health.bucket}, compile_count "
         f"{session.compile_count}, window-overflow cells "
         f"{sum(health.window_overflow_cells.values())}, WS dropped pairs "
         f"{health.total_ws_dropped}")
    _log_peak_memory("serve")
    return {"session": session, "requests": reqs}


def correctness_phase(net, scenes, *, batch: int, seed: int = 0) -> dict:
    """One batch through ``net`` and through ``net`` with every layer on
    the XLA backend, both at fp32 matmul precision; compare logits."""
    import jax
    from repro.core.sparse_tensor import SparseTensor
    from repro.serve import compile_network

    xla_net = dataclasses.replace(net, specs=tuple(
        dataclasses.replace(s, backend="xla") for s in net.specs))
    with jax.default_matmul_precision("highest"):
        ref = compile_network(xla_net, scenes[0].layout, batch=batch,
                              key=jax.random.key(seed))
        got = compile_network(net, scenes[0].layout, batch=batch,
                              params=ref.params)
        st = SparseTensor.from_point_clouds(
            _clouds(scenes[:batch], net.in_channels), ref.layout)
        n = int(st.count)
        want = np.asarray(ref(st).features)[:n]
        have = np.asarray(got(st).features)[:n]
    if not (np.isfinite(want).all() and np.isfinite(have).all()):
        raise RuntimeError("correctness: non-finite logits")
    max_abs = float(np.abs(have - want).max())
    max_rel = max_abs / max(float(np.abs(want).max()), 1e-30)
    _log(f"correctness: {n} voxels, Pallas vs XLA logits max abs diff "
         f"{max_abs:.3e}, max rel diff {max_rel:.3e} (of the largest "
         f"reference logit; tolerance {LOGIT_TOL:.0e})")
    if max_rel > LOGIT_TOL:
        raise RuntimeError(f"correctness: max rel diff {max_rel:.3e} > "
                           f"{LOGIT_TOL:.0e}")
    return {"max_abs": max_abs, "max_rel": max_rel}


def train_phase(net, scenes, *, steps: int, seed: int = 0) -> list:
    """``steps`` trainer steps on the labelled ``scenes`` as one batch;
    returns the losses, which must all be finite."""
    import jax
    from repro.serve import compile_network
    from repro.train.pointcloud import labeled_batch

    session = compile_network(net, scenes[0].layout, batch=len(scenes),
                              key=jax.random.key(seed))
    trainer = session.compile_train()
    st, labels = labeled_batch(scenes, session.layout,
                               channels=net.in_channels)
    losses = []
    for i in range(steps):
        t = time.perf_counter()
        loss = trainer.step(st, labels)["loss"]
        _log(f"train: step {i} loss {loss:.6f}, "
             f"{time.perf_counter() - t:.1f} s wall"
             f"{' (includes compile)' if i == 0 else ''}")
        losses.append(loss)
    if not np.isfinite(losses).all():
        raise RuntimeError(f"train: non-finite losses {losses}")
    _log(f"train: {int(st.count)} voxels in bucket "
         f"{session._bucket(st.capacity)}, compile_count "
         f"{trainer.compile_count}")
    return losses


def main() -> int:
    import jax

    _log(f"jax {jax.__version__}")
    devices = jax.devices()
    dev = devices[0]
    _log(f"devices: platform={dev.platform} kind={dev.device_kind} "
         f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; nothing is run off the chip",
              file=sys.stderr)
        return 2

    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    from repro.data.scenes import scene_batch
    from repro.models.pointcloud import minkunet42

    _log(f"compile cache: {enable_compile_cache()}")
    net = minkunet42()
    t0 = time.perf_counter()
    try:
        serve_scenes = scene_batch(seed=0, **SERVE_SCENES)
        serve_phase(net, serve_scenes, batch=SERVE_BATCH)
        correctness_phase(net, serve_scenes, batch=SERVE_BATCH)
        train_scenes = scene_batch(seed=1, labels=True,
                                   n_classes=net.n_classes, **TRAIN_SCENES)
        train_phase(net, train_scenes, steps=TRAIN_STEPS)
    except Exception:
        traceback.print_exc()
        return 1
    _log_peak_memory("all phases")
    _log(f"total {time.perf_counter() - t0:.1f} s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
