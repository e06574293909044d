"""End-to-end trajectory bench: session API vs hand-stitched pipeline,
single-scene vs batched, per indexing engine — persisted to BENCH_e2e.json
(same accumulate-history contract as BENCH_dataflow/BENCH_indexing).

The claim under test: the SpiraSession front door (bucketing + plan + feature
pass fused in one jitted graph) costs nothing over the hand-stitched
``build_network_plan`` + ``pointcloud_forward`` baseline — both run at the
same bucketed capacity so the comparison is graph-vs-graph, not
padding-vs-no-padding. Batching B scenes into one call amortizes per-call
dispatch/compile overhead; on a compute-bound CPU host the batched graph is
work-dominated (per-scene BN now costs O(N) via the segmented-reduction
engine, independent of S, but the conv work itself is what dominates), so
the ``batch_amortization`` row is the quantity to watch on real TPUs, not
here.

Off-TPU the ``zdelta_pallas`` rows time the Pallas interpreter (relative
cost only, see benchmarks/common.py) and are restricted to the smoke-sized
scene.
"""
from __future__ import annotations

import argparse
import os

import jax
import numpy as np

from repro.core import SparseTensor, build_network_plan
from repro.data import scenes
from repro.kernels import ops
from repro.models import pointcloud as pc
from repro.obs import MetricsRegistry
from repro.serve import compile_network
from repro.serve.bucketing import bucket_capacity
from .common import append_history, emit, timeit, us

RESULTS = os.path.join(os.path.dirname(__file__), "..", "BENCH_e2e.json")


def _clouds(B, kind, extent, seed=0):
    batch = scenes.scene_batch(seed=seed, batch=B, kind=kind, extent=extent,
                               overlap=0.5)
    rng = np.random.default_rng(seed + 1)
    return batch[0].layout, [
        (sc.coords, rng.normal(size=(len(sc.coords), 4)).astype(np.float32))
        for sc in batch]


def run(smoke: bool = False):
    B = 2 if smoke else 4
    net = pc.sparse_resnet21(in_channels=4, n_classes=20)
    small = _clouds(B, "indoor", (48, 40, 24))
    full = small if smoke else _clouds(B, "indoor", (96, 80, 36))
    rows, engines_rec = [], {}
    reg = MetricsRegistry()   # per-repeat latencies → percentile export

    for engine in ["zdelta", "zdelta_pallas"]:
        # interpreter off-TPU: keep the pallas engine to the small scene
        layout, clouds = (small if engine != "zdelta"
                          and not ops.on_tpu() else full)
        session = compile_network(net, layout, batch=B, engine=engine)
        st1 = SparseTensor.from_point_clouds(clouds[:1], session.layout)
        st_b = SparseTensor.from_point_clouds(clouds, session.layout)

        # hand-stitched baseline at the SAME bucketed capacity: one jitted
        # plan+forward graph, padded input — what callers wrote pre-session.
        cap = bucket_capacity(st1.capacity)
        stp = st1.pad_to(cap)
        specs = session.net.conv_specs()

        @jax.jit
        def hand(packed, feats, specs=specs, lo=session.layout, eng=engine):
            plan = build_network_plan(packed, specs=specs, layout=lo,
                                      engine=eng)
            return pc.pointcloud_forward(session.params, session.net, plan,
                                         feats, layout=lo)

        t_hand = timeit(lambda: hand(stp.packed, stp.features), repeats=3,
                        warmup=1, registry=reg,
                        name=f"e2e/{engine}/hand_single")
        t_sess1 = timeit(lambda: session(st1).features, repeats=3, warmup=1,
                         registry=reg, name=f"e2e/{engine}/session_single")
        t_sessb = timeit(lambda: session(st_b).features, repeats=3, warmup=1,
                         registry=reg, name=f"e2e/{engine}/session_batched")

        rec = {
            "sizes": [len(c) for c, _ in clouds],
            "hand_stitched_single_us": us(t_hand),
            "session_single_us": us(t_sess1),
            "session_batched_us": us(t_sessb),
            "session_batched_per_scene_us": us(t_sessb / B),
            "session_vs_hand": round(t_hand / t_sess1, 3),
            "batch_amortization": round(t_sess1 / (t_sessb / B), 3),
        }
        engines_rec[engine] = rec
        rows.append((f"e2e/{engine}/hand_single", us(t_hand), ""))
        rows.append((f"e2e/{engine}/session_single", us(t_sess1),
                     f"vs_hand={rec['session_vs_hand']}"))
        rows.append((f"e2e/{engine}/session_batched_per_scene",
                     us(t_sessb / B),
                     f"amortization={rec['batch_amortization']}"))

    rec = {
        "host_backend": jax.default_backend(),
        "net": net.name,
        "batch": B,
        "smoke": smoke,
        "note": ("session and baseline run at the same bucketed capacity; "
                 "pallas rows interpret off-TPU and use the small scene"),
        "engines": engines_rec,
        # per-row latency percentiles from the timing loop (repro.obs)
        "metrics": reg.snapshot(),
    }
    append_history(RESULTS, rec)
    emit(rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    a = ap.parse_args()
    run(smoke=a.smoke)
