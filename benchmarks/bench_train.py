"""Training-step trajectory bench — persisted to BENCH_train.json (same
accumulate-history contract as BENCH_e2e/BENCH_dataflow/BENCH_indexing).

Quantities under test, per engine:

* ``fwd_us`` vs ``step_us`` — forward-only session call vs full fused
  plan→forward→loss→grad→update step at the same bucketed capacity. Their
  ratio (``bwd_over_fwd``) is the whole cost of differentiation; the
  kernel-map-transposed VJPs keep it in GEMM territory (the backward is the
  same dataflows over transposed maps — no extra searches, no gathered
  intermediate), so it should sit near the classic ~2–3× of dense nets,
  not blow up with indexing work.
* ``plan_us`` and ``plan_share_of_step`` — the network plan's share of one
  train step. Both forward and backward consume ONE plan per step
  (Minuet's amortization argument applied inside the step); a
  backward-side re-index would double this share.
* ``steps_to_amortize_compile`` — compile cost of the fused train graph
  over the steady-state step, the plan-ahead trade training buys into.
* per-stage BN breakdown — ``bn_us_segment`` vs ``bn_us_sliced`` times one
  level-0 BN application (fwd + bwd, the stage's full train-step cost)
  under the O(N) segment engine vs the retired O(S·cap) sliced
  formulation, at the session's real scene segmentation (S = batch = 4,
  the acceptance regime). ``bn_share_of_step`` projects the segment
  engine's BN stage over all layers against the measured step;
  ``bn_share_of_step_sliced`` is the same projection for the sliced
  baseline — the gap is what the segmented-reduction engine removed from
  the step.

Off-TPU the ``zdelta_pallas`` row times the Pallas interpreter (relative
cost only, see benchmarks/common.py) and is restricted to smoke size.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.data import scenes
from repro.kernels import ops
from repro.models import pointcloud as pc
from repro.obs import MetricsRegistry
from repro.serve import compile_network
from repro.train.pointcloud import PointCloudTrainConfig, labeled_batch
from .common import append_history, emit, timeit, us

RESULTS = os.path.join(os.path.dirname(__file__), "..", "BENCH_train.json")


def _bn_stage_times(session, st, width):
    """(t_segment, t_sliced): one level-0 BN application, fwd + bwd, at the
    session's real scene segmentation."""
    plan = session.plan(st)
    seg0 = pc.level_segments(plan, session.layout)[0]
    cap0 = plan.coords[0].capacity
    count0 = plan.coords[0].count
    x = jax.random.normal(jax.random.key(3), (cap0, width))

    def seg_loss(v):
        return jnp.vdot(pc._relu_bn(v, count0, seg0,
                                    segment=session.segment), v)

    def sliced_loss(v):
        return jnp.vdot(pc._relu_bn_sliced(v, count0, seg0), v)

    t_seg = timeit(jax.jit(jax.grad(seg_loss)), x, repeats=5, warmup=1)
    t_sliced = timeit(jax.jit(jax.grad(sliced_loss)), x, repeats=5, warmup=1)
    return t_seg, t_sliced


def run(smoke: bool = False):
    B = 4           # S >= 4: the regime the segment engine is priced in
    extent = (48, 40, 24) if smoke else (64, 48, 24)
    n_classes = 8
    batch = scenes.scene_batch(seed=0, batch=B, kind="indoor", extent=extent,
                               labels=True, n_classes=n_classes)
    net = pc.tiny_segnet(in_channels=4, n_classes=n_classes) if smoke \
        else pc.minkunet42(in_channels=4, n_classes=n_classes)
    rows, engines_rec = [], {}
    reg = MetricsRegistry()   # per-repeat latencies → percentile export
    engines = ["zdelta", "zdelta_pallas"]
    if not smoke and not ops.on_tpu():
        engines = ["zdelta"]   # interpreter-priced pallas only at smoke size

    for engine in engines:
        session = compile_network(net, batch[0].layout, batch=B,
                                  engine=engine)
        trainer = session.compile_train(PointCloudTrainConfig())
        st, labels = labeled_batch(batch, session.layout)

        t0 = time.perf_counter()
        trainer.step(st, labels)                  # compile + first step
        compile_s = time.perf_counter() - t0
        t_step = timeit(lambda: trainer.step(st, labels), repeats=5, warmup=1,
                        registry=reg, name=f"train/{engine}/step")
        # the self-healing wrapper (train.guard): same fused step plus one
        # in-graph isfinite flag + per-leaf selects and the host-side
        # ladder bookkeeping — guard_overhead prices "always-on" safety
        gtrainer = session.compile_train(PointCloudTrainConfig(), guard=True)
        gtrainer.step(st, labels)                 # compile the guarded graph
        t_gstep = timeit(lambda: gtrainer.step(st, labels),
                         repeats=5, warmup=1, registry=reg,
                         name=f"train/{engine}/guarded_step")
        t_fwd = timeit(lambda: session(st).features, repeats=5, warmup=1,
                       registry=reg, name=f"train/{engine}/fwd")
        t_plan = timeit(lambda: session.plan(st).coords[0].packed,
                        repeats=5, warmup=1, registry=reg,
                        name=f"train/{engine}/plan")
        t_bn_seg, t_bn_sliced = _bn_stage_times(session, st,
                                                net.specs[0].cout)
        n_bn = len(net.specs)

        rec = {
            "voxels": int(st.count),
            "scenes": B,
            "plan_us": us(t_plan),
            "fwd_us": us(t_fwd),
            "step_us": us(t_step),
            "guarded_step_us": us(t_gstep),
            "guard_overhead": round(t_gstep / t_step, 3),
            "bwd_over_fwd": round(t_step / t_fwd, 3),
            "plan_share_of_step": round(t_plan / t_step, 3),
            "bn_us_segment": us(t_bn_seg),
            "bn_us_sliced": us(t_bn_sliced),
            "segment_vs_sliced_bn": round(t_bn_sliced / t_bn_seg, 2),
            "bn_share_of_step": round(n_bn * t_bn_seg / t_step, 3),
            "bn_share_of_step_sliced": round(n_bn * t_bn_sliced / t_step, 3),
            "compile_s": round(compile_s, 2),
            "steps_to_amortize_compile": round(compile_s / t_step, 1),
        }
        engines_rec[engine] = rec
        rows.append((f"train/{engine}/plan", us(t_plan),
                     f"share_of_step={rec['plan_share_of_step']}"))
        rows.append((f"train/{engine}/fwd", us(t_fwd), ""))
        rows.append((f"train/{engine}/step", us(t_step),
                     f"bwd_over_fwd={rec['bwd_over_fwd']}"))
        rows.append((f"train/{engine}/guarded_step", us(t_gstep),
                     f"overhead={rec['guard_overhead']}"))
        rows.append((f"train/{engine}/bn_segment", us(t_bn_seg),
                     f"share_of_step={rec['bn_share_of_step']}"))
        rows.append((f"train/{engine}/bn_sliced", us(t_bn_sliced),
                     f"segment_speedup={rec['segment_vs_sliced_bn']}"))

    rec = {
        "host_backend": jax.default_backend(),
        "net": net.name,
        "batch": B,
        "smoke": smoke,
        "note": ("step = fused plan+forward+loss+grad+update at the session's "
                 "bucketed capacity; fwd = forward-only session call at the "
                 "same capacity; one plan serves both directions (transposed-"
                 "map VJPs), so plan_share_of_step would double without it. "
                 "bn_* rows price one level-0 BN stage (fwd+bwd) at S=4 "
                 "scenes: segment = the O(N) segmented-reduction engine on "
                 "the hot path, sliced = the retired O(S*cap) dynamic_slice "
                 "+ one-hot formulation kept as baseline"),
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
