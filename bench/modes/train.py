"""Training: steps of the program's trainer on labelled scans.

Set-up makes the pool of batches (``traffic["scans_per_item"]`` labelled
scans each) and the weights from the seed, compiles the configuration's
network (its ``program_net``, ``harness.Cell.hook``) for that many scans
per call, builds the trainer with ``session.compile_train()``
(the trainer's own AdamW) and drives it through its first ``check_steps``
steps on batches that all differ: the first compiles, and the reference
follows all of them. The window then goes on with the same trainer,
round the pool, one whole step at a time.

Once the window has closed, the reference runs the same first steps from
the same weights, in float32 at the configuration's matmul precision, and
the run compares the losses, the first gradient as the optimizer took it
(from its first moment after one step) and the change of the parameters
over the steps: the readings that the workload's ``limits`` name, of
those :func:`readings` makes.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench import harness, opcount, reference, traffic
from bench.modes import common


def build(cell: harness.Cell, params=None):
    """The pool of labelled batches, the weights and the trainer."""
    from repro.core.packing import BitLayout
    from repro.serve import compile_network
    from repro.train.pointcloud import labeled_tensor

    tr = cell.traffic
    pool = traffic.scan_pool(cell.seed, dict(tr, labels=True))
    if params is None:
        params = common.make_params(cell)
    net = common.checked_program_net(cell)
    layout = BitLayout.for_extent(*tr["extent"], guard=traffic.GUARD)
    per = int(tr["scans_per_item"])
    session = compile_network(net, layout, params=params, batch=per)
    trainer = session.compile_train()
    check_optimizer(cell, trainer)
    cin = cell.net.in_channels
    batches = [labeled_tensor([(s.coords, traffic.scan_features(s, cin),
                                s.labels) for s in item], session.layout)
               for item in pool]
    sizes = [int(b[0].count) for b in batches]
    if len(common.one_per_bucket(session, sizes)) != 1:
        raise ValueError(f"the batches fall in more than one capacity "
                         f"bucket ({sizes} voxels): a step would compile "
                         "inside the window")
    return pool, batches, params, session, trainer


def check_optimizer(cell: harness.Cell, trainer) -> None:
    """The trainer's optimizer must be the one the workload states, which
    is the one the reference runs."""
    opt = trainer.tcfg.opt
    want = cell.workload["optimizer"]
    have = {k: getattr(opt, k) for k in want if k != "min_lr_ratio"}
    diff = {k: (v, want[k]) for k, v in have.items() if v != want[k]}
    if diff:
        raise ValueError(f"the trainer's optimizer differs from the "
                         f"workload's: {diff}")


def first_steps(cell: harness.Cell, trainer, batches, n: int) -> dict:
    """Drive ``trainer`` through ``n`` steps on ``batches[:n]``; keep what
    the reference is compared with: the weights before and after, each
    step's loss, and the first gradient as the optimizer took it, which is
    its first moment after one step over ``1 - b1``."""
    import jax
    p0 = jax.device_get(trainer.session.params)
    b1 = cell.workload["optimizer"]["b1"]
    losses, g1 = [], None
    for i in range(n):
        losses.append(trainer.step(*batches[i])["loss"])
        if i == 0:
            g1 = jax.tree.map(lambda m: np.asarray(m, np.float64) / (1 - b1),
                              jax.device_get(trainer.opt_state.mu))
    return {"p0": p0, "losses": losses, "g1": g1,
            "pn": jax.device_get(trainer.session.params)}


def readings(seen: dict, ref: dict) -> Dict[str, dict]:
    """The numbers that can be compared, with the leaf each was read on:
    the first step's relative loss gap and the largest over the steps;
    the first gradient's norm gap, of the worst and of the median leaf;
    and the worst leaf's gap of the norm of the weights' change, leaving
    out the leaves that move by round-off alone. The workload's
    ``limits`` name those compared."""
    losses = [abs(a - b) / abs(b) for a, b in zip(seen["losses"],
                                                  ref["losses"])]
    if not all(np.isfinite(seen["losses"])):
        losses = [float("inf")] * len(losses)
    g = reference.leaf_norm_gaps(seen["g1"], ref["first_grad"])
    gleaf = max(g, key=g.get)
    still = reference.still_leaves(ref["first_grad"])
    upd, uleaf = reference.leaf_norm_gap(
        reference.tree_sub(seen["pn"], seen["p0"]),
        reference.tree_sub(ref["params"], seen["p0"]), skip=still)
    return {"first_loss_gap": {"value": losses[0]},
            "loss_gap": {"value": max(losses)},
            "grad_gap": {"value": g[gleaf], "leaf": gleaf},
            "median_grad_gap": {"value": float(np.median(list(g.values())))},
            "update_gap": {"value": upd, "leaf": uleaf,
                           "left_out": len(still)}}


def as_seen(ref: dict, p0) -> dict:
    """A reference run in the program's place, as :func:`readings` takes it."""
    return {"p0": p0, "losses": ref["losses"], "g1": ref["first_grad"],
            "pn": ref["params"]}


def reference_steps(cell: harness.Cell, pool, p0, n: int, dtype=None,
                    drop_scans=(), precision=None) -> dict:
    """The reference's first ``n`` steps from ``p0`` on ``pool[:n]``, by
    default in float32 at the configuration's matmul precision.
    ``drop_scans`` leaves those scans of every batch unlabelled."""
    import jax.numpy as jnp
    net = cell.net
    inputs = []
    for item in pool[:n]:
        hs = [reference.build_scan(s.coords, net) for s in item]
        feats = [traffic.scan_features(s, net.in_channels) for s in item]
        labels = [s.labels if i not in drop_scans
                  else np.full_like(s.labels, -1) for i, s in enumerate(item)]
        inputs.append(cell.hook("device_inputs")(hs, feats, net, labels))
    return reference.train_steps(p0, inputs, net, optimizer(cell),
                                 dtype=dtype or jnp.float32,
                                 precision=precision
                                 or common.precision(cell),
                                 forward_one=cell.hook("forward_one"))


def optimizer(cell: harness.Cell) -> reference.AdamW:
    return reference.AdamW(**cell.workload["optimizer"])


def run(cell: harness.Cell, devs) -> dict:
    n_check = int(cell.traffic["check_steps"])
    pool, batches, params, session, trainer = build(cell)
    if len(pool) <= n_check:
        raise ValueError("the pool must hold more batches than the steps "
                         "the reference follows")
    seen = first_steps(cell, trainer, batches, n_check)
    setup_s = time.perf_counter() - cell.t0

    steps: List[int] = []
    losses = []
    with common.Window(cell) as win:
        j = n_check
        while win.elapsed() < cell.seconds:
            k = j % len(pool)
            with common.annotate("bench/step"):
                losses.append(trainer.step(*batches[k])["loss"])
            steps.append(k)
            j += 1
    voxels = sum(int(batches[k][0].count) for k in steps)
    failed = int(sum(not np.isfinite(l) for l in losses))
    device = harness.device_record(devs)
    del trainer, session, params, batches
    harness.free_device_memory()

    end_to_end = {"train_voxels_per_s": voxels / win.seconds,
                  "setup_s": setup_s}
    context, breakdown = None, None
    if cell.trace:
        summary = win.summary()
        work = {"train_flops": 0.0, "os_flops": 0.0, "os_bytes": 0.0}
        forward_flops = cell.hook("forward_flops")
        hosts = {}
        for k in steps:
            for i, s in enumerate(pool[k]):
                if (k, i) not in hosts:
                    hosts[(k, i)] = reference.build_scan(s.coords, cell.net)
                hs = hosts[(k, i)]
                work["train_flops"] += 3 * forward_flops(hs, cell.net)
                w = opcount.os_call_work(hs, cell.net, backward=True)
                work["os_flops"] += w["flops"]
                work["os_bytes"] += w["bytes"]
        context = common.context(cell, devs, summary, units=len(steps),
                                 work=work, spans={})
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = common.breakdown(summary)

    ref = reference_steps(cell, pool, seen["p0"], n_check)
    got = readings(seen, ref)
    checks = {k: dict(got[k], value=harness.finite(got[k]["value"]),
                      limit=limit)
              for k, limit in cell.workload["limits"].items()}
    return {"ok": failed == 0 and len(steps) > 0, "attempted": len(steps),
            "failed": failed, "end_to_end": end_to_end, "context": context,
            "device": device, "breakdown": breakdown, "checks": checks}
