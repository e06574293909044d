#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control 3] [--out calibrate.jsonl]

For each seed, in one process and at the cell's own size, it runs the
timed path as a run does (the same session, engine or trainer, with the
seed's weights and traffic, its products at the configuration's matmul
precision) and reads each number ``correct`` compares, against the
reference at that precision. For the first ``--control`` seeds it also
reads the program against the reference at the highest precision; the
control (:func:`control`), the reference put in the program's place in
the nearest precision below the stated one, and beside it the reference in
bfloat16; and faults: for serving, an answer altered where it is produced
(the first voxel's logits replaced by its neighbour's in the output order,
and moved one class along), for training, half the batch left out (the
reference with one scan of each batch unlabelled). A step that leaves the
state unchanged reads 1 by the leaf measure and needs no run. One JSON
line per seed goes to standard output and to ``--out``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def serve_seeds(cell, seeds, n_control, emit):
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench import reference, traffic
    from bench.modes import common, serve
    highest = jax.lax.Precision.HIGHEST
    session = engine = None
    for i, seed in enumerate(seeds):
        c = dataclasses.replace(cell, seed=seed)
        if session is None:
            pool, feats, _, session, engine = serve.build(c)
        else:
            pool = [it[0] for it in traffic.scan_pool(seed, c.traffic)]
            feats = [traffic.scan_features(s, c.net.in_channels)
                     for s in pool]
            session.params = common.make_params(c)
        n = int(c.traffic.get("check_sample", 3))
        served, lat = [], []
        for k in range(n):
            req, t = serve.serve_one(engine, pool[k], feats[k])
            served.append((k, pool[k], req))
            lat.append(t)
        params = jax.device_get(session.params)
        hosts = serve.HostScans(c.net)
        row = {"seed": seed, "latency_s": lat,
               "program": serve.check_sample(c, served, params, hosts)}
        if i < n_control:
            row["program_at_highest"] = serve.check_sample(
                c, served, params, hosts, precision=highest)
            lvl = c.net.out_level
            ctl_name, dtype, prec = control(c)
            worst = {}
            for k, scan, req in served:
                hs = hosts.of(k, scan)
                ref = serve.reference_logits(c, hs, scan, params)
                top = serve.reference_logits(c, hs, scan, params,
                                             precision=highest)
                low = serve.reference_logits(c, hs, scan, params,
                                             jnp.bfloat16)
                vox, m = hs.coords(lvl), hs.count(lvl)
                got = {"control_bf16": reference.logit_gaps(
                           vox, low[:m], hs, ref, lvl),
                       "control_bf16_at_highest": reference.logit_gaps(
                           vox, low[:m], hs, top, lvl),
                       "fault_altered_answer": reference.logit_gaps(
                           req.voxels, altered(req.logits), hs, ref, lvl),
                       "fault_wrong_class": reference.logit_gaps(
                           req.voxels, wrong_class(req.logits), hs, ref,
                           lvl)}
                if ctl_name not in got:
                    ctl = serve.reference_logits(c, hs, scan, params, dtype,
                                                 prec)
                    got[ctl_name] = reference.logit_gaps(vox, ctl[:m], hs,
                                                         ref, lvl)
                for name, gaps in got.items():
                    for g, v in gaps.items():
                        w = worst.setdefault(name, {})
                        w[g] = max(w.get(g, 0.0), v)
            for name, gaps in worst.items():
                row[name] = {g: {"value": v} for g, v in gaps.items()}
        emit(row)


def in_place(cell, served, params, hosts, dtype, precision) -> list:
    """``served`` as the reference in ``dtype`` at ``precision`` would have
    served it: each request's voxels and logits replaced by the reference's,
    the reference put in the program's place. Each pool scan is run once."""
    import types
    from bench.modes import serve
    lvl, answers, out = cell.net.out_level, {}, []
    for k, scan, req in served:
        if k not in answers:
            hs = hosts.of(k, scan)
            logits = serve.reference_logits(cell, hs, scan, params, dtype,
                                            precision)
            answers[k] = types.SimpleNamespace(
                outcome="ok", voxels=hs.coords(lvl),
                logits=logits[:hs.count(lvl)])
        out.append((k, scan, answers[k]))
    return out


def altered(logits):
    """An answer altered where it is produced: the first voxel's logits
    replaced by the second's."""
    import numpy as np
    bad = np.array(logits, np.float32)
    bad[0] = bad[1]
    return bad


def wrong_class(logits):
    """An answer altered where it is produced: the first voxel's logits
    moved one class along, so that it answers another class. Another
    voxel's logits (:func:`altered`) can lie within rounding of the right
    ones at level 0, where sparse outdoor voxels see the same few
    neighbours."""
    import numpy as np
    bad = np.array(logits, np.float32)
    bad[0] = np.roll(bad[0], 1)
    return bad


def control(cell) -> tuple:
    """``(row name, dtype, precision)`` of the control: the reference in
    the nearest precision below the one the configuration states, three
    bfloat16 passes for float32 at ``highest``, bfloat16 for other
    float32."""
    import jax
    import jax.numpy as jnp
    from bench.modes import common
    if cell.config["matmul_precision"] == "highest":
        return "control_high", jnp.float32, jax.lax.Precision.HIGH
    return "control_bf16", jnp.bfloat16, common.precision(cell)


def train_seeds(cell, seeds, n_control, emit):
    import dataclasses
    import jax
    from repro.train.optimizer import init_opt_state
    from repro.train.pointcloud import labeled_tensor
    from bench import traffic
    from bench.modes import common, train
    n = int(cell.traffic["check_steps"])
    session = trainer = None
    for i, seed in enumerate(seeds):
        c = dataclasses.replace(cell, seed=seed)
        t = time.perf_counter()
        if trainer is None:
            pool, batches, _, session, trainer = train.build(c)
        else:
            pool = traffic.scan_pool(seed, dict(c.traffic, labels=True))
            cin = c.net.in_channels
            batches = [labeled_tensor(
                [(s.coords, traffic.scan_features(s, cin), s.labels)
                 for s in item], session.layout) for item in pool[:n]]
            session.params = common.make_params(c)
            trainer.opt_state = init_opt_state(session.params,
                                               trainer.tcfg.opt)
        seen = train.first_steps(c, trainer, batches, n)
        t_prog = time.perf_counter() - t
        ref = train.reference_steps(c, pool, seen["p0"], n)
        row = {"seed": seed, "program_s": t_prog,
               "losses": seen["losses"], "ref_losses": ref["losses"],
               "program": train.readings(seen, ref)}
        if i < n_control:
            top = train.reference_steps(c, pool, seen["p0"], n,
                                        precision=jax.lax.Precision.HIGHEST)
            row["program_at_highest"] = train.readings(seen, top)
            name, dtype, prec = control(c)
            low = train.reference_steps(c, pool, seen["p0"], n,
                                        dtype=dtype, precision=prec)
            row[name] = train.readings(train.as_seen(low, seen["p0"]), ref)
            half = train.reference_steps(c, pool, seen["p0"], n,
                                         drop_scans=(1,))
            row["fault_half_batch"] = train.readings(
                train.as_seen(half, seen["p0"]), ref)
        emit(row)


def main(argv=None, *, root: Path = ROOT, need_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    for path in (str(root / "src"), str(root)):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None

    def emit(row):
        row["t"] = time.perf_counter() - T0
        line = json.dumps(row, default=float)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        with harness.open_cell(root, args.workload, seed=seeds[0],
                               seconds=0, trace=False, t0=T0,
                               need_chip=need_chip) as (_, cell, devs):
            {"serve": serve_seeds, "train": train_seeds}[
                cell.workload["mode"]](cell, seeds, args.control, emit)
            print(json.dumps({"device": harness.device_record(devs)}),
                  flush=True)
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
