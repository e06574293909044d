"""Serving: scans through the program's serving engine, closed loop.

Set-up makes the pool of scans and the weights from the seed, compiles the
configuration's network (its ``program_net``, ``harness.Cell.hook``) with
``compile_network(net, layout, params=...)`` (the session's
defaults: one scan per call, the engine, backend and search that the
session picks) and puts one scan through a ``PointCloudServeEngine`` to
compile the bucket. The window keeps ``traffic["in_flight"]`` (one) scan
in the engine: each scan is submitted, served, and its logits brought to
the host before the next is submitted, going round the pool in order.
Latency runs from the submission to the logits on the host.

Once the window has closed, a sample of the scans it served (the largest,
and others drawn from the seed) is run through the reference, in float32
at the configuration's matmul precision, and each served logit is compared
with the reference's. The workload's ``limits`` name the gaps compared:
the widest, which an altered answer shows, and the median, which a lower
precision shows.
"""
from __future__ import annotations

import time
from typing import List

import numpy as np

from bench import harness, opcount, reference, traffic
from bench.modes import common


def build(cell: harness.Cell, params=None):
    """The pool, the weights and the engine, from ``cell.seed``."""
    import jax
    from repro.core.packing import BitLayout
    from repro.serve import PointCloudServeEngine, compile_network

    tr = cell.traffic
    if int(tr.get("in_flight", 1)) != 1 or int(tr.get("scans_per_item", 1)) != 1:
        raise ValueError("the serve mode keeps one scan of one request in "
                         "flight")
    pool = [items[0] for items in traffic.scan_pool(cell.seed, tr)]
    feats = [traffic.scan_features(s, cell.net.in_channels) for s in pool]
    if params is None:
        params = common.make_params(cell)
    net = common.checked_program_net(cell)
    layout = BitLayout.for_extent(*tr["extent"], guard=traffic.GUARD)
    session = compile_network(net, layout, params=params)
    engine = PointCloudServeEngine(session)
    return pool, feats, params, session, engine


def serve_one(engine, scan, feats):
    """Submit one scan, serve it, and return (request, latency s)."""
    from repro.serve import PointCloudRequest
    req = PointCloudRequest(coords=scan.coords, features=feats)
    t = time.perf_counter()
    engine.submit(req)
    engine.step()
    return req, time.perf_counter() - t


class HostScans(dict):
    """The reference's levels and maps of each pool scan, built once."""

    def __init__(self, net):
        super().__init__()
        self.net = net

    def of(self, k: int, scan) -> reference.HostScan:
        if k not in self:
            self[k] = reference.build_scan(scan.coords, self.net)
        return self[k]


def check_sample(cell: harness.Cell, served: List[tuple], params_host,
                 hosts: HostScans, dtype=None, precision=None) -> dict:
    """Compare a sample of ``served`` = [(pool index, scan, request)] with
    the reference; returns each of :data:`reference.GAPS` at the worst
    scan by it, and which scan that was."""
    n = int(cell.traffic.get("check_sample", 3))
    pick = common.sample(served, n, cell.seed,
                         size=lambda s: len(s[1].coords))
    out = {k: {"value": 0.0, "scan": None} for k in reference.GAPS}
    for k, scan, req in pick:
        if req.outcome != "ok" or req.logits is None:
            gaps = {k: float("inf") for k in reference.GAPS}
        else:
            hs = hosts.of(k, scan)
            ref = reference_logits(cell, hs, scan, params_host, dtype,
                                   precision)
            gaps = reference.logit_gaps(req.voxels, req.logits, hs, ref,
                                        cell.net.out_level)
        for name, gap in gaps.items():
            if gap >= out[name]["value"]:
                out[name] = {"value": gap, "scan": k}
    return out


def reference_logits(cell, hs, scan, params_host, dtype=None,
                     precision=None) -> np.ndarray:
    """The reference's logits of one scan, rows in key order; by default
    in float32 at the configuration's matmul precision."""
    import jax.numpy as jnp
    net = cell.net
    inp = cell.hook("device_inputs")(
        [hs], [traffic.scan_features(scan, net.in_channels)], net)
    return np.asarray(reference.forward(
        params_host, inp, net=net, dtype=dtype or jnp.float32,
        precision=precision or common.precision(cell),
        forward_one=cell.hook("forward_one")))[0]


def run(cell: harness.Cell, devs) -> dict:
    import jax

    pool, feats, params, session, engine = build(cell)
    for k in common.one_per_bucket(session, [len(s.coords) for s in pool]):
        warm, _ = serve_one(engine, pool[k], feats[k])
        if warm.outcome != "ok":
            raise RuntimeError(f"warm-up request ended {warm.outcome!r}: "
                               f"{warm.error}")
    pack = engine.metrics.histogram("serve/pack")
    setup_s = time.perf_counter() - cell.t0

    served, latencies = [], []
    pack0 = (pack.count, pack.sum)
    with common.Window(cell) as win:
        i = 0
        while win.elapsed() < cell.seconds:
            k = i % len(pool)
            with common.annotate("bench/request"):
                req, lat = serve_one(engine, pool[k], feats[k])
            served.append((k, pool[k], req))
            latencies.append(lat)
            i += 1
    failed = sum(r.outcome != "ok" for _, _, r in served)
    pack1 = (pack.count, pack.sum)
    device = harness.device_record(devs)
    params_host = jax.device_get(params)
    del engine, session, params, warm
    harness.free_device_memory()

    ok_lat = [l for (_, _, r), l in zip(served, latencies) if r.outcome == "ok"]
    end_to_end = {
        "scan_latency_p50_ms": 1e3 * common.percentile(ok_lat, 50),
        "scan_latency_p95_ms": 1e3 * common.percentile(ok_lat, 95),
        "setup_s": setup_s,
    }
    context, breakdown = None, None
    hosts = HostScans(cell.net)
    if cell.trace:
        summary = win.summary()
        work = {"forward_flops": 0.0, "os_flops": 0.0, "os_bytes": 0.0}
        forward_flops = cell.hook("forward_flops")
        for k, scan, _ in served:
            hs = hosts.of(k, scan)
            work["forward_flops"] += forward_flops(hs, cell.net)
            w = opcount.os_call_work(hs, cell.net, backward=False)
            work["os_flops"] += w["flops"]
            work["os_bytes"] += w["bytes"]
        dn = pack1[0] - pack0[0]
        context = common.context(cell, devs, summary, units=len(served),
                                 work=work, spans={"serve/pack": (
                                     dn, pack1[1] - pack0[1])})
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        breakdown = common.breakdown(summary)

    got = check_sample(cell, served, params_host, hosts)
    checks = {k: dict(got[k], value=harness.finite(got[k]["value"]),
                      limit=limit)
              for k, limit in cell.workload["limits"].items()}
    return {"ok": failed == 0 and len(served) > 0, "attempted": len(served),
            "failed": failed, "end_to_end": end_to_end, "context": context,
            "device": device, "breakdown": breakdown, "checks": checks}
