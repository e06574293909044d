"""The unified observability layer (repro.obs) — acceptance suite.

Pins, in order: histogram edge cases (empty/single/at-below-above bucket
edges), exporter contracts (JSON snapshot round-trip, golden Prometheus
text, grammar parser), span nesting + determinism under FakeClock,
registry thread-safety (the pack-ahead worker / async ckpt writer story),
an exactly-pinned FakeClock serve snapshot (counts, bucket occupancy,
percentiles), the zero-overhead invariant (instrumentation changes neither
results nor compile/search counts), and the counters-dict API
compatibility of engine and trainer over registry-backed counters.
"""
from __future__ import annotations

import json
import math
import threading

import jax
import numpy as np
import pytest

from repro.core import SparseTensor, SpConvSpec
from repro.core.zdelta import reset_search_calls, search_call_count
from repro.data import scenes
from repro.models.pointcloud import PointCloudNet
from repro.obs import (MetricsRegistry, current_path, default_registry,
                       parse_prometheus_text, span)
from repro.serve import (FakeClock, FaultySession, PointCloudRequest,
                         PointCloudServeEngine, compile_network)

EDGE0 = 2.0 ** -20          # first default histogram edge
EDGE_LAST = 2.0 ** 6        # last default histogram edge


# ---------------------------------------------------------------------------
# histograms
# ---------------------------------------------------------------------------

def test_histogram_empty_percentiles():
    h = MetricsRegistry().histogram("h")
    assert h.count == 0 and h.sum == 0.0
    for q in (0.5, 0.9, 0.99, 1.0):
        assert h.percentile(q) == 0.0
    assert h.occupancy() == {}


def test_histogram_single_sample():
    h = MetricsRegistry().histogram("h")
    h.record(0.1)
    assert h.count == 1 and h.sum == 0.1
    # every percentile is the upper edge of the one occupied bucket
    for q in (0.01, 0.5, 0.99):
        assert h.percentile(q) == 0.125
    assert h.occupancy() == {"0.125": 1}


def test_histogram_at_below_above_first_and_last_edges():
    h = MetricsRegistry().histogram("h")
    h.record(0.0)               # below the first edge -> first bucket
    h.record(EDGE0)             # exactly at the first edge -> first bucket
    h.record(EDGE0 * 1.0001)    # just above -> second bucket
    h.record(EDGE_LAST)         # exactly at the last edge -> last bucket
    h.record(EDGE_LAST * 2)     # above the last edge -> +Inf overflow
    occ = h.occupancy()
    assert occ[repr(EDGE0)] == 2
    assert occ[repr(2.0 ** -19)] == 1
    assert occ[repr(EDGE_LAST)] == 1
    assert occ["+Inf"] == 1
    assert h.count == 5
    # rank-5 sample sits in the overflow bucket: conservative estimate +inf
    assert h.percentile(0.99) == math.inf
    assert h.percentile(0.5) == 2.0 ** -19


def test_histogram_percentile_rank_arithmetic():
    h = MetricsRegistry().histogram("h")
    for v in (1.0, 1.0, 2.0, 2.0):
        h.record(v)
    assert h.percentile(0.5) == 1.0     # rank ceil(0.5*4)=2 -> le=1.0 bucket
    assert h.percentile(0.51) == 2.0    # rank 3 -> le=2.0 bucket
    assert h.percentile(1.0) == 2.0
    with pytest.raises(ValueError):
        h.percentile(0.0)
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.histogram("h") is reg.histogram("h")
    with pytest.raises(ValueError, match="already registered"):
        reg.histogram("x")


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

def test_snapshot_json_round_trip():
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    reg.counter("c").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h").record(0.25)
    reg.rate("r").mark(3)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap
    assert snap["counters"] == {"c": 2}
    assert snap["gauges"] == {"g": 1.5}
    assert snap["rates"] == {"r": 3 / 60.0}
    assert snap["histograms"]["h"] == {
        "count": 1, "sum": 0.25, "p50": 0.25, "p90": 0.25, "p99": 0.25,
        "buckets": {"0.25": 1}}


def test_prometheus_text_golden():
    reg = MetricsRegistry(clock=lambda: 0.0)
    reg.counter("requests").inc(3)
    reg.gauge("queue/depth").set(2.0)          # '/' sanitized to '_'
    h = reg.histogram("lat", lo=-1, hi=1)      # edges 0.5, 1.0, 2.0
    for v in (0.25, 1.0, 5.0):
        h.record(v)
    expected = (
        "# TYPE spira_lat histogram\n"
        'spira_lat_bucket{le="0.5"} 1\n'
        'spira_lat_bucket{le="1.0"} 2\n'
        'spira_lat_bucket{le="2.0"} 2\n'
        'spira_lat_bucket{le="+Inf"} 3\n'
        "spira_lat_sum 6.25\n"
        "spira_lat_count 3\n"
        "# TYPE spira_queue_depth gauge\n"
        "spira_queue_depth 2.0\n"
        "# TYPE spira_requests counter\n"
        "spira_requests 3\n"
    )
    assert reg.to_prometheus_text() == expected
    samples = parse_prometheus_text(expected)
    assert samples["spira_requests"] == [("", 3.0)]
    assert samples["spira_lat_bucket"][-1] == ('le="+Inf"', 3.0)


@pytest.mark.parametrize("bad", [
    "no_value_here\n",
    "0leading_digit 1\n",
    "name{unquoted=x} 1\n",
    "name 1 2 3\n",
    "name not_a_number\n",
    "# TYPE broken\n",
])
def test_prometheus_parser_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_prometheus_text(bad)


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_nesting_paths_and_fake_clock():
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    with span("serve", reg):
        ck.advance(0.25)
        with span("pack", reg):
            ck.advance(0.5)
            assert current_path() == "serve/pack"
        assert current_path() == "serve"
    assert current_path() == ""
    snap = reg.snapshot()
    assert snap["histograms"]["serve/pack"]["sum"] == 0.5
    assert snap["histograms"]["serve"]["sum"] == 0.75


def test_span_records_on_exception_and_propagates():
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    with pytest.raises(RuntimeError, match="boom"):
        with span("dispatch", reg):
            ck.advance(2.0)
            raise RuntimeError("boom")
    assert current_path() == ""                  # stack unwound
    assert reg.histogram("dispatch").count == 1
    assert reg.histogram("dispatch").sum == 2.0


def test_span_multisegment_name_records_flat_path():
    reg = MetricsRegistry(clock=FakeClock())
    with span("serve/pack", reg):
        pass
    assert "serve/pack" in reg.snapshot()["histograms"]
    with pytest.raises(ValueError):
        span("/bad", reg)


def test_spans_nest_per_thread():
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    paths = []

    def worker():
        with span("w", reg) as s:
            paths.append(s.path)

    with span("main", reg):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    assert paths == ["w"]        # not "main/w": stacks are thread-local


def _trace_events(tmp_path, prefix):
    """Every host event of the one trace under ``tmp_path`` whose name
    starts with ``prefix``, as (name, stats)."""
    import glob
    import warnings
    from jax.profiler import ProfileData
    (f,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    out = []
    with warnings.catch_warnings():
        # jaxlib's stats type has no module; nothing to do with the trace
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(f).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        out.append((e.name, dict(e.stats)))
    return out


def test_span_annotates_the_profiler_trace(tmp_path):
    """With jax imported, a span is also a profiler annotation named by its
    path, carrying its keyword arguments; the histogram is unchanged."""
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    jax.profiler.start_trace(str(tmp_path))
    with span("serve/dispatch", reg, batch=7):
        ck.advance(0.5)
        with span("session/call", reg):
            pass
    jax.profiler.stop_trace()
    evs = dict(_trace_events(tmp_path, "serve/"))
    assert evs["serve/dispatch"]["batch"] == 7
    assert "serve/dispatch/session/call" in evs
    assert reg.histogram("serve/dispatch").sum == 0.5
    assert reg.histogram("serve/dispatch/session/call").count == 1


def test_obs_imports_without_jax():
    """obs never imports jax: a span opens no annotation where jax is not
    loaded."""
    import os
    import subprocess
    import sys
    code = ("import sys; from repro.obs import MetricsRegistry, span\n"
            "with span('serve/pack', MetricsRegistry(), batch=1) as s:\n"
            "    assert s._ann is None\n"
            "assert 'jax' not in sys.modules\n")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.environ.get("PYTHONPATH", "")]))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=60)


def test_registry_thread_safety_counters():
    reg = MetricsRegistry()
    c = reg.counter("n")
    N, K = 8, 2000

    def worker():
        for _ in range(K):
            c.inc()
            reg.histogram("h").record(1.0)

    threads = [threading.Thread(target=worker) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * K
    assert reg.histogram("h").count == N * K


# ---------------------------------------------------------------------------
# the instrumented pipeline (tiny net, same fixtures as tests/test_faults)
# ---------------------------------------------------------------------------

def _tiny_net():
    specs = (
        SpConvSpec("l0", 4, 8, K=3, m_in=0, m_out=0, dataflow="ws"),
        SpConvSpec("l1", 8, 8, K=3, m_in=0, m_out=1),
        SpConvSpec("l2", 8, 8, K=3, m_in=1, m_out=1),
    )
    return PointCloudNet("tiny_obs", specs, in_channels=4, n_classes=5)


@pytest.fixture(scope="module")
def world():
    batch = scenes.scene_batch(seed=7, batch=4, kind="indoor",
                               extent=(28, 24, 16), overlap=0.5)
    rng = np.random.default_rng(7)
    clouds = [(sc.coords,
               rng.normal(size=(len(sc.coords), 4)).astype(np.float32))
              for sc in batch]
    return batch[0].layout, clouds


def test_fake_clock_serve_snapshot_is_exactly_pinned(world):
    """A FakeClock-driven serve run yields exact metrics: every count,
    bucket occupancy and percentile below is arithmetic, not timing."""
    layout, clouds = world
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128,
                              metrics=reg)
    # each session call burns exactly 1s of fake time inside dispatch
    fs = FaultySession(session, delay=1.0, sleep=ck.sleep)
    eng = PointCloudServeEngine(fs, max_batch=2, clock=ck)
    assert eng.metrics is reg
    reqs = [PointCloudRequest(c, f) for c, f in clouds]
    eng.run(reqs)
    assert all(r.outcome == "ok" for r in reqs)

    snap = reg.snapshot()
    # counters: 4 requests in 2 batches of 2
    for key, want in [("serve_admitted", 4), ("serve_batches_run", 2),
                      ("serve_scenes_served", 4), ("serve_shed", 0),
                      ("serve_retries", 0), ("session_runs", 2)]:
        assert snap["counters"][key] == want, key
    # queue wait: batch 1 drains at t=0 (0s x2), batch 2 at t=1 (1s x2)
    qw = snap["histograms"]["serve_queue_wait"]
    assert qw["count"] == 4 and qw["sum"] == 2.0
    assert qw["buckets"] == {repr(EDGE0): 2, "1.0": 2}
    assert qw["p50"] == EDGE0 and qw["p90"] == 1.0 and qw["p99"] == 1.0
    # latency: batch 1 served at t=1 (1s x2), batch 2 at t=2 (2s x2)
    lat = snap["histograms"]["serve_latency_ok"]
    assert lat["count"] == 4 and lat["sum"] == 6.0
    assert lat["buckets"] == {"1.0": 2, "2.0": 2}
    assert lat["p50"] == 1.0 and lat["p90"] == 2.0 and lat["p99"] == 2.0
    # dispatch span: the injected 1s delay, twice; pack burns no fake time
    disp = snap["histograms"]["serve/dispatch"]
    assert disp["count"] == 2 and disp["sum"] == 2.0
    assert snap["histograms"]["serve/pack"]["sum"] == 0.0
    # the session call nests under the engine dispatch span on this thread
    assert snap["histograms"]["serve/dispatch/session/call"]["count"] == 2
    # rolling QPS: 4 scenes inside the 60s window
    assert snap["rates"]["serve_qps"] == 4 / 60.0
    # deterministic end to end: a fresh identical run pins the same numbers
    assert json.loads(json.dumps(snap)) == snap


def test_zero_overhead_invariant(world):
    """Instrumentation is observational only: results bitwise identical,
    jit compile counts and traced zdelta search counts unchanged between a
    direct session call and the fully instrumented engine path."""
    layout, clouds = world
    s1 = compile_network(_tiny_net(), layout, batch=4, min_bucket=128)
    s2 = compile_network(_tiny_net(), layout, batch=4, min_bucket=128,
                         params=s1.params)

    jax.clear_caches()
    reset_search_calls()
    stb = SparseTensor.from_point_clouds(clouds, s1.layout)
    direct = s1(stb).unbatch()
    direct_logits = [np.asarray(sc.features)[: int(sc.count)]
                     for sc in direct]
    searches_direct = search_call_count()
    compiles_direct = s1.compile_count   # before clear_caches resets caches
    assert searches_direct > 0

    jax.clear_caches()
    reset_search_calls()
    eng = PointCloudServeEngine(s2)
    reqs = [PointCloudRequest(c, f) for c, f in clouds]
    eng.run(reqs)
    assert search_call_count() == searches_direct
    assert s2.compile_count == compiles_direct
    for req, want in zip(reqs, direct_logits):
        np.testing.assert_array_equal(req.logits, want)


def test_engine_spans_of_a_batch_share_its_number(world, tmp_path):
    """The engine's spans of one batch carry its sequence number into the
    trace: pack, dispatch and answer of each of the two batches."""
    layout, clouds = world
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128)
    eng = PointCloudServeEngine(session, max_batch=2)
    warm = [PointCloudRequest(c, f) for c, f in clouds[:2]]
    eng.run(warm)                       # compile outside the trace
    reqs = [PointCloudRequest(c, f) for c, f in clouds]
    jax.profiler.start_trace(str(tmp_path))
    eng.run(reqs)
    jax.profiler.stop_trace()
    assert all(r.outcome == "ok" for r in reqs)
    by = {}
    for name, stats in _trace_events(tmp_path, "serve/"):
        by.setdefault(name, []).append(stats.get("batch"))
    for name in ("serve/pack", "serve/dispatch", "serve/answer"):
        assert sorted(by[name]) == [1, 2], name
    assert by["serve/dispatch/session/call"] == [None, None]
    hist = session.metrics.snapshot()["histograms"]
    assert hist["serve/answer"]["count"] == 2 + 1


def test_session_counts_the_rows_its_os_convs_walk(world):
    """``spconv_rows_walked`` counts each OS conv's map rows (the bucket),
    ``spconv_rows_real`` those that hold a voxel of its output level; the
    WS layer counts in neither, and the plan alone records no span."""
    layout, clouds = world
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128)
    st = SparseTensor.from_point_clouds(clouds, session.layout)
    session(st)
    bucket = session.last_health.bucket
    level1 = sum(len(np.unique(c >> 1, axis=0)) for c, _ in clouds)
    snap = session.metrics.snapshot()["counters"]
    assert snap["spconv_rows_walked"] == 2 * bucket      # l1 and l2
    assert snap["spconv_rows_real"] == 2 * level1
    session.plan(st)
    assert not any(k.startswith("session/plan")
                   for k in session.metrics.snapshot()["histograms"])


def test_session_counts_the_row_tiles_its_os_convs_skip(world):
    """``spconv_tiles_walked`` counts each OS conv's 128-row tiles,
    ``spconv_tiles_live`` those the kernel does not skip: the tiles that
    hold a voxel of the output level, ahead of its PAD tail."""
    layout, clouds = world
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128)
    session(SparseTensor.from_point_clouds(clouds, session.layout))
    bucket = session.last_health.bucket
    level1 = sum(len(np.unique(c >> 1, axis=0)) for c, _ in clouds)
    snap = session.metrics.snapshot()["counters"]
    assert snap["spconv_tiles_walked"] == 2 * bucket // 128   # l1 and l2
    assert snap["spconv_tiles_live"] == 2 * math.ceil(level1 / 128)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_live_tiles_match_numpy(seed):
    """A row tile is live iff some entry of its rows, at any offset, is a
    valid input index."""
    from repro.kernels.spconv_gather_gemm import live_tiles
    rng = np.random.default_rng(seed)
    bm, n_tiles, kd = 8, 24, 5
    m = rng.integers(0, 50, (n_tiles * bm, kd)).astype(np.int32)
    m[rng.random(m.shape) < 0.97] = -1
    m[bm * rng.choice(n_tiles, 6, replace=False)[:, None]
      + np.arange(bm)] = -1                     # whole tiles dead
    m[:bm] = -1
    m[bm - 1, kd - 1] = 0                       # input row 0 keeps it live
    want = (m.reshape(n_tiles, bm * kd) >= 0).any(axis=1).astype(np.int32)
    got = np.asarray(live_tiles(m, bm))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert 0 < want.sum() < n_tiles


def test_engine_counters_dict_api_compatible(world):
    """The plain-int counter attributes and the counters dict keep their
    pre-registry surface while sourcing from the shared registry."""
    layout, clouds = world
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128)
    eng = PointCloudServeEngine(session)
    assert eng.metrics is session.metrics
    # attribute read/write round-trips through the registry
    assert eng.admitted == 0 and isinstance(eng.admitted, int)
    eng.retries += 1
    assert eng.retries == 1
    assert session.metrics.counter("serve_retries").value == 1
    eng.retries = 0
    reqs = [PointCloudRequest(c, f) for c, f in clouds]
    eng.run(reqs)
    assert eng.counters == {
        "admitted": 4, "shed": 0, "invalid": 0, "quarantined": 0,
        "deadline_expired": 0, "retries": 0, "overflow_replans": 0,
        "batches_run": 1, "scenes_served": 4, "packs_overlapped": 0,
        "rejected_open": 0, "dispatch_timeouts": 0, "admission_shed": 0,
        "breaker_trips": 0, "downsampled": 0, "degradations": 0}
    snap = session.metrics.snapshot()
    assert all(snap["counters"][f"serve_{k}"] == v
               for k, v in eng.counters.items())


def test_breaker_gauge_and_outcome_counters_exported(world):
    """The overload-control surface reaches the Prometheus export: the
    breaker-state gauge walks closed(0) -> open(2) -> half_open(1) ->
    closed(0), and the new outcome counters (rejected_open /
    dispatch_timeouts / breaker_trips) appear as spira_serve_* series."""
    from repro.obs import parse_prometheus_text
    from repro.serve import BreakerConfig, FakeClock, FaultySession

    layout, clouds = world
    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    session = compile_network(_tiny_net(), layout, batch=4, min_bucket=128,
                              metrics=reg)
    fs = FaultySession(session, fail_calls=range(0, 2), exc=RuntimeError)
    eng = PointCloudServeEngine(fs, max_batch=1, clock=ck,
                                breaker=BreakerConfig(threshold=2,
                                                      cooldown=1.0))
    gauge = reg.gauge("serve_breaker_state")
    assert gauge.value == 0                       # closed at construction
    reqs = [PointCloudRequest(c, f) for c, f in clouds]
    for r in reqs[:2]:                            # two failures: trip
        eng.submit(r)
        eng.step()
    assert gauge.value == 2 and eng.breaker_trips == 1
    eng.submit(reqs[2])                           # open: rejected fast
    eng.step()
    assert reqs[2].outcome == "rejected_open" and eng.rejected_open == 1
    ck.advance(1.5)                               # cooldown -> half-open
    eng.submit(reqs[3])                           # probe succeeds -> closed
    eng.step()
    assert reqs[3].outcome == "ok" and gauge.value == 0

    samples = parse_prometheus_text(reg.to_prometheus_text())
    assert samples["spira_serve_breaker_state"] == [("", 0.0)]
    assert samples["spira_serve_rejected_open"] == [("", 1.0)]
    assert samples["spira_serve_breaker_trips"] == [("", 1.0)]
    assert samples["spira_serve_dispatch_timeouts"] == [("", 0.0)]
    assert "spira_serve_latency_rejected_open_bucket" in samples
    snap = reg.snapshot()
    assert snap["counters"]["serve_quarantined"] == 2  # the trip's failures


def test_trainer_metrics_and_ckpt_metrics(world, tmp_path):
    layout, clouds = world
    from repro.models import pointcloud as pc
    from repro.train import GuardConfig, labeled_tensor
    rng = np.random.default_rng(3)
    labeled = [(c, f, rng.integers(0, 5, size=len(c)).astype(np.int32))
               for c, f in clouds]
    # training needs a submanifold-ending net (per-voxel supervision)
    net = pc.tiny_segnet(in_channels=4, n_classes=5, width=8, depth=3)
    session = compile_network(net, layout, batch=4, min_bucket=128)
    tr = session.compile_train(guard=GuardConfig(ckpt_every=1),
                               ckpt=str(tmp_path))
    assert tr.metrics is session.metrics
    assert tr.ckpt.metrics is session.metrics   # str ckpt inherits registry
    st, lab = labeled_tensor(labeled, session.layout)
    tr.step(st, lab)
    tr.step(st, lab)
    tr.ckpt.wait()
    # counters dict keeps its full pre-registry surface
    c = tr.counters
    assert c["steps_total"] == 2 and c["steps_ok"] == 2
    assert c["checkpoint_saves"] == 2
    assert c["checksum_failures"] == 0 and "last_good_step" in c
    snap = session.metrics.snapshot()
    assert snap["counters"]["train_steps_total"] == 2
    assert snap["histograms"]["train/step"]["count"] == 2
    assert snap["histograms"]["train/pack"]["count"] == 2
    assert snap["histograms"]["ckpt/save"]["count"] == 2
    assert snap["counters"]["ckpt_bytes_written"] > 0
    # restore records duration + bytes on the same registry
    p, o, s = tr.ckpt.restore(None, session.params, tr.opt_state)
    snap = session.metrics.snapshot()
    assert snap["histograms"]["ckpt/restore"]["count"] == 1
    assert snap["counters"]["ckpt_bytes_read"] > 0
    # prometheus export of the whole pipeline parses
    parse_prometheus_text(session.metrics.to_prometheus_text())


def test_zdelta_counter_is_registry_backed_and_thread_safe():
    reset_search_calls()
    from repro.core.zdelta import _count_search

    def worker():
        for _ in range(500):
            _count_search()

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert search_call_count() == 8 * 500
    assert default_registry().counter("zdelta_search_calls").value == 8 * 500
    reset_search_calls()
    assert search_call_count() == 0
