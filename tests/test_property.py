"""Hypothesis property-based tests on the engine's invariants."""
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

try:
    import hypothesis  # noqa: F401  (gate only; strategies imported below)
except ImportError as e:
    # Announce the skip loudly at collection time: a bare importorskip makes
    # property tests vanish silently from the CI log, and "the invariants
    # were never property-checked" should be visible, not inferred from a
    # skip count.
    print(f"[test_property] SKIPPING all property tests at collection: "
          f"hypothesis is not installed ({e}). The engine's invariants "
          f"(packing order, offset additivity, z-delta == brute force) were "
          f"NOT property-checked in this run.", file=sys.stderr, flush=True)
    pytest.skip("hypothesis not installed", allow_module_level=True)

from hypothesis import given, settings, strategies as st

from repro.core import (BitLayout, build_coord_set, pack, pack_offsets,
                        unpack, offset_grid, zdelta_offsets, zdelta_search)
from repro.core.packing import round_down
from repro.core.voxel import pad_value
from repro.core import reference
from repro.kernels.segsum import (SegmentSpec, segment_sum,
                                  segments_from_sizes)

SET = settings(max_examples=25, deadline=None)


coords_strategy = st.lists(
    st.tuples(st.integers(16, 200), st.integers(16, 150), st.integers(16, 80)),
    min_size=1, max_size=300)


@SET
@given(coords_strategy)
def test_pack_preserves_lexicographic_order(cs):
    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    c = np.array(sorted(set(cs)), np.int32)
    p = np.asarray(pack(jnp.asarray(c), layout))
    assert (np.diff(p) > 0).all()          # strictly increasing
    back, _ = unpack(jnp.asarray(p), layout)
    np.testing.assert_array_equal(np.asarray(back), c)


@SET
@given(coords_strategy,
       st.tuples(st.integers(-8, 8), st.integers(-8, 8), st.integers(-8, 8)))
def test_packed_offset_additivity_property(cs, d):
    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    c = np.array(sorted(set(cs)), np.int32)
    dd = np.array(d, np.int32)
    lhs = np.asarray(pack(jnp.asarray(c), layout)
                     + pack_offsets(jnp.asarray(dd), layout))
    rhs = np.asarray(pack(jnp.asarray(c + dd), layout))
    np.testing.assert_array_equal(lhs, rhs)


@SET
@given(coords_strategy, st.integers(1, 4))
def test_downsample_bitmask_equals_reference(cs, m):
    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    c = np.array(sorted(set(cs)), np.int32)
    got, _ = unpack(round_down(pack(jnp.asarray(c), layout), layout, m), layout)
    np.testing.assert_array_equal(np.asarray(got), (c >> m) << m)


@SET
@given(coords_strategy, st.sampled_from([3, 5]))
def test_zdelta_kernel_map_equals_bruteforce(cs, K):
    """The headline invariant: one-shot z-delta search == dict brute force
    for arbitrary coordinate sets (not just surface scenes)."""
    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    c = np.array(sorted(set(cs)), np.int32)
    coord_set = build_coord_set(pack(jnp.asarray(c), layout))
    _, anchors, zstep = zdelta_offsets(K, 1, layout)
    got = np.asarray(zdelta_search(coord_set, coord_set, anchors, zstep, K=K))
    want = reference.kernel_map_reference(c, c, K, 1)
    np.testing.assert_array_equal(got[: len(c)], want)


@SET
@given(coords_strategy)
def test_coord_set_is_sorted_unique_padded(cs):
    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    c = np.array(list(cs) + list(cs)[: len(cs) // 2], np.int32)  # dup tail
    s = build_coord_set(pack(jnp.asarray(c), layout))
    n = int(s.count)
    arr = np.asarray(s.packed)
    assert (np.diff(arr[:n]) > 0).all() if n > 1 else True
    assert (arr[n:] == pad_value(arr.dtype)).all()
    assert n == len(np.unique(arr[:n]))


@SET
@given(st.lists(st.integers(0, 24), min_size=1, max_size=5),
       st.integers(0, 40), st.integers(1, 4), st.sampled_from([4, 16]),
       st.integers(0, 2 ** 31 - 1))
def test_segment_engine_bit_invariances(sizes, pad, C, q, seed):
    """The segmented-reduction engine's contract, forward AND gradient:
    bitwise invariant under zero-extension (appending PAD rows), capacity
    re-bucketing (pow2 growth) and scene permutation — for arbitrary
    segment size profiles, including empty scenes."""
    rng = np.random.default_rng(seed)
    S = len(sizes)
    n = sum(sizes)
    cap = n + pad + 1
    sp = SegmentSpec(backend="xla", q=q)

    def build(order, cap):
        sid, starts, counts = segments_from_sizes(
            [sizes[b] for b in order], cap)
        x = np.zeros((cap, C), np.float32)
        pos = 0
        for b in order:
            x[pos:pos + sizes[b]] = data[b]
            pos += sizes[b]
        return (jnp.asarray(x), jnp.asarray(sid), jnp.asarray(starts),
                jnp.asarray(counts))

    def run(args):
        return np.asarray(segment_sum(*args, num_segments=S, spec=sp))

    def grad(args):
        x, sid, starts, counts = args
        g = jax.grad(lambda v: jnp.vdot(
            segment_sum(v, sid, starts, counts, num_segments=S, spec=sp),
            jnp.asarray(w)))(x)
        return np.asarray(g)

    data = [rng.normal(size=(sz, C)).astype(np.float32) for sz in sizes]
    w = rng.normal(size=(S, C)).astype(np.float32)
    ident = list(range(S))
    base = build(ident, cap)
    out = run(base)
    gout = grad(base)
    # zero-extension + pow2 re-bucketing
    for cap2 in (cap + 17, max(64, 1 << int(np.ceil(np.log2(cap + 1))))):
        ext = build(ident, cap2)
        np.testing.assert_array_equal(run(ext), out)
        np.testing.assert_array_equal(grad(ext)[:n], gout[:n])
    # scene permutation: per-scene results ride along bitwise
    perm = list(rng.permutation(S))
    pargs = build(perm, cap)
    np.testing.assert_array_equal(run(pargs), out[perm])


def _boundary_vals(b: int, guard: int):
    vals = {0, 1, guard - 1, guard, guard + 1,
            (1 << b) - guard - 1, (1 << b) - guard, (1 << b) - 2,
            (1 << b) - 1}
    return sorted(v for v in vals if 0 <= v < (1 << b))


_L32 = BitLayout(bx=10, by=9, bz=8)      # 27 bits -> int32 words
_L64 = BitLayout(bx=22, by=21, bz=20)    # 63 bits -> int64 words


@SET
@given(st.sampled_from([_L32, _L64]), st.data())
def test_pack_unpack_roundtrip_at_field_boundaries(layout, data):
    """unpack(pack(c)) == c when every component sits ON a field boundary
    (0, guard±1, max-in-field, max∓guard) — pack is exact across the whole
    field for both int32 and int64 packings (the aliasing that validation
    guards against happens only OUTSIDE the field, pinned below)."""
    import contextlib

    c = np.array(data.draw(st.lists(
        st.tuples(st.sampled_from(_boundary_vals(layout.bx, layout.guard)),
                  st.sampled_from(_boundary_vals(layout.by, layout.guard)),
                  st.sampled_from(_boundary_vals(layout.bz, layout.guard))),
        min_size=1, max_size=64)), np.int64)
    ctx = (jax.enable_x64(True) if layout.bits_total > 31
           else contextlib.nullcontext())
    with ctx:
        p = np.asarray(pack(jnp.asarray(c), layout))
        assert p.dtype == (np.int32 if layout.bits_total <= 31 else np.int64)
        back, _ = unpack(jnp.asarray(p), layout)
        np.testing.assert_array_equal(np.asarray(back), c)


@SET
@given(st.integers(1, 1 << 8), st.integers(0, 2))
def test_out_of_field_rejected_by_validation_not_wrapped(excess, axis):
    """PINNED companion: a component past its field width aliases another
    voxel under raw pack() — the guarded ingest boundary must reject it
    (policy="reject") for any overflow amount, never wrap."""
    from repro.core import SparseTensor, ValidationError

    layout = BitLayout(bx=8, by=8, bz=8)
    c = np.array([[20, 21, 22]], np.int64)
    c[0, axis] = (1 << 8) + excess
    f = np.zeros((1, 3), np.float32)
    with pytest.raises(ValidationError):
        SparseTensor.from_point_cloud(c, f, layout)


@SET
@given(st.integers(0, 2 ** 31 - 2), st.integers(1, 64))
def test_sorted_query_positions_monotone(x0, span):
    """searchsorted positions over a sorted array are monotone in the query
    — the property the z-delta window kernel's Phase A start table relies
    on (window starts never move backwards within a tile)."""
    arr = jnp.asarray(np.sort(np.random.default_rng(span).integers(
        0, 2 ** 30, 512)).astype(np.int32))
    qs = jnp.asarray(np.arange(x0 % (2 ** 30), x0 % (2 ** 30) + span,
                               dtype=np.int32))
    pos = np.asarray(jnp.searchsorted(arr, qs))
    assert (np.diff(pos) >= 0).all()


_POISON = [float("nan"), float("inf"), float("-inf")]


@SET
@given(st.data())
def test_guarded_update_never_writes_nonfinite(data):
    """The guarded train step's update (train.guard.guarded_apply_updates)
    under ARBITRARY NaN/Inf injection positions in the gradient tree (and
    optionally the loss): the step is refused (step_ok=0) and params AND
    optimizer state pass through bitwise identical — no non-finite value
    can ever reach the weights. With no injection the step applies and the
    new params are all finite. Deterministically mirrored in
    tests/test_train_guard.py (test_guarded_apply_updates_*)."""
    from repro.train import AdamWConfig, init_opt_state
    from repro.train.guard import guarded_apply_updates

    rng = np.random.default_rng(data.draw(st.integers(0, 2 ** 16)))
    shapes = {"a": (4, 3), "b": (6,), "c": (2, 2, 2)}
    params = {k: jnp.asarray(rng.normal(size=s).astype(np.float32))
              for k, s in shapes.items()}
    grads = {k: jnp.asarray(rng.normal(size=s).astype(np.float32) * 1e-2)
             for k, s in shapes.items()}
    cfg = AdamWConfig(warmup_steps=1, total_steps=10)
    opt = init_opt_state(params, cfg)

    # inject poison at 0..4 arbitrary (leaf, flat-index) positions, plus
    # optionally into the loss scalar
    n_inject = data.draw(st.integers(0, 4))
    for _ in range(n_inject):
        k = data.draw(st.sampled_from(sorted(shapes)))
        flat = np.array(grads[k]).reshape(-1)
        flat[data.draw(st.integers(0, flat.size - 1))] = \
            data.draw(st.sampled_from(_POISON))
        grads[k] = jnp.asarray(flat.reshape(shapes[k]))
    poison_loss = data.draw(st.booleans())
    loss = jnp.asarray(data.draw(st.sampled_from(_POISON))
                       if poison_loss else 1.25)

    before_p = [np.asarray(x).tobytes() for x in jax.tree.leaves(params)]
    before_o = [np.asarray(x).tobytes() for x in jax.tree.leaves(opt)]
    new_p, new_o, m = guarded_apply_updates(params, grads, opt, cfg,
                                            loss=loss)
    bad = n_inject > 0 or poison_loss
    assert float(m["step_ok"]) == (0.0 if bad else 1.0)
    after_p = [np.asarray(x).tobytes() for x in jax.tree.leaves(new_p)]
    after_o = [np.asarray(x).tobytes() for x in jax.tree.leaves(new_o)]
    if bad:
        assert after_p == before_p and after_o == before_o
    else:
        assert int(new_o.step) == 1
    assert all(np.isfinite(np.asarray(x)).all()
               for x in jax.tree.leaves(new_p))


# ---------------------------------------------------------------------------
# serving overload: the terminal-outcome invariant (ISSUE 10)
# Deterministic mirror: tests/test_overload.py
# test_terminal_outcome_invariant_mixed_faults (same harness, fixed mix).
# ---------------------------------------------------------------------------

_SERVE_TERMINAL = ("ok", "invalid", "quarantined", "shed", "deadline_expired",
                   "rejected_open", "dispatch_timeout")


class _IdentitySession:
    """Duck-typed stub session (callable + layout/num_scenes/min_bucket):
    exercises the whole engine control plane — scheduling, admission,
    breaker, ladder, bisection — without a compiled network."""

    def __init__(self, layout, num_scenes=4, min_bucket=128):
        self.layout = layout
        self.num_scenes = num_scenes
        self.min_bucket = min_bucket

    def run_with_health(self, st_, **kw):
        return st_, None

    def __call__(self, st_):
        return st_


_serve_req_strategy = st.lists(
    st.tuples(
        st.integers(2, 180),                  # scene size (rows drawn below)
        st.floats(0.0, 0.2),                  # inter-arrival gap (s)
        st.one_of(st.none(), st.floats(-0.5, 2.0)),   # absolute deadline
        st.booleans(),                        # poisoned?
    ),
    min_size=1, max_size=14)


@SET
@given(_serve_req_strategy,
       st.sets(st.integers(0, 20), max_size=4),       # failing call indices
       st.integers(0, 2 ** 31 - 1))
def test_serve_overload_every_request_terminal(spec, fail_calls, seed):
    """Under arbitrary arrival schedules, deadlines, scene sizes (mixed
    pow2 buckets) and injected fault mixes, every submitted request reaches
    exactly ONE terminal outcome — none lost, none double-finalized (each
    finalization records exactly one per-outcome latency sample, so the
    histogram counts must sum to submissions) — and the engine's counters
    sum back to the submissions."""
    from repro.obs import MetricsRegistry
    from repro.serve import (AdmissionConfig, BreakerConfig, FakeClock,
                             FaultySession, LadderConfig,
                             PointCloudServeEngine, feature_poison,
                             make_traffic, run_open_loop)

    layout = BitLayout.for_extent(220, 170, 100, guard=16)
    rng = np.random.default_rng(seed)
    base = np.array(sorted(set(
        map(tuple, rng.integers((16, 16, 16), (200, 150, 80),
                                size=(200, 3))))), np.int32)
    clouds, arrivals, deadlines, poison = [], [], {}, []
    t = 0.0
    for i, (size, gap, deadline, poisoned) in enumerate(spec):
        size = min(size, len(base))
        clouds.append((base[:size],
                       np.ones((size, 4), np.float32)))
        t += gap
        arrivals.append(t)
        if deadline is not None:
            deadlines[i] = deadline
        if poisoned:
            poison.append(i)

    ck = FakeClock()
    reg = MetricsRegistry(clock=ck)
    fs = FaultySession(_IdentitySession(layout), delay=0.03, sleep=ck.sleep,
                       poison=feature_poison(), fail_calls=fail_calls,
                       exc=RuntimeError)
    eng = PointCloudServeEngine(
        fs, clock=ck, max_queue=5, metrics=reg, scheduler="bucket",
        admission=AdmissionConfig(target=0.04, interval=0.15),
        breaker=BreakerConfig(threshold=2, cooldown=0.3),
        ladder=LadderConfig(target=0.04, escalate_after=0.2,
                            deescalate_after=0.4, voxel_budget=128))
    reqs = make_traffic(clouds, len(clouds), poison=poison,
                        deadlines=deadlines)
    run_open_loop(eng, list(zip(arrivals, reqs)), ck)

    n = len(reqs)
    assert all(r.outcome in _SERVE_TERMINAL for r in reqs)
    recorded = sum(reg.histogram(f"serve_latency_{o}").count
                   for o in _SERVE_TERMINAL)
    assert recorded == n, f"finalizations {recorded} != submissions {n}"
    c = eng.counters
    mix = {o: sum(r.outcome == o for r in reqs) for o in _SERVE_TERMINAL}
    assert c["shed"] == mix["shed"]
    assert c["invalid"] == mix["invalid"]
    assert c["quarantined"] == mix["quarantined"]
    assert c["deadline_expired"] == mix["deadline_expired"]
    assert c["rejected_open"] == mix["rejected_open"]
    assert c["dispatch_timeouts"] == mix["dispatch_timeout"]
    assert c["scenes_served"] == mix["ok"]
    refused = mix["shed"] + sum(
        r.outcome == "deadline_expired" and r.deadline is not None
        and r.submitted_at is not None and r.submitted_at > r.deadline
        for r in reqs)
    assert c["admitted"] + refused == n
