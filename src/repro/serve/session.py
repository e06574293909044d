"""SpiraSession: one front door from raw points to logits.

Spira's thesis is that indexing and computation decouple and can be planned
network-wide at start (§5.5). This module makes that the *API*: a session is
a compile-once/run-many pipeline object that owns everything a caller used
to hand-stitch —

* spec resolution and tuner persistence (``core.tuner.apply_tuning``),
* capacity bucketing (power-of-two buckets, PAD padding — the
  ``serve.bucketing`` policy, now an internal detail),
* network-wide plan building (``core.build_network_plan``) fused with the
  feature pass into ONE jitted graph,

so the hot path is a single call::

    session = compile_network(net, layout, params=params, batch=4)
    out = session(SparseTensor.from_point_clouds(clouds, session.layout))
    per_scene = out.unbatch()

Training shares the front door: ``session.compile_train()`` returns a
:class:`~repro.train.PointCloudTrainer` whose fused
plan→forward→loss→grad→update step runs under the same bucketing and
updates ``session.params`` in place (backward reuses the forward plan via
the kernel-map-transposed VJPs — ``train.pointcloud`` module doc).

The jit cache *is* the bucket cache: the session pads every input to its
power-of-two capacity bucket, so all requests in a bucket hit one compiled
executable and ``session.compile_count`` == number of distinct buckets seen
(same ``_cache_size`` contract the PR-2 ``BucketedPlanner`` tests rely on).

SparseTensor layout and why batching is free
--------------------------------------------
A :class:`~repro.core.sparse_tensor.SparseTensor` is (features, packed,
count, layout): ``packed[: count]`` strictly ascending deduplicated packed
voxel words, PAD (int max) tail, feature rows aligned. Batched tensors fold
the scene index into the ``BitLayout.bb`` bits — the word's *most
significant* field. That single choice is why the whole indexing pipeline
runs batched without modification:

* **Sortedness is batch-major** — the sorted batched array is the
  concatenation of per-scene sorted arrays, so scene rows are contiguous at
  V0 and stay contiguous at every downsampled level.
* **``round_down`` never touches batch bits** — it clears low bits of the
  x/y/z fields only, so the round-down lemma (sorted input splits into
  ``4^Δ`` interleaved sorted runs keyed by cleared (x, y) residues; see
  ``packing.round_down``) is batch-oblivious and the single-sort merge
  downsample works on batched streams unchanged.
* **The guard band isolates scenes** — weight offsets carry no batch
  component and real x/y/z field values stay ``guard`` away from field
  boundaries, so offset queries can never borrow/carry into the batch field
  and alias a neighboring scene's voxel: kernel maps cannot cross scenes.

Feature computation is batch-aware in exactly one place: BN statistics are
computed per scene (``models.pointcloud._relu_bn`` with the scene segments
recovered from each level's batch bits) through the O(N) segmented-
reduction engine (``kernels.segsum`` — one pass over the row buffer, no
per-scene ``dynamic_slice`` or ``[cap, S]`` one-hot passes), whose
alignment- and zero-extension-invariant add schedule makes a batch-of-B
run *bit-identical* to B single-scene runs, gradients included — tested
in tests/test_session.py and tests/test_segsum.py. The engine backend is
the session's ``segment`` spec (``segment_backend=`` at compile time,
co-tuned on step time under ``tuner="measure"``).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import (LayerTuneResult, apply_tuning, build_network_plan,
                        l1_partition, tune_layer_cost_model,
                        tune_layer_measure, tune_segment_backend_measure,
                        zdelta_offsets)
from repro.core.network_plan import NetworkPlan
from repro.core.packing import BitLayout
from repro.core.sparse_tensor import SparseTensor, ensure_sparse_tensor
from repro.core.spconv import SpConvSpec
from repro.kernels import ops
from repro.kernels.segsum import SegmentSpec
from repro.models.pointcloud import (PointCloudNet, init_pointcloud,
                                     packed_segments, pointcloud_forward)
from repro.obs import MetricsRegistry, span
from .bucketing import bucket_capacity


@dataclasses.dataclass
class HealthReport:
    """Per-call degradation accounting (``SpiraSession.run_with_health``).

    A healthy call has ``ws_dropped_pairs`` all zero: every (input, offset)
    pair the lossless kernel map found was actually computed. Nonzero means
    a WS/hybrid layer's tuned ``ws_capacity`` truncated real pairs *after
    the session exhausted its escalation budget* — the logits are degraded
    the same way a silently-truncated call used to be, but now it is
    reported. ``window_overflow_cells`` is a perf signal only (overflowed
    Pallas superwindow cells are repaired exactly by the XLA fallback)."""

    bucket: int                # final padded capacity the call ran at
    escalation: int            # escalation level of the serving plan
                               # (ws_capacity scaled by 2^escalation)
    replans: int               # extra plan+forward passes taken
    ws_dropped_pairs: Dict[str, int]        # layer -> truncated pairs
    window_overflow_cells: Dict[str, int]   # layer -> overflowed cells

    @property
    def total_ws_dropped(self) -> int:
        return sum(self.ws_dropped_pairs.values())

    @property
    def ok(self) -> bool:
        """No degradation: the served logits equal the lossless network's."""
        return self.total_ws_dropped == 0

    def summary(self) -> str:
        worst = sorted(self.ws_dropped_pairs.items(), key=lambda kv: -kv[1])
        worst = [f"{k}:{v}" for k, v in worst if v][:3]
        return (f"bucket={self.bucket} escalation={self.escalation} "
                f"replans={self.replans} "
                f"ws_dropped={self.total_ws_dropped}"
                f"{' (' + ', '.join(worst) + ')' if worst else ''} "
                f"window_overflows="
                f"{sum(self.window_overflow_cells.values())}")


@dataclasses.dataclass
class SpiraSession:
    """Compiled point-cloud pipeline: ``session(st) -> st`` of logits.

    Built by :func:`compile_network` — do not construct directly unless you
    already hold resolved (tuned) specs. The session is the only hot-path
    entry point; it accepts any :class:`SparseTensor` whose layout matches
    (single-scene or batched up to ``num_scenes``) and any size (bucketed
    internally).

    Overflow escalation (robustness contract): WS/hybrid layers with a
    tuned ``ws_capacity`` silently truncate pairs beyond it
    (``dataflow.ws_kept_map``) — fine for the traffic the tuner saw, wrong
    for a denser-than-tuned scene. Every call therefore returns the
    dropped-pair count per lossy layer (computed inside the jitted graph
    from the plan's own kernel map, one reduction per layer); when nonzero,
    the session *replans at the next escalation level* — capacity bucket
    and every tuned ``ws_capacity`` doubled — up to ``max_overflow_replans``
    times, instead of serving truncated logits. Each escalation level is
    its own jitted executable (the jit cache stays the bucket cache, per
    level); traffic within tuned capacity never pays anything. See
    :class:`HealthReport` / :meth:`run_with_health`.
    """

    net: PointCloudNet
    layout: BitLayout
    params: dict
    engine: str = "zdelta"
    downsample_method: str = "auto"
    min_bucket: int = 1024
    max_bucket: Optional[int] = None
    # segmented-reduction engine config (kernels.segsum) — one spec for the
    # whole network, so every per-scene reduction shares one bit contract;
    # backend co-tuned on step time under tuner="measure"
    segment: SegmentSpec = SegmentSpec()
    # bounded retries for pair-capacity overflow (class doc); 0 restores
    # the old serve-truncated-but-report behavior
    max_overflow_replans: int = 2
    # One observability surface for the whole pipeline (repro.obs): the
    # engine and trainer built on this session inherit this registry, so
    # plan/serve/train metrics export together. Spans stay OUTSIDE the
    # jitted graphs (obs.trace) — instrumentation never changes
    # compile_count or results (pinned in tests/test_obs.py).
    metrics: Optional[MetricsRegistry] = None

    def __post_init__(self):
        if self.metrics is None:
            self.metrics = MetricsRegistry()
        specs = self.net.conv_specs()
        self._fns: Dict[int, object] = {}
        self._os_rows: Dict[int, dict] = {}   # escalation level -> os_rows
        self._fn = self._make_fn(0)   # escalation level 0 = the tuned plan
        self.last_health: Optional[HealthReport] = None
        self._plan_fn = jax.jit(
            lambda packed: build_network_plan(
                packed, specs=specs, layout=self.layout, engine=self.engine,
                downsample_method=self.downsample_method))

    def _escalated_net(self, esc: int) -> PointCloudNet:
        """The network with every lossy ``ws_capacity`` scaled ``2^esc``
        (params are capacity-independent, so they are shared across
        levels)."""
        if esc == 0:
            return self.net
        specs = tuple(
            dataclasses.replace(s, ws_capacity=s.ws_capacity << esc)
            if (s.ws_capacity and s.dataflow in ("ws", "hybrid")) else s
            for s in self.net.conv_specs())
        return dataclasses.replace(self.net, specs=specs)

    def _make_fn(self, esc: int):
        """The jitted plan+forward executable for one escalation level,
        returning health scalars alongside the logits."""
        fn = self._fns.get(esc)
        if fn is not None:
            return fn
        net = self._escalated_net(esc)
        specs = net.conv_specs()
        layout = self.layout
        engine = self.engine
        method = self.downsample_method
        seg_spec = self.segment
        out_level = specs[-1].m_out if specs else 0

        # Lossy layers: WS/hybrid with an explicit pair capacity. For
        # hybrid only the sparse (weight-stationary) offset columns can
        # drop; the split is static (offset L1 norms), resolved here.
        lossy = []
        for s in specs:
            if not s.ws_capacity or s.dataflow not in ("ws", "hybrid"):
                continue
            cols = None
            if s.dataflow == "hybrid":
                _, cols = l1_partition(s.K, s.offset_stride, s.t)
                if cols.size == 0:
                    continue
            lossy.append((s.name, int(s.ws_capacity), cols))
        # Rows of each OS conv's kernel map (its output capacity) and the
        # row tiles its kernel walks, per input capacity: static shapes,
        # noted when ``run`` traces for a capacity.
        os_rows: Dict[int, Dict[str, Tuple[int, int, int]]] = {}
        self._os_rows[esc] = os_rows

        @jax.jit
        def run(params, packed, feats):
            plan = build_network_plan(packed, specs=specs, layout=layout,
                                      engine=engine,
                                      downsample_method=method)
            logits = pointcloud_forward(params, net, plan, feats,
                                        layout=layout, segment=seg_spec)
            with jax.named_scope("outputs"):
                out = plan.coords[out_level]
                # Degradation signals, computed from the plan the call
                # already built: pairs beyond ws_capacity are exactly what
                # dataflow.ws_kept_map will zero out.
                drops = {}
                for name, cap, cols in lossy:
                    m = plan.kmaps[name].m
                    mc = m if cols is None else m[:, cols]
                    pairs = (mc >= 0).sum(axis=0)
                    drops[name] = jnp.maximum(pairs - cap, 0).sum() \
                                     .astype(jnp.int32)
                counts = {m: cs.count for m, cs in plan.coords.items()}
                tiles = {s.name: ops.spconv_os_tiles(plan.kmaps[s.name].m,
                                                     bm=s.bm)
                         for s in specs if s.dataflow == "os"}
                live = sum(n for _, n in tiles.values())
            os_rows[packed.shape[0]] = {
                s.name: (s.m_out, plan.kmaps[s.name].m.shape[0],
                         tiles[s.name][0])
                for s in specs if s.dataflow == "os"}
            return (logits, out.packed, out.count, drops, plan.stats,
                    counts, live)

        self._fns[esc] = run
        return run

    # -- hot path ---------------------------------------------------------

    def __call__(self, st: SparseTensor) -> SparseTensor:
        return self.run_with_health(st)[0]

    def run_with_health(self, st: SparseTensor, *,
                        max_replans: Optional[int] = None
                        ) -> Tuple[SparseTensor, HealthReport]:
        """Run with the escalation loop (class doc) and return
        ``(logits, health)``. ``session(st)`` is sugar for the first
        element; the last report also lands on ``session.last_health``.

        ``max_replans`` caps this CALL's escalation budget below the
        session's ``max_overflow_replans`` (it can only tighten, never
        raise it) — the serving engine's degradation ladder passes 0 under
        sustained overload, serving at the base plan with any WS drops
        flagged on the HealthReport instead of cured by replans."""
        ensure_sparse_tensor(st, where="SpiraSession")
        if st.layout != self.layout:
            raise ValueError(
                f"SparseTensor layout {st.layout} != session layout "
                f"{self.layout}. Build inputs against the session's layout "
                "(session.layout) — e.g. SparseTensor.from_point_clouds("
                "clouds, session.layout) — or compile a session for this "
                "layout with compile_network(net, layout).")
        if st.channels != self.net.in_channels:
            raise ValueError(
                f"SparseTensor has {st.channels} feature channels; "
                f"{self.net.name} expects {self.net.in_channels}.")
        base = self._bucket(st.capacity)
        budget = (self.max_overflow_replans if max_replans is None
                  else min(max_replans, self.max_overflow_replans))
        esc = replans = 0
        while True:
            bucket = self._esc_bucket(base, esc)
            stp = st.pad_to(bucket)
            fn = self._make_fn(esc)
            # Span at the host boundary around the fused plan+forward call
            # and the one fetch of its plan-side scalars (WS drops, window
            # overflows, each level's voxel count, the OS convs' live row
            # tiles). Those depend on the plan alone, but a call's outputs
            # all become ready when it ends, so the fetch waits for the
            # forward too; fetching and unpacking the logits is the
            # reader's (the serve engine's serve/answer span). Escalated
            # retries record separately as session/replan.
            with span("session/call" if esc == 0 else "session/replan",
                      self.metrics):
                logits, out_packed, out_count, drops, ovf, counts, live = fn(
                    self.params, stp.packed, stp.features)
                dropped, ovf, counts, live = jax.device_get(
                    (drops, ovf, counts, live))
            self._count_rows(self._os_rows[esc][bucket], counts, live)
            if sum(dropped.values()) == 0 or esc >= budget:
                break
            esc += 1
            replans += 1
        health = HealthReport(
            bucket=bucket, escalation=esc, replans=replans,
            ws_dropped_pairs={k: int(v) for k, v in dropped.items()},
            window_overflow_cells={k: int(v) for k, v in ovf.items()})
        self.last_health = health
        self._record_health(health)
        # Logits live on the network's OUTPUT level coordinate set (== the
        # input set only for submanifold-ending segmentation nets).
        out = SparseTensor(features=logits, packed=out_packed,
                           count=out_count, layout=self.layout)
        return out, health

    def _count_rows(self, os_rows: Dict[str, Tuple[int, int, int]],
                    counts: Mapping[int, int], live: int) -> None:
        """Rows the OS convs of one call walk (their maps' rows, the output
        capacity), and of those the rows that hold a voxel of the output
        level: ``spconv_rows_walked`` less ``spconv_rows_real`` is the
        padding the OS kernel walks. Likewise its row tiles:
        ``spconv_tiles_walked`` less ``spconv_tiles_live`` are the dead
        tiles whose work the kernel skips."""
        walked = real = tiles = 0
        for m_out, rows, n_tiles in os_rows.values():
            walked += rows
            real += min(int(counts[m_out]), rows)
            tiles += n_tiles
        self.metrics.counter("spconv_rows_walked").inc(walked)
        self.metrics.counter("spconv_rows_real").inc(real)
        self.metrics.counter("spconv_tiles_walked").inc(tiles)
        self.metrics.counter("spconv_tiles_live").inc(int(live))

    def _record_health(self, health: HealthReport) -> None:
        """Fold one call's HealthReport into the registry: run/replan
        counters, bucket/escalation gauges, and the per-layer kernel-map
        stats — WS drops from the health report, window-overflow cells
        lifted from ``NetworkPlan.stats`` — as per-layer gauges."""
        reg = self.metrics
        reg.counter("session_runs").inc()
        if health.replans:
            reg.counter("session_replans").inc(health.replans)
        reg.gauge("session_bucket").set(health.bucket)
        reg.gauge("session_escalation").set(health.escalation)
        for name, v in health.ws_dropped_pairs.items():
            reg.gauge(f"session_ws_dropped_pairs_{name}").set(v)
        for name, v in health.window_overflow_cells.items():
            reg.gauge(f"plan_window_overflow_cells_{name}").set(v)

    def _esc_bucket(self, base_bucket: int, esc: int) -> int:
        """Escalated capacity bucket: the next pow2 bucket per level,
        clamped to ``max_bucket`` (ws_capacity keeps scaling even when the
        bucket has hit the ceiling — it is what removes the drops)."""
        b = base_bucket << esc
        if self.max_bucket is not None and b > self.max_bucket:
            b = max(base_bucket, self.max_bucket)
        return b

    def compile_train(self, tcfg=None, *, opt_state=None, guard=None,
                      ckpt=None, resume: bool = False):
        """Training entry point: a :class:`~repro.train.PointCloudTrainer`
        bound to this session.

        The trainer fuses plan→forward→loss→grad→update into one jitted
        graph per capacity bucket (the same pow2 bucketing as inference —
        its jit cache is its bucket cache) and updates ``self.params`` in
        place each step, so the session serves the trained weights
        immediately. The backward pass reuses the forward plan via the
        kernel-map-transposed custom VJPs in ``core.dataflow`` — zero extra
        kernel-map searches per step (``train.pointcloud`` module doc).

        Any of ``guard`` / ``ckpt`` / ``resume`` upgrades the result to a
        :class:`~repro.train.guard.GuardedPointCloudTrainer` — the
        self-healing trainer (``train.guard`` module doc): in-graph
        non-finite skip, loss-spike skip, per-scene bisection quarantine,
        checkpoint rollback, typed abort.

        * ``guard`` — a :class:`~repro.train.guard.GuardConfig`, or
          ``True`` for the defaults.
        * ``ckpt`` — a :class:`~repro.ckpt.CheckpointManager` or a
          directory path; enables the auto-checkpoint cadence
          (``GuardConfig.ckpt_every``), the ``last_good`` rollback anchor
          and crash-safe resume.
        * ``resume=True`` — restore the newest *verifying* checkpoint from
          ``ckpt`` before the first step (torn/corrupt checkpoints are
          walked past), so a restarted run continues instead of starting
          over.
        """
        if guard is None and ckpt is None and not resume:
            from repro.train.pointcloud import PointCloudTrainer
            return PointCloudTrainer(self, tcfg, opt_state=opt_state)
        from repro.train.guard import GuardConfig, GuardedPointCloudTrainer
        if guard is True:
            guard = GuardConfig()
        if resume and ckpt is None:
            raise ValueError("compile_train(resume=True) needs ckpt= (a "
                             "CheckpointManager or directory) to resume "
                             "from")
        return GuardedPointCloudTrainer(self, tcfg, guard=guard, ckpt=ckpt,
                                        opt_state=opt_state, resume=resume)

    def plan(self, st: SparseTensor) -> NetworkPlan:
        """The network plan the session would use for ``st`` (bucketed) —
        for inspection/benchmarks; the hot path fuses this into ``run``."""
        ensure_sparse_tensor(st, where="SpiraSession.plan")
        stp = st.pad_to(self._bucket(st.capacity))
        return self._plan_fn(stp.packed)

    def _bucket(self, n: int) -> int:
        return bucket_capacity(n, min_bucket=self.min_bucket,
                               max_bucket=self.max_bucket)

    # -- facts ------------------------------------------------------------

    @property
    def num_scenes(self) -> int:
        """Scene slots per call (1 << layout.bb); any B <= this works."""
        return 1 << self.layout.bb

    @property
    def compile_count(self) -> int:
        """Compiled executables so far — one per distinct (capacity bucket,
        escalation level) pair; without overflow traffic that is exactly
        one per bucket (the jit cache is the bucket cache)."""
        total = 0
        for fn in self._fns.values():
            cache_size = getattr(fn, "_cache_size", None)
            if cache_size is None:
                return -1
            total += int(cache_size())
        return total

    def __repr__(self):
        return (f"SpiraSession({self.net.name}, engine={self.engine!r}, "
                f"scenes<={self.num_scenes}, layout={self.layout}, "
                f"compiled_buckets={self.compile_count})")


TunerArg = Union[None, str, Mapping[str, LayerTuneResult]]


def compile_network(
    net: PointCloudNet,
    layout: BitLayout,
    *,
    params: Optional[dict] = None,
    key: Optional[jax.Array] = None,
    batch: int = 1,
    engine: str = "zdelta",
    downsample_method: str = "auto",
    min_bucket: int = 1024,
    max_bucket: Optional[int] = None,
    tuner: TunerArg = None,
    tune_sample: Optional[SparseTensor] = None,
    segment_backend: str = "auto",
    max_overflow_replans: int = 2,
    dtype=jnp.float32,
    metrics: Optional[MetricsRegistry] = None,
) -> SpiraSession:
    """Build a :class:`SpiraSession` — the compile-once front door.

    * ``batch`` widens the layout's batch field to hold that many scenes
      (no-op if ``layout`` already carries enough batch bits). One session
      then serves any 1..batch scenes per call.
    * ``params`` — network parameters; freshly initialized from ``key``
      (default ``jax.random.key(0)``) when omitted.
    * ``tuner`` — absorbs the one-time §5.4 tuning step:
        - ``None``: use the specs as authored.
        - ``"cost_model"``: analytic per-layer (t, backend, symmetry) choice
          from a sample plan's kernel-map statistics (device-free;
          ``tune_sample`` required).
        - ``"measure"``: wall-clock joint (t, backend, bm, bn) sweep plus
          exact superwindow sizing (``plan_superwindow``) per layer
          (``tune_sample`` required; honest on TPU, indicative on CPU).
        - a mapping ``{layer_name: LayerTuneResult}``: precomputed results
          (e.g. persisted from a previous run), applied via
          ``core.tuner.apply_tuning``.
      Tuned specs are persisted on the session's network — the session IS
      the tuner persistence.
    * ``max_overflow_replans`` — escalation budget for pair-capacity
      overflow (:class:`SpiraSession` class doc); 0 serves truncated logits
      but still reports the drops in the HealthReport.
    * ``segment_backend`` — the segmented-reduction engine backend
      ("auto" | "xla" | "pallas"; ``kernels.segsum``) shared by every
      per-scene BN/pooling/loss reduction. Under ``tuner="measure"`` it is
      co-tuned on *step* time (fwd + transposed bwd —
      ``core.tuner.tune_segment_backend_measure``, the train-mode
      objective) and the tuned spec persisted on the session.
    * ``metrics`` — a shared :class:`~repro.obs.MetricsRegistry`; the
      session (and any engine/trainer built on it) records there. Omitted,
      the session creates a private one at ``session.metrics``.
    """
    if (1 << layout.bb) < batch:
        layout = layout.with_batch(batch)
    if params is None:
        params = init_pointcloud(key if key is not None else jax.random.key(0),
                                 net, dtype)
    seg_spec = SegmentSpec(backend=segment_backend)
    if tuner is not None:
        specs = _tune_specs(net, layout, params, tuner, tune_sample,
                            engine=engine, downsample_method=downsample_method,
                            min_bucket=min_bucket)
        net = dataclasses.replace(net, specs=specs)
        if tuner == "measure":
            seg_spec = _tune_segment(seg_spec, tune_sample,
                                     min_bucket=min_bucket)
    return SpiraSession(net=net, layout=layout, params=params, engine=engine,
                        downsample_method=downsample_method,
                        min_bucket=min_bucket, max_bucket=max_bucket,
                        segment=seg_spec,
                        max_overflow_replans=max_overflow_replans,
                        metrics=metrics)


def _tunable_backends() -> Tuple[str, ...]:
    """Backends worth timing: off-TPU "pallas" would time the interpreter."""
    return ("xla", "pallas") if ops.on_tpu() else ("xla",)


def _tune_segment(seg_spec: SegmentSpec, tune_sample: SparseTensor, *,
                  min_bucket: int) -> SegmentSpec:
    """Measure the segment-engine backend on the sample's V0 segmentation
    (step-time objective) and persist the winner on the spec."""
    stp = tune_sample.pad_to(bucket_capacity(tune_sample.capacity,
                                             min_bucket=min_bucket))
    seg = packed_segments(stp.packed, stp.count, stp.layout)
    res = tune_segment_backend_measure(
        stp.features, seg, q=seg_spec.q, backends=_tunable_backends())
    return dataclasses.replace(seg_spec, backend=res.backend)


def _tune_specs(net: PointCloudNet, layout: BitLayout, params: dict,
                tuner: TunerArg, tune_sample: Optional[SparseTensor], *,
                engine: str, downsample_method: str,
                min_bucket: int) -> Tuple[SpConvSpec, ...]:
    """Resolve ``tuner`` into a tuned spec tuple (see compile_network)."""
    if isinstance(tuner, Mapping):
        return tuple(apply_tuning(s, tuner[s.name]) if s.name in tuner else s
                     for s in net.specs)
    if tuner not in ("cost_model", "measure"):
        raise ValueError(f"tuner must be None, 'cost_model', 'measure' or a "
                         f"{{layer: LayerTuneResult}} mapping, got {tuner!r}")
    if tune_sample is None:
        raise ValueError(f"tuner={tuner!r} needs tune_sample= (a "
                         "representative SparseTensor) to build the sample "
                         "plan it tunes against")
    ensure_sparse_tensor(tune_sample, where="compile_network(tune_sample=)")
    stp = tune_sample.pad_to(bucket_capacity(tune_sample.capacity,
                                             min_bucket=min_bucket))
    plan = build_network_plan(stp.packed, specs=net.conv_specs(),
                              layout=layout, engine=engine,
                              downsample_method=downsample_method)
    backends = _tunable_backends()
    tuned = []
    for s in net.specs:
        kmap = plan.kmaps[s.name]
        if tuner == "cost_model":
            res = tune_layer_cost_model(
                kmap, K=s.K, stride=s.offset_stride, cin=s.cin, cout=s.cout,
                backends=backends, submanifold=s.submanifold)
        else:
            feats = jax.random.normal(jax.random.key(hash(s.name) & 0xffff),
                                      (plan.coords[s.m_in].capacity, s.cin),
                                      jnp.float32)
            _, anchors, zstep = zdelta_offsets(s.K, s.offset_stride, layout)
            coords = (plan.coords[s.m_in], plan.coords[s.m_out], anchors,
                      zstep)
            res = tune_layer_measure(
                feats, kmap, params[s.name]["w"], K=s.K,
                stride=s.offset_stride, ws_capacity=kmap.m.shape[0],
                backends=backends, coords=coords, submanifold=s.submanifold)
        tuned.append(apply_tuning(s, res))
    return tuple(tuned)
