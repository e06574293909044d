"""Pieces the modes share: weights, the program's network, the measured
window and its trace, sampling and percentiles."""
from __future__ import annotations

import logging
import math
import shutil
import tempfile
import time
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import harness, peaks, reference, tracing


def make_params(cell: harness.Cell):
    """The weights, made on the device from the seed in one jitted call of
    the configuration's ``init_params``."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cell.config["dtype"])
    init = jax.jit(partial(cell.hook("init_params"), net=cell.net,
                           dtype=dtype))
    params = init(reference.seed_key(cell.seed))
    jax.block_until_ready(params)
    return params


def precision(cell: harness.Cell):
    """The matmul precision the configuration states, for the reference."""
    return reference.precision(cell.config["matmul_precision"])


def program_net(cfg: dict, net):
    """The program's network for configuration ``cfg``: one ``SpConvSpec``
    per layer of the reference's description ``net``, at the program's
    defaults."""
    from repro.core import SpConvSpec
    from repro.models.pointcloud import PointCloudNet
    specs = tuple(SpConvSpec(L.name, L.cin, L.cout, K=L.K, m_in=L.m_in,
                             m_out=L.m_out) for L in net.layers)
    return PointCloudNet(cfg["network"], specs, net.in_channels,
                         net.n_classes)


def checked_program_net(cell: harness.Cell):
    """The configuration's ``program_net``, whose convs must be the
    reference's layers: the program runs whatever weights it is given, so
    a conv that differs would go unseen. Raises naming the first that
    differs, before anything is compiled."""
    net = cell.hook("program_net")(cell.config, cell.net)
    have = {s.name: (s.cin, s.cout, s.K, s.m_in, s.m_out)
            for s in net.conv_specs()}
    for L in cell.net.layers:
        want = (L.cin, L.cout, L.K, L.m_in, L.m_out)
        got = have.pop(L.name, None)
        if got != want:
            raise ValueError(
                f"the program's conv {L.name!r} (cin, cout, K, m_in, m_out) "
                f"is {got}, the reference's layer {want}")
    if have:
        raise ValueError(f"the program's convs {sorted(have)} are no layers "
                         "of the reference")
    return net


def one_per_bucket(session, sizes: Sequence[int]) -> List[int]:
    """Indices of one item per capacity bucket the session puts ``sizes``
    (voxels per call) in: warming these up compiles every program the
    window will run."""
    from repro.serve.bucketing import bucket_capacity
    first = {}
    for i, n in enumerate(sizes):
        b = bucket_capacity(n, min_bucket=session.min_bucket,
                            max_bucket=session.max_bucket)
        first.setdefault(b, i)
    return sorted(first.values())


def percentile(values: Sequence[float], p: float) -> float:
    """Nearest-rank percentile."""
    if not values:
        return math.nan
    v = sorted(values)
    return v[max(math.ceil(p / 100.0 * len(v)), 1) - 1]


def sample(items: List, n: int, seed: int, *, size: Callable,
           key: Callable = lambda it: it[0]) -> List:
    """Up to ``n`` items of distinct ``key``: the largest by ``size``, and
    the rest drawn from ``seed``."""
    distinct = {}
    for it in items:
        distinct.setdefault(key(it), it)
    pool = list(distinct.values())
    if not pool:
        return []
    first = max(pool, key=size)
    rest = [it for it in pool if it is not first]
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(rest))
    return [first] + [rest[i] for i in order[:max(n - 1, 0)]]


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = {"n": 0, "listening": False}


def _count_compile(name, *args, **kwargs):
    if name == COMPILE_EVENT:
        _compiles["n"] += 1


def compiles() -> int:
    """Programs JAX has compiled in this process since the first call."""
    if not _compiles["listening"]:
        import jax
        jax.monitoring.register_event_duration_secs_listener(_count_compile)
        _compiles["listening"] = True
    return _compiles["n"]


class _CompileLog(logging.Handler):
    """What JAX logs of its compilations while ``jax_log_compiles`` is on."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.lines: List[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage().splitlines()[0][:200])


class Window:
    """The measured window: its host clock, its ``bench/window`` span and,
    with ``cell.trace``, the profiler trace around it. A window in which
    JAX compiles anything, the program's own operations on a shape that
    set-up did not warm up among them, raises when it closes, naming what
    JAX logged of those compilations."""

    def __init__(self, cell: harness.Cell):
        self.trace = cell.trace
        self._ops = tracing.device_ops if cell.on_chip else tracing.cpu_ops
        self._dir: Optional[str] = None
        self._ann = None
        self.start = self.end = None

    def __enter__(self) -> "Window":
        import jax
        if self.trace:
            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self._dir)
        self._ann = annotate("bench/window")
        self._ann.__enter__()
        self._log = _CompileLog()
        logging.getLogger("jax").addHandler(self._log)
        self._logged = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._compiled = compiles()
        self.start = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.start

    def __exit__(self, *exc):
        import jax
        self.end = time.perf_counter()
        n = compiles() - self._compiled
        jax.config.update("jax_log_compiles", self._logged)
        logging.getLogger("jax").removeHandler(self._log)
        self._ann.__exit__(*exc)
        if self.trace:
            jax.profiler.stop_trace()
        if n and exc[0] is None:
            said = "; ".join(self._log.lines[:40])
            raise RuntimeError(f"{n} programs compiled inside the window: "
                               f"{said}")
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def summary(self) -> tracing.Summary:
        """The trace reduced to the window's readings; the trace files go."""
        try:
            prof = tracing.load(self._dir)
            spans = tracing.host_spans(prof)
            win = tracing.window_of(spans, "bench/window")
            if win is None:
                raise RuntimeError("the trace holds no bench/window span")
            return tracing.summarize(self._ops(prof), spans, win)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)


def context(cell: harness.Cell, devs, summary: tracing.Summary, *,
            units: int, work: Dict[str, float],
            spans: Dict[str, tuple]) -> dict:
    """What the per-layer readers read."""
    return {"mode": cell.workload["mode"], "trace": summary, "units": units,
            "work": work, "spans": spans,
            "peaks": peaks.peak(devs[0].device_kind)}


def breakdown(summary: tracing.Summary) -> dict:
    return {"device_ops": [[k, v] for k, v in summary.device_ops],
            "idle_gaps": [[k, v] for k, v in summary.idle_gaps]}
