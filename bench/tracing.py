"""Reduction of a profiler trace to the benchmark's device readings.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
operations are the events of the ``XLA Ops`` line of every ``/device:TPU:n``
plane; the benchmark's own host spans (``jax.profiler.TraceAnnotation``
names starting with ``bench/``) are the events of the host plane's lines.
Both are on the profiler's one clock.

* Busy time: the union of the device operations' intervals inside the
  traced window, averaged over the chips; idle share is one minus busy
  over the window.
* Kernel time: operations nest (a while loop holds the operations of its
  body), so each operation is charged its self time, its length less the
  operations nested in it, and self times are summed by kernel. A kernel is
  named by its HLO name without the ``%`` and the ``.n`` suffix
  (``spconv_gather_gemm``, ``segment_sum_pallas``), which is the name of
  the jitted function that calls ``pallas_call``; on the TPU an event's
  name is the whole HLO instruction, ``%name.n = type op(...)``, and only
  the name before `` = `` counts. Pallas kernels are told apart from XLA's
  own operations by the ``pallas_call`` in their op name, the
  ``tpu_custom_call`` target in their instruction, or by name.
* Idle gaps: the stretches of the window in which no device operation
  runs, cut where a ``bench/`` span opens or closes, each piece named by
  the innermost span open over it and by whether it comes before the
  span's first device operation, after its last, or between; summed by
  name.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
import warnings
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
PALLAS_KERNELS = ("spconv_gather_gemm", "segment_sum_pallas",
                  "ws_scatter_gemm", "masked_group_gemm")


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float          # ns, profiler clock
    end: float            # ns
    op: str = ""          # op name metadata (jit path), where recorded

    @property
    def kernel(self) -> str:
        head = self.name.split(" = ", 1)[0].lstrip("%")
        base, _, suffix = head.rpartition(".")
        return base if base and suffix.isdigit() else head

    @property
    def pallas(self) -> bool:
        return ("pallas_call" in self.op or "tpu_custom_call" in self.name
                or self.kernel in PALLAS_KERNELS)


def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def _quiet(fn):
    """Reading event stats makes jaxlib warn that their type has no
    module; the warning says nothing about the trace."""
    @functools.wraps(fn)
    def wrapped(*a, **kw):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            return fn(*a, **kw)
    return wrapped


def load(trace_dir: str):
    """The ``ProfileData`` of the one trace written under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, found "
                           f"{len(files)}")
    return ProfileData.from_file(files[0])


@_quiet
def device_ops(profile, prefix: str = DEVICE_PREFIX,
               line_name: str = OPS_LINE) -> Dict[str, List[Ev]]:
    """Operations per device plane; empty when the trace has none."""
    out: Dict[str, List[Ev]] = {}
    for plane in profile.planes:
        name = plane.name
        if not (name.startswith(prefix) and name[len(prefix):].isdigit()):
            continue
        evs = []
        for line in plane.lines:
            if line.name != line_name:
                continue
            for e in line.events:
                st = _stats(e)
                op = str(st.get("tf_op", "") or st.get("long_name", ""))
                evs.append(Ev(e.name, float(e.start_ns),
                              float(e.start_ns) + float(e.duration_ns), op))
        out[name] = evs
    return out


@_quiet
def cpu_ops(profile) -> Dict[str, List[Ev]]:
    """Operations the XLA CPU client ran: host events with an ``hlo_op``.
    Only the tests, which run the harness on the CPU, read these."""
    evs = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                st = _stats(e)
                if "hlo_op" in st:
                    evs.append(Ev(str(st["hlo_op"]), float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns)))
    return {"/host:CPU": evs} if evs else {}


def host_spans(profile, prefix: str = SPAN_PREFIX) -> List[Ev]:
    """The benchmark's own spans, from every host plane."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append(Ev(e.name, float(e.start_ns),
                                  float(e.start_ns) + float(e.duration_ns)))
    return out


def clip(evs: Iterable[Ev], lo: float, hi: float) -> List[Ev]:
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in evs if e.end > lo and e.start < hi]


def merged(evs: Iterable[Ev]) -> List[Tuple[float, float]]:
    """The union of the events' intervals, as sorted disjoint intervals."""
    out: List[List[float]] = []
    for s, e in sorted((e.start, e.end) for e in evs):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(evs: Sequence[Ev]) -> List[Tuple[Ev, float]]:
    """Each event with its length less that of the events nested in it."""
    order = sorted(evs, key=lambda e: (e.start, -e.end))
    child = defaultdict(float)
    stack: List[Ev] = []
    for e in order:
        while stack and stack[-1].end <= e.start:
            stack.pop()
        if stack:
            parent = stack[-1]
            child[id(parent)] += min(e.end, parent.end) - e.start
        stack.append(e)
    return [(e, max(e.end - e.start - child[id(e)], 0.0)) for e in order]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _gap_name(gap: Tuple[float, float], spans: Sequence[Ev],
              busy: List[Tuple[float, float]]) -> str:
    mid = 0.5 * (gap[0] + gap[1])
    open_ = [s for s in spans if s.start <= mid < s.end]
    if not open_:
        return "outside bench spans"
    sp = min(open_, key=lambda s: s.end - s.start)
    inside = [b for b in busy if b[1] > sp.start and b[0] < sp.end]
    if not inside or mid < inside[0][0]:
        where = "before device work"
    elif mid > inside[-1][1]:
        where = "after device work"
    else:
        where = "between device ops"
    return f"{sp.name} ({where})"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # averaged over chips
    kernel_s: Dict[str, float]        # self time by kernel, per chip
    pallas_s: float
    xla_s: float
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(ops: Dict[str, List[Ev]], spans: Sequence[Ev],
              window: Tuple[float, float], top: int = 10) -> Summary:
    """Readings of the window ``(lo, hi)`` (ns) from every chip's
    operations; kernel and gap times are averaged over the chips."""
    lo, hi = window
    if hi <= lo:
        raise ValueError(f"empty trace window {window}")
    if not ops:
        raise RuntimeError("the trace holds no device plane")
    n = len(ops)
    busy_ns = 0.0
    kernel = defaultdict(float)
    pallas = xla = 0.0
    gap_by = defaultdict(float)
    for evs in ops.values():
        evs = clip(evs, lo, hi)
        busy = merged(evs)
        busy_ns += sum(e - s for s, e in busy)
        for e, t in self_times(evs):
            kernel[e.kernel] += t
            if e.pallas:
                pallas += t
            else:
                xla += t
        for a, b in gaps(busy, lo, hi):
            cuts = sorted({a, b} | {t for sp in spans for t in (sp.start, sp.end)
                                    if a < t < b})
            for piece in zip(cuts, cuts[1:]):
                gap_by[_gap_name(piece, spans, busy)] += piece[1] - piece[0]
    s = 1e-9 / n
    kernel_s = {k: v * s for k, v in kernel.items()}
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_ns * s, kernel_s=kernel_s,
        pallas_s=pallas * s, xla_s=xla * s,
        device_ops=sorted(kernel_s.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(((k, v * s) for k, v in gap_by.items()),
                         key=lambda kv: -kv[1])[:top])


def window_of(spans: Sequence[Ev], name: str) -> Optional[Tuple[float, float]]:
    """The interval of the one span called ``name``."""
    hits = [s for s in spans if s.name == name]
    if len(hits) != 1:
        return None
    return hits[0].start, hits[0].end
