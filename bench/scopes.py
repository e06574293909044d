#!/usr/bin/env python3
"""Device time by program scope, and idle time by program span.

    python3 bench/scopes.py --workload <serve cell> --seed <n> --seconds <s>

The serving program names its stages with ``jax.named_scope``: a stage of
the plan (``plan/sort``, ``plan/downsample``, ``plan/search``,
``plan/segments``), a layer's ``<layer>/conv`` or ``<layer>/norm``,
``head`` and ``outputs``. Its host spans (``repro.obs.span``) are profiler
annotations named by their path (``serve/pack``, ``serve/dispatch``,
``serve/dispatch/session/call``, ``serve/answer``).

* Scope time: each device operation's self time (``tracing.self_times``)
  charged to the innermost program scope in its op-name path. The TPU's
  trace events carry no op name, only the instruction, so the path is
  read from the compiled program's HLO text (``metadata={op_name=...}``)
  by instruction name, for the events of that program's module (the
  ``XLA Modules`` line of the device plane). An instruction whose path
  names no scope (XLA's own copies and split reductions carry none, and a
  while loop's body names its ops from the body alone) takes the scope of
  the instruction that calls its computation, else of its operands, else
  of its users (:func:`hlo_scopes`). The rest, the operations of other
  programs among them, is ``unscoped``.
* Idle gaps by program span: ``tracing.summarize`` given the program's
  spans beside the benchmark's names each idle piece by the innermost of
  either; busy, kernel and idle-share readings do not depend on the spans.

Run as a command, it serves the cell's scans through the program's engine
as the serve mode does (set-up, one warm-up per bucket, a closed loop for
``--seconds``) under a profiler trace, and prints one JSON line: device
time per scan by scope, the ``unscoped`` share of busy time, idle time by
span, the share of the OS convs' rows that are padding (the session's
``spconv_rows_walked`` and ``spconv_rows_real`` counters) and the mean
``serve/answer`` span. The benchmark's own runs do not call this module.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import tracing  # noqa: E402

MODULES_LINE = "XLA Modules"
# the program's span namespaces (serve engine, session, trainer, checkpoints)
PROGRAM_SPANS = ("serve/", "session/", "train/", "ckpt/")
UNSCOPED = "unscoped"
PLAN_STAGES = ("sort", "downsample", "search", "segments")
LAYER_STAGES = ("conv", "norm")
TOP_SCOPES = ("head", "outputs")
_LAYER = re.compile(r"^[A-Za-z_][\w.\-]*$")


@dataclasses.dataclass(frozen=True)
class ModuleEv(tracing.Ev):
    """A device operation and the HLO module (jitted program) it ran in."""
    module: str = ""

    @property
    def instr(self) -> str:
        """The HLO instruction's name: ``fusion.12``."""
        return self.name.split(" = ", 1)[0].lstrip("%")


def scope_of(op: str) -> Optional[str]:
    """The innermost program scope named in an op-name path, or None.

    ``jit(run)/jit(build_network_plan)/plan/search/jit(zdelta_search)/...``
    is ``plan/search``; ``jit(run)/s1_conv/norm/mul`` is ``s1_conv/norm``.
    """
    segs = [p.split(":", 1)[0] for p in op.split("/")]
    for i in range(len(segs) - 1, -1, -1):
        seg = segs[i]
        if seg in TOP_SCOPES:
            return seg
        if i and seg in PLAN_STAGES and segs[i - 1] == "plan":
            return f"plan/{seg}"
        if i and seg in LAYER_STAGES and _LAYER.match(segs[i - 1]):
            return f"{segs[i - 1]}/{seg}"
    return None


_INSTR = re.compile(r"^\s*(?:ROOT )?%([^\s=]+) = .*?\s([\w\-]+)\(")
_COMP = re.compile(r"^(?:ENTRY )?%([^\s(]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def hlo_module(text: str) -> str:
    """The module name of a compiled program's HLO text: ``jit_run``."""
    m = re.match(r"\s*HloModule ([^\s,]+)", text)
    return m.group(1) if m else ""


def hlo_scopes(text: str) -> Dict[str, str]:
    """The program scope of each instruction of a compiled program's HLO
    text, by instruction name. An instruction whose op name names no scope
    takes the scope of the instruction that calls its computation (a
    fusion, a while loop, a call), else of an operand, else of a user;
    instructions that none of these reach are left out."""
    comp_of: Dict[str, str] = {}
    refs: Dict[str, List[str]] = {}
    scope: Dict[str, str] = {}
    comps = set()
    comp = ""
    for line in text.splitlines():
        c = _COMP.match(line)
        if c:
            comp = c.group(1)
            comps.add(comp)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(1)
        comp_of[name] = comp
        refs[name] = _REF.findall(_OP_NAME.sub("", line.split(" = ", 1)[1]))
        op = _OP_NAME.search(line)
        sc = scope_of(op.group(1)) if op else None
        if sc is not None:
            scope[name] = sc
    operands = {n: [r for r in rs if r in comp_of] for n, rs in refs.items()}
    users: Dict[str, List[str]] = defaultdict(list)
    for n, ops in operands.items():
        for o in ops:
            users[o].append(n)
    caller: Dict[str, str] = {}
    for n, rs in refs.items():
        for c in rs:
            if c in comps:
                caller.setdefault(c, n)
    changed = True
    while changed:
        changed = False
        for n in comp_of:
            if n in scope:
                continue
            for src in ([caller.get(comp_of[n], "")], operands[n], users[n]):
                sc = next((scope[x] for x in src if x in scope), None)
                if sc is not None:
                    scope[n] = sc
                    changed = True
                    break
    return scope


def in_modules(evs: Sequence[tracing.Ev],
               runs: Sequence[Tuple[float, float, str]]) -> List[ModuleEv]:
    """The events, each tagged with the module whose run ``(start, end,
    module)`` holds its start; untagged where none does."""
    runs = sorted(runs)
    starts = [r[0] for r in runs]
    out = []
    for e in evs:
        i = bisect.bisect_right(starts, e.start) - 1
        mod = runs[i][2] if i >= 0 and e.start < runs[i][1] else ""
        out.append(ModuleEv(e.name, e.start, e.end, e.op, mod))
    return out


@tracing._quiet
def module_ops(profile, on_chip: bool = True) -> Dict[str, List[ModuleEv]]:
    """Device operations per chip (``tracing.device_ops``) tagged with
    their module from the plane's ``XLA Modules`` line; off the chip, the
    XLA CPU client's operations tagged by their ``hlo_module`` stat."""
    if not on_chip:
        evs = []
        for plane in profile.planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        evs.append(ModuleEv(
                            str(st["hlo_op"]), float(e.start_ns),
                            float(e.start_ns) + float(e.duration_ns),
                            module=str(st.get("hlo_module", ""))))
        return {"/host:CPU": evs} if evs else {}
    runs = {}
    for plane in profile.planes:
        runs[plane.name] = [
            (float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name.split("(", 1)[0])
            for line in plane.lines if line.name == MODULES_LINE
            for e in line.events]
    return {name: in_modules(evs, runs.get(name, []))
            for name, evs in tracing.device_ops(profile).items()}


def program_spans(profile) -> List[tracing.Ev]:
    """The program's ``repro.obs`` spans, from every host plane."""
    return [s for p in PROGRAM_SPANS for s in tracing.host_spans(profile, p)]


def scope_times(ops: Dict[str, List[ModuleEv]], window: Tuple[float, float],
                programs: Optional[Dict[str, Dict[str, str]]] = None
                ) -> Dict[str, float]:
    """Self time (s, averaged over the chips) inside ``window`` (ns) by
    program scope: an operation's scope is that of its instruction in its
    module's entry of ``programs`` (module name -> :func:`hlo_scopes`),
    else ``unscoped``."""
    programs = programs or {}
    out: Dict[str, float] = defaultdict(float)
    for evs in ops.values():
        for e, t in tracing.self_times(tracing.clip(evs, *window)):
            out[programs.get(e.module, {}).get(e.instr, UNSCOPED)] += t
    s = 1e-9 / max(len(ops), 1)
    return {k: v * s for k, v in out.items()}


def serving_program(session, scan, feats) -> Dict[str, Dict[str, str]]:
    """The scope of each instruction of the session's serving program at
    the scan's capacity bucket, under its module's name. It lowers the
    program again, and compiles it or finds it in the compilation cache."""
    from repro.core.sparse_tensor import SparseTensor
    st = SparseTensor.from_point_clouds([(scan.coords, feats)],
                                        session.layout)
    stp = st.pad_to(session._bucket(st.capacity))
    text = session._make_fn(0).lower(session.params, stp.packed,
                                     stp.features).compile().as_text()
    return {hlo_module(text): hlo_scopes(text)}


def stage(scope: str) -> str:
    """A layer's scope by its stage (``conv``, ``norm``); others as they
    are."""
    head, _, tail = scope.rpartition("/")
    return tail if tail in LAYER_STAGES and head != "plan" else scope


SPANS = ("serve/pack", "serve/answer")
COUNTERS = ("spconv_rows_walked", "spconv_rows_real")


def report(cell, devs) -> dict:
    """Serve the cell's scans for ``cell.seconds`` under a profiler trace
    and read the trace by program scope and span."""
    import jax
    from bench.modes import common, serve

    pool, feats, _, session, engine = serve.build(cell)
    for k in common.one_per_bucket(session, [len(s.coords) for s in pool]):
        serve.serve_one(engine, pool[k], feats[k])
    reg = engine.metrics
    spans0 = {k: (reg.histogram(k).count, reg.histogram(k).sum)
              for k in SPANS}
    counters0 = {k: reg.counter(k).value for k in COUNTERS}
    d = tempfile.mkdtemp(prefix="bench_scopes_")
    try:
        jax.profiler.start_trace(d)
        n, failed = 0, 0
        with common.annotate("bench/window"):
            t = time.perf_counter()
            while time.perf_counter() - t < cell.seconds:
                k = n % len(pool)
                with common.annotate("bench/request"):
                    req, _ = serve.serve_one(engine, pool[k], feats[k])
                failed += req.outcome != "ok"
                n += 1
        jax.profiler.stop_trace()
        prof = tracing.load(d)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    programs = serving_program(session, pool[0], feats[0])
    spans = tracing.host_spans(prof)
    win = tracing.window_of(spans, "bench/window")
    ops = module_ops(prof, cell.on_chip)
    summary = tracing.summarize(ops, spans + program_spans(prof), win)
    scopes = scope_times(ops, win, programs)
    by_stage: Dict[str, float] = defaultdict(float)
    for k, v in scopes.items():
        by_stage[stage(k)] += v
    walked, real = (reg.counter(k).value - counters0[k] for k in COUNTERS)
    spans_d = {k: (reg.histogram(k).count - spans0[k][0],
                   reg.histogram(k).sum - spans0[k][1]) for k in SPANS}
    return {
        "workload": cell.name, "seed": cell.seed, "scans": n,
        "failed": failed, "window_s": summary.window_s,
        "busy_s": summary.busy_s,
        "unscoped_share_of_busy": 100.0 * scopes.get(UNSCOPED, 0.0)
        / summary.busy_s if summary.busy_s else None,
        "ms_per_scan_by_stage": {k: 1e3 * v / n for k, v in sorted(
            by_stage.items(), key=lambda kv: -kv[1])},
        "ms_per_scan_by_scope": {k: 1e3 * v / n for k, v in sorted(
            scopes.items(), key=lambda kv: -kv[1])},
        "os_padding_row_share": (100.0 * (walked - real) / walked
                                 if walked else None),
        "span_mean_ms": {k: 1e3 * t / c for k, (c, t) in spans_d.items()
                         if c},
        "idle_gaps": summary.idle_gaps, "device": devs[0].device_kind,
    }


def main(argv=None, *, root: Path = ROOT, need_chip: bool = True) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    from bench import harness
    try:
        with harness.open_cell(root, args.workload, seed=args.seed,
                               seconds=args.seconds, trace=True, t0=T0,
                               need_chip=need_chip) as (_, cell, devs):
            if cell.workload["mode"] != "serve":
                print(f"bench: {args.workload} is no serving cell",
                      file=sys.stderr)
                return 2
            out = report(cell, devs)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
