"""The benchmark's harness: one run of one cell, from files found by name.

A run reads ``BENCHMARK.json``, the cell's workload file
``bench/workloads/<cell>.json`` and its configuration's file, checks that
JAX holds the chips the cell asks for, and hands the run to the workload's
mode (``bench/modes/<mode>.py``). The mode builds the system under test
through the program's entry points, warms up, measures its window, and
checks what the window produced against the reference. The harness then
reads the metrics the cell reports, each per-layer metric by its reader
``bench/metrics/<metric>.py``, and prints the result line.

A configuration is its file of sizes, ``bench/configs/<name>.json``, and
the reference file it names beside it, which defines ``net(cfg)``: the
network's convs as the reference's layer table (``reference.Net``). The
reference file may also define, in place of the shared functions
(:data:`HOOKS`), ``init_params(key, net, dtype)``, ``device_inputs(scans,
feats, net, labels=None)``, ``forward_one(params, net, inp, precision)``,
``program_net(cfg, net)`` and ``forward_flops(scan, net)``: then the
weights, the reference's arrays and logits, the program's network and the
count of useful operations are its own. Every caller takes them through
:meth:`Cell.hook`.

Nothing here names a cell, a configuration or a metric: adding one is
adding files and ``BENCHMARK.json`` entries, also for a network that is
more than a stack of convs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import sys
from pathlib import Path
from typing import Dict, List

CACHE_DIR = ".jax_cache"

# What a configuration's reference file may define, and the module of the
# shared function used where it does not.
HOOKS = {"init_params": "bench.reference",
         "device_inputs": "bench.reference",
         "forward_one": "bench.reference",
         "program_net": "bench.modes.common",
         "forward_flops": "bench.opcount"}


class NoChip(RuntimeError):
    """JAX holds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    """Everything a mode needs to run one cell."""
    name: str
    workload: dict
    config: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    root: Path
    t0: float                       # perf_counter at process start
    net: object = None              # the reference's description (Net)
    on_chip: bool = True            # False only in the tests on the CPU
    hooks: object = None            # the configuration's reference file

    @property
    def traffic(self) -> dict:
        return self.workload["traffic"]

    def hook(self, name: str):
        """The configuration's reference file's function ``name`` where it
        defines one, else the shared one (:data:`HOOKS`)."""
        own = getattr(self.hooks, name, None)
        if own is not None:
            return own
        return getattr(importlib.import_module(HOOKS[name]), name)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a benchmark file by its path (names may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: Path, name: str) -> tuple:
    """``(BENCHMARK.json, its workload entry, the workload file, the
    configuration entry, the configuration file)`` of cell ``name``."""
    bench = load_json(root / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    wfile = root / "bench" / "workloads" / f"{name}.json"
    workload = load_json(wfile)
    for key in ("config", "chips"):
        if workload[key] != entry[key]:
            raise ValueError(f"{wfile} gives {key} {workload[key]!r}, "
                             f"BENCHMARK.json {entry[key]!r}")
    centry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(root / centry["file"])
    return bench, entry, workload, centry, config


def reference_file(root: Path, centry: dict, config: dict):
    """The configuration's reference file, beside its configuration file,
    as a module."""
    path = (root / centry["file"]).parent / config["reference"]
    return load_module(path, f"bench_config_{centry['name']}")


def reported(bench: dict, cell: str, trace: bool) -> List[dict]:
    """The metrics cell ``cell`` reports in a run with ``trace`` on/off."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, whatever the environment says, so that a run compiles only
    what no earlier run of the checkout compiled."""
    import jax
    path = str(root / CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n: int) -> list:
    """The first ``n`` accelerator devices; raises :class:`NoChip` when JAX
    finds none or fewer."""
    import jax
    devs = jax.devices()
    if devs[0].platform not in ("tpu",):
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def device_record(devs: list) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def free_device_memory() -> None:
    """Drop what the program left on the device before the reference runs:
    its arrays go with their last reference, and compiled programs stay."""
    gc.collect()


def read_per_layer(bench: dict, cell: str, ctx: dict, root: Path) -> Dict:
    """Each per-layer metric the cell reports, from its reader; a reader
    that finds nothing to read leaves its metric out, and standard error
    names it."""
    out = {}
    for m in reported(bench, cell, trace=True):
        reader = load_module(root / "bench" / "metrics" / f"{m['name']}.py",
                             f"bench_metric_{m['name']}")
        got = reader.read(ctx)
        value = math.nan if got is None else float(got["value"])
        if not math.isfinite(value):
            print(f"bench: metric {m['name']} found nothing to read in "
                  f"this run ({value}); it is left out", file=sys.stderr)
            continue
        got = {k: v for k, v in got.items() if k != "value"}
        out[m["name"]] = {"value": value, "unit": m["unit"], **got}
    return out


@contextlib.contextmanager
def open_cell(root: Path, name: str, *, seed: int, seconds: float,
              trace: bool, t0: float, need_chip: bool = True):
    """``(BENCHMARK.json, the cell, its devices)`` for the block, with the
    program's float32 products at the matmul precision its configuration
    states (:func:`matmul_precision`). Every way of driving a cell (a run,
    a calibration, the scope report) opens it here. Raises :class:`NoChip`
    before anything is built when JAX holds too few chips;
    ``need_chip=False`` (tests on the CPU) skips that look, and with it the
    persistent compilation cache, a setting of the whole process."""
    bench, entry, workload, centry, config = find_cell(root, name)
    chips = int(entry["chips"])
    import jax
    if need_chip:
        devs = require_chips(chips)
        enable_compile_cache(root)
    else:
        devs = jax.devices()[:chips]
    ref = reference_file(root, centry, config)
    cell = Cell(name=name, workload=workload, config=config, chips=chips,
                seed=seed, seconds=seconds, trace=trace, root=root, t0=t0,
                on_chip=need_chip, net=ref.net(config), hooks=ref)
    with matmul_precision(config["matmul_precision"]):
        yield bench, cell, devs


@contextlib.contextmanager
def matmul_precision(name: str):
    """Run the block with the program's float32 products at matmul
    precision ``name``, in every thread of the process (the serving engine
    may call the session from a worker thread), and restore JAX's setting
    afterwards.

    ``default`` leaves JAX's setting as it is, so the program compiles
    as it would with nothing asked for. ``highest`` sets it for the whole
    process: every product that names no precision, the Pallas kernels'
    among them, computes in full float32. ``high`` is refused before
    anything is built: the Pallas TPU lowering has no three-pass bfloat16
    mode, so the program cannot run at it."""
    import jax
    from bench import reference
    reference.precision(name)
    if name == "high":
        raise ValueError("matmul_precision 'high': the program's Pallas "
                         "kernels have no three-pass bfloat16 mode on the "
                         "TPU; state 'default' or 'highest'")
    if name == "default":
        yield
        return
    before = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", name)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", before)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: Path, t0: float, need_chip: bool = True) -> int:
    """One run; the exit code."""
    try:
        with open_cell(root, cell_name, seed=seed, seconds=seconds,
                       trace=trace, t0=t0, need_chip=need_chip) as (
                           bench, cell, devs):
            mode = importlib.import_module(
                f"bench.modes.{cell.workload['mode']}")
            res = mode.run(cell, devs)
    except NoChip as e:
        print(f"bench: {e}; nothing is run off the chip", file=sys.stderr)
        return 3

    metrics = {}
    if trace:
        metrics = read_per_layer(bench, cell_name, res["context"], root)
    else:
        for m in reported(bench, cell_name, trace=False):
            metrics[m["name"]] = {"value": float(res["end_to_end"][m["name"]]),
                                  "unit": m["unit"]}
    checks = res["checks"]
    correct = bool(res["ok"]) and all(c["value"] <= c["limit"]
                                      for c in checks.values())
    line = {"correct": correct, "attempted": int(res["attempted"]),
            "failed": int(res["failed"]), "metrics": metrics,
            "device": res["device"]}
    if trace and res.get("breakdown") is not None:
        line["breakdown"] = res["breakdown"]
    line["checks"] = checks
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line, allow_nan=False, default=_json_num), flush=True)
    return 0


def _json_num(v):
    if hasattr(v, "item"):
        return v.item()
    raise TypeError(f"not JSON: {type(v).__name__}")


def finite(v: float, big: float = 1e30) -> float:
    """Checks print infinities as a large number, so the line stays JSON."""
    return v if math.isfinite(v) else big
