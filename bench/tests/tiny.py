"""A checkout holding tiny cells, for running the harness on the CPU."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

TESTS = Path(__file__).resolve().parent
BENCH = TESTS.parent
ROOT = BENCH.parent

SERVE = {"config": "sparse_resnet21", "mode": "serve", "chips": 1,
         "traffic": {"kind": "indoor", "extent": [32, 28, 16],
                     "overlap": 0.3, "pool": 3, "scans_per_item": 1,
                     "max_voxels": 3500,
                     "in_flight": 1, "check_sample": 2}}
# No training cell is in BENCHMARK.json (PERF.md, Open questions): the
# tiny one carries the trainer's own AdamW and limits that hold the
# mechanism on the CPU, where both sides compute in full float32.
TRAIN = {"config": "tiny_segnet", "mode": "train", "chips": 1,
         "traffic": {"kind": "indoor", "extent": [32, 28, 16],
                     "overlap": 0.3, "pool": 4, "scans_per_item": 2,
                     "max_voxels": 3500, "n_classes": 5,
                     "check_steps": 3},
         "optimizer": {"lr": 0.01, "b1": 0.9, "b2": 0.95, "eps": 1e-08,
                       "weight_decay": 0.0, "grad_clip": 1.0,
                       "warmup_steps": 5, "total_steps": 2000,
                       "min_lr_ratio": 0.1},
         "limits": {"first_loss_gap": 1e-3, "update_gap": 0.3}}
TRAIN_RATE = {"name": "train_voxels_per_s", "unit": "voxels/s",
              "better": "higher", "bound": 0.01, "source": "host_clock"}
CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "hbm_bytes": 1e10}


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def real_workloads() -> dict:
    return {w["name"]: json.loads(
        (BENCH / "workloads" / f"{w['name']}.json").read_text())
        for w in benchmark()["workloads"]}


def config_cell(file: str, config: dict = None, **fields):
    """A cell of the configuration file ``file`` (a path from the
    repository's root; ``config`` in its place, where given) with its
    reference file, as ``harness.open_cell`` makes one; ``fields`` set the
    cell's others."""
    from bench import harness
    cfg = harness.load_json(ROOT / file) if config is None else config
    centry = {"name": Path(file).stem, "file": file}
    ref = harness.reference_file(ROOT, centry, cfg)
    fields = dict(dict(name=centry["name"], workload={}, chips=1, seed=0,
                       seconds=0, trace=False, root=ROOT, t0=0.0), **fields)
    return harness.Cell(config=cfg, net=ref.net(cfg), hooks=ref, **fields)


def make_root(tmp: Path, cells=None) -> Path:
    """A checkout under ``tmp`` with the repository's configurations, the
    test configurations (``bench/tests/*.json``) and the metric readers,
    and tiny cells (by default ``tiny.serve`` and ``tiny.train``). A tiny
    cell takes
    the limits and the optimizer of the real cell of its mode, where it
    names none (of the real cell of its configuration, where there is
    one), and reports the metrics of the real cells of its mode; a
    training cell reports ``train_voxels_per_s``."""
    bench = benchmark()
    real = real_workloads()
    by_mode = {w["mode"]: w for w in real.values()}
    by_config = {w["config"]: w for w in real.values()}
    (tmp / "bench" / "workloads").mkdir(parents=True)
    for d in ("configs", "metrics"):
        shutil.copytree(BENCH / d, tmp / "bench" / d)
    for path in sorted(TESTS.glob("*.json")):
        ref = json.loads(path.read_text())["reference"]
        for f in (path, TESTS / ref):
            shutil.copy(f, tmp / "bench" / "configs")
        bench["configs"].append({"name": path.stem, "source": "tests",
                                 "file": f"bench/configs/{path.name}",
                                 "reduced": [], "why": "tests"})
    cells = cells or {"tiny.serve": SERVE, "tiny.train": TRAIN}
    entries = []
    for name, wl in cells.items():
        wl = dict(wl)
        like = by_config.get(wl["config"], by_mode.get(wl["mode"], {}))
        for key in ("limits", "optimizer"):
            if key in like:
                wl.setdefault(key, like[key])
        (tmp / "bench" / "workloads" / f"{name}.json").write_text(
            json.dumps(wl))
        entries.append({"name": name, "config": wl["config"],
                        "traffic": name.split(".")[-1], "chips": 1,
                        "why": "a tiny cell for the CPU tests"})
    bench["workloads"] = entries
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if "workloads" in m:
                modes = {real[w]["mode"] for w in m["workloads"]}
                m["workloads"] = [e["name"] for e in entries
                                  if cells[e["name"]]["mode"] in modes]
    trains = [e["name"] for e in entries
              if cells[e["name"]]["mode"] == "train"]
    if trains:
        bench["end_to_end"].append(dict(TRAIN_RATE, workloads=trains))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def run_cell(root: Path, cell: str, capsys, *, seed: int = 2 ** 31 + 5,
             seconds: float = 0.5, trace: int = 0):
    """Run ``cell`` through the command's entry point on the CPU; returns
    (exit code, the result line or None, standard error)."""
    from bench import run
    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                   str(seconds), "--trace", str(trace)], root=root,
                  need_chip=False)
    out, err = capsys.readouterr()
    lines = [l for l in out.splitlines() if l.strip()]
    line = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, line, err
