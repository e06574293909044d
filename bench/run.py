#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout, on a machine that holds the chips the
cell asks for (``BENCHMARK.json``). The cell's inputs and weights are made
from ``--seed``; the run sets up, warms up, measures a window of at least
``--seconds`` seconds, checks what the window produced against the plain
reference, and prints one JSON line last on standard output. With
``--trace 1`` the window is traced and the line carries the per-layer
metrics instead of the end-to-end ones. Without a TPU, or with fewer chips
than the cell asks for, it exits non-zero and prints no result: there is no
CPU fallback.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def parse(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None, *, root: Path = ROOT, need_chip: bool = True) -> int:
    args = parse(argv)
    if args.seed < 0 or args.seconds < 0:
        print("bench: --seed and --seconds must be >= 0", file=sys.stderr)
        return 2
    for p in (str(root / "src"), str(root)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    return harness.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), root=root, t0=T0,
                       need_chip=need_chip)


if __name__ == "__main__":
    sys.exit(main())
