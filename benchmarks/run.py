"""Benchmark harness: one module per paper figure/table.

``python -m benchmarks.run [fig ...] [--backend {xla,pallas}]`` — prints
``name,us_per_call,derived`` CSV rows. See benchmarks/common.py for the
CPU-host measurement caveat; TPU roofline projections live in
EXPERIMENTS.md (from the dry-run).

``--backend`` selects the primary dataflow backend recorded by the
``dataflow`` bench (which always measures both, so BENCH_dataflow.json
accumulates an xla-vs-pallas trajectory per run). fig8/fig9 sweep the
backends side by side unconditionally.
"""
import argparse
import sys
import traceback

from repro.compile_cache import enable_compile_cache

from . import (bench_dataflow, bench_e2e, bench_indexing, fig2_breakdown,
               fig3b_density, fig7_end2end, fig8_layerwise, fig9_dataflow,
               fig10_mapping, fig11_ablation, fig12_networkwide)

ALL = {
    "fig2": fig2_breakdown.run,
    "fig3b": fig3b_density.run,
    "fig7": fig7_end2end.run,
    "fig8": fig8_layerwise.run,
    "fig9": fig9_dataflow.run,
    "fig10": fig10_mapping.run,
    "fig11": fig11_ablation.run,
    "fig12": fig12_networkwide.run,
    "dataflow": bench_dataflow.run,
    "indexing": bench_indexing.run,
    "e2e": bench_e2e.run,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("figs", nargs="*", help="subset of: " + " ".join(ALL))
    ap.add_argument("--backend", choices=("xla", "pallas"), default=None,
                    help="primary dataflow backend; implies the 'dataflow' "
                         "bench when no figs are listed")
    args = ap.parse_args()
    enable_compile_cache()

    which = args.figs or (["dataflow"] if args.backend else list(ALL))
    print("name,us_per_call,derived")
    failed = []
    for name in which:
        try:
            if name == "dataflow":
                ALL[name](backend=args.backend or "xla")
            else:
                ALL[name]()
        except Exception as e:  # keep the harness running; report at end
            traceback.print_exc()
            failed.append((name, str(e)))
    if failed:
        for name, err in failed:
            print(f"{name},FAILED,{err[:120]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
