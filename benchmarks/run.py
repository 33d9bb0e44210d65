"""Benchmark harness — one function per paper table/figure plus the dry-run
roofline table. Prints ``name,us_per_call,derived`` CSV.

Usage: PYTHONPATH=src python -m benchmarks.run [--fast] [--only GROUP]
       [--artifact-dir DIR]

``--artifact-dir`` makes the artifact-writing groups (fit/loop/fleet/serve/
pipeline/transfer) emit
their CI-sized JSON artifacts there even in ``--fast`` mode — the input of
the bench regression gate (``tools/bench_gate.py``).  Any group that raises
marks the whole run failed (non-zero exit), so CI cannot green-light a run
that silently skipped a benchmark; an unknown ``--only`` group is an error,
not an empty no-op run.
"""

from __future__ import annotations

import argparse
import inspect
import pathlib
import sys
import traceback


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="small observation set, skip CV/MLP (CI mode)")
    ap.add_argument("--only", default=None, help="run a single benchmark group")
    ap.add_argument("--artifact-dir", default=None,
                    help="write fast-mode BENCH_*.json artifacts to this dir")
    args = ap.parse_args(argv)

    from . import fit_bench
    from . import fleet_bench
    from . import loop_bench
    from . import paper_experiments as pe
    from . import pipeline_bench
    from . import roofline
    from . import serve_bench
    from . import transfer_bench

    groups = {
        "fit": fit_bench.bench_fit,
        "fleet": fleet_bench.bench_fleet,
        "loop": loop_bench.bench_loop,
        "pipeline": pipeline_bench.bench_pipeline,
        "serve": serve_bench.bench_serve,
        "transfer": transfer_bench.bench_transfer,
        "dataset": pe.bench_dataset,
        "campaign": pe.bench_campaign,
        "pca": pe.bench_pca,
        "model_comparison": pe.bench_model_comparison,
        "feature_importance": pe.bench_feature_importance,
        "util_impact": pe.bench_util_impact,
        "etl": pe.bench_etl,
        "recommendation": pe.bench_recommendation,
        "extensions": pe.bench_extensions,
        "kernels": pe.bench_kernels,
    }
    if args.only:
        if args.only not in groups and args.only != "roofline":
            ap.error(
                f"unknown benchmark group {args.only!r}; "
                f"choose from {sorted(groups) + ['roofline']}"
            )
        groups = {args.only: groups[args.only]} if args.only in groups else {}

    print("name,us_per_call,derived")
    failures = 0
    for gname, fn in groups.items():
        kwargs = {}
        if (
            args.artifact_dir
            and "artifact_dir" in inspect.signature(fn).parameters
        ):
            kwargs["artifact_dir"] = pathlib.Path(args.artifact_dir)
        try:
            for name, us, derived in fn(args.fast, **kwargs):
                print(f"{name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"{gname},0,ERROR {type(e).__name__}: {e}", file=sys.stdout)
            traceback.print_exc(file=sys.stderr)

    # roofline rows from the dry-run artifacts (if present)
    if args.only in (None, "roofline"):
        try:
            recs = roofline.load_records()
            for name, us, derived in roofline.csv_rows(recs):
                print(f"{name},{us:.1f},{derived}")
            s = roofline.summarize(recs)
            print(f"roofline_summary,0,{s}")
        except Exception as e:  # noqa: BLE001
            print(f"roofline,0,ERROR {e}")

    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    main()
