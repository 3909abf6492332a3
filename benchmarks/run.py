"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per benchmark (us_per_call =
wall time of the benchmark's run; derived = pass/fail summary of the
paper-claim checks), then a detailed check listing on stderr. Exits 1 when a
benchmark module raised or any of its checks is False.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only fig4]
"""
from __future__ import annotations

import argparse
import sys
import time


def _modules():
    from . import (alg_analysis, bench_allocator, bench_serve, fig3_weights,
                   fig4_pmax, fig5_users_subcarriers, fig6_workloads,
                   fig8_accuracy, table2_exhaustive, roofline_report)

    return {
        "bench_allocator": bench_allocator,
        "bench_serve": bench_serve,
        "fig3_weights": fig3_weights,
        "fig4_pmax": fig4_pmax,
        "fig5_users_subcarriers": fig5_users_subcarriers,
        "fig6_workloads": fig6_workloads,
        "fig8_accuracy": fig8_accuracy,
        "table2_exhaustive": table2_exhaustive,
        "alg_analysis": alg_analysis,
        "roofline_report": roofline_report,
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="full sweep grids")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()

    mods = _modules()
    if args.only:
        mods = {k: v for k, v in mods.items() if args.only in k}

    all_checks = {}
    errors = 0
    print("name,us_per_call,derived")
    for name, mod in mods.items():
        t0 = time.time()
        try:
            out = mod.run(quick=not args.full)
            # bench modules return (rows, checks) or (rows, checks,
            # perf_checks); perf checks are informational (timing ratios on a
            # shared box) and never count as claim failures
            rows, checks = out[0], out[1]
            perf = out[2] if len(out) > 2 else {}
            dt_us = (time.time() - t0) * 1e6
            n_pass = sum(1 for v in checks.values() if v is True)
            n_check = sum(1 for v in checks.values() if isinstance(v, bool))
            print(f"{name},{dt_us:.0f},checks={n_pass}/{n_check}")
            all_checks[name] = dict(checks)
            all_checks[name].update({f"perf[{k}]": f"INFO:{v}" for k, v in perf.items()})
        except Exception as e:  # noqa: BLE001 — report it, run the rest
            print(f"{name},-1,ERROR:{type(e).__name__}")
            all_checks[name] = {"exception": str(e)}
            errors += 1

    print("\n--- paper-claim checks ---", file=sys.stderr)
    failures = 0
    for name, checks in all_checks.items():
        for k, v in checks.items():
            status = v if not isinstance(v, bool) else ("PASS" if v else "FAIL")
            if v is False:
                failures += 1
            print(f"{name}.{k}: {status}", file=sys.stderr)
    if failures:
        print(f"\n{failures} claim-check failure(s)", file=sys.stderr)
    if errors:
        print(f"{errors} benchmark module(s) raised", file=sys.stderr)
    return 1 if failures or errors else 0


if __name__ == "__main__":
    sys.exit(main())
