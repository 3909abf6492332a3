"""Closed-loop FedSem benchmark: concurrent FL jobs over the live allocation
service (the `repro.launch.fedsem_e2e` harness, recorded as BENCH rows).

Phases (shared compiled-executable cache, see the harness docstring):
backend equivalence (PlannedBackend == virtual-clock ServiceBackend, exact
hardened X), the A(rho) feedback loop (a refit from the job's own
proxy-accuracy measurements must be applied and stay monotone), J concurrent
heterogeneous FL jobs sharing one `RealClockDriver` — each a TENANT whose
refits are scoped to its own rounds — then the non-interference gate: each
job re-run alone must reproduce its co-tenanted trajectory exactly. Rows
record every job's fig8-style per-round accuracy/energy trajectory (tenant-
tagged; these rounds co-batched across tenants), each job's own refit
trajectory, plus the service-side latency/occupancy summary under FL load.

Writes ``BENCH_fedsem.json`` at the repo root (full run) so future PRs have
a closed-loop trajectory; ``--smoke`` writes
``experiments/bench/BENCH_fedsem_smoke.json`` with a tiny autoencoder and a
reduced allocator for CI.

Exit status gates ONLY the deterministic claims (equivalence, refit
monotonicity, tenant non-interference, every job finishing every round):
throughput/occupancy
observations are informational ``perf_checks`` — a loaded CI box must not
fail an unrelated PR (the bench_serve convention).

  PYTHONPATH=src python -m benchmarks.bench_fedsem            # full, root JSON
  PYTHONPATH=src python -m benchmarks.bench_fedsem --smoke    # CI-sized
"""
from __future__ import annotations

import json
import pathlib
import platform

import jax

from repro.core import tree_bits
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.fedsem_e2e import (
    check_backend_equivalence,
    check_noninterference,
    harness_config,
    make_job,
    run_multijob,
    run_refit_loop,
    tenant_id,
    trajectory,
)
from repro.semcom import init_params

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT_JSON = ROOT / "BENCH_fedsem.json"
# smoke runs use a reduced allocator + tiny AE — methodologically different
# numbers must not clobber the committed full-run trajectory file
OUT_JSON_SMOKE = ROOT / "experiments" / "bench" / "BENCH_fedsem_smoke.json"


def run(quick: bool = False, seed: int = 0, smoke: bool | None = None):
    smoke = quick if smoke is None else smoke
    allocator, serve_cfg, specs, rounds, ae, batch, eval_batch = harness_config(
        smoke, rounds=None, jobs=None
    )
    key = jax.random.PRNGKey(seed)
    executables: dict = {}

    probe = make_job(specs[0], rounds, ae, batch, eval_batch)
    d_bits = tree_bits(init_params(jax.random.PRNGKey(0), probe.ae))
    eq = check_backend_equivalence(
        jax.random.fold_in(key, 100), probe.cfg.fl, allocator, serve_cfg,
        d_bits, executables,
    )
    _, refit = run_refit_loop(
        jax.random.fold_in(key, 200),
        make_job(specs[0], rounds, ae, batch, eval_batch),
        serve_cfg, executables,
    )
    key3 = jax.random.fold_in(key, 300)
    jobs = [make_job(s, rounds, ae, batch, eval_batch) for s in specs]
    results, summary = run_multijob(key3, jobs, serve_cfg, executables)
    # per-tenant non-interference: each job re-run alone (same seed fold and
    # tenant id) must reproduce its co-tenanted trajectory exactly
    nonint = check_noninterference(key3, jobs, results, serve_cfg, executables)

    # one row per (job, round): the multi-job accuracy/energy trajectory,
    # tagged with the job's tenant id (these rounds co-batched across tenants)
    rows = []
    for i, (spec, job, res) in enumerate(zip(specs, jobs, results)):
        traj = trajectory(res)
        for rnd in range(traj["rounds"]):
            rows.append(
                {
                    "job": res.name,
                    "tenant": tenant_id(job, i),
                    "scenario": spec[1],
                    "n_clients": spec[2],
                    "n_subcarriers": spec[3],
                    "round": rnd,
                    "loss": traj["loss"][rnd],
                    "rho": traj["rho"][rnd],
                    "energy": traj["energy"][rnd],
                    "t_fl": traj["t_fl"][rnd],
                    "objective": traj["objective"][rnd],
                }
            )
    # each job's own refit trajectory: the fit its LATER rounds solved under,
    # scoped to its tenant registry entry (never visible to co-tenants)
    refits = [
        {
            "job": res.name,
            "tenant": tenant_id(job, i),
            "refit_applied": res.refit_applied,
            "refit_round": res.refit_round,
            "fit_a": float(res.accuracy_fit.a) if res.accuracy_fit else None,
            "fit_b": float(res.accuracy_fit.b) if res.accuracy_fit else None,
            "n_measurements": len(res.measurements),
        }
        for i, (job, res) in enumerate(zip(jobs, results))
    ]
    # plus the service-side view of the same load: latency + the occupancy of
    # the MIXED-TENANT co-batches (distinct tenants' rounds sharing one solve)
    service_row = {
        "jobs": len(results),
        "tenants": len({r["tenant"] for r in rows}),
        "rounds": rounds,
        "requests": summary.get("completed"),
        "latency_p50_s": summary.get("latency_p50_s"),
        "latency_p95_s": summary.get("latency_p95_s"),
        "batch_occupancy_mean": summary.get("batch_occupancy_mean"),
        "mean_batch_size": summary.get("mean_batch_size"),
        "cache_hit_rate": summary.get("cache_hit_rate"),
    }

    completed = all(len(r.history) == rounds for r in results)
    checks = {
        "service_backend_matches_planned": eq["equivalent"],
        "refit_applied_and_monotone": refit["ok"],
        "tenant_noninterference_as_if_alone": nonint["ok"],
        "all_jobs_completed_all_rounds": completed,
        "every_round_allocated": all(0.0 < r["rho"] <= 1.0 for r in rows),
        "service_latency_recorded": bool(
            summary.get("latency_p95_s", 0) >= summary.get("latency_p50_s", 0) > 0
        ),
    }
    perf_checks = {
        # co-batching across concurrent jobs is timing-dependent (jobs drift
        # apart as their training speeds differ) — observed, never gating
        "concurrent_rounds_co_batched": summary.get("mean_batch_size", 0) > 1.0,
        "training_reduced_loss_somewhere": any(
            r.history[-1].loss < r.history[0].loss for r in results
        ),
    }

    result = {
        "specs": [list(s) for s in specs],
        "rounds": rounds,
        "inner": allocator.inner,
        "smoke": smoke,
        "equivalence": eq,
        "refit": refit,
        "noninterference": nonint,
        "rows": rows,
        "refits": refits,
        "service": service_row,
        "checks": checks,
        "perf_checks": perf_checks,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        "platform": platform.platform(),
    }
    out = OUT_JSON_SMOKE if smoke else OUT_JSON
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(result, indent=2) + "\n")
    return rows, checks, perf_checks


if __name__ == "__main__":
    import argparse
    import sys

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="tiny CI-sized run")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    setup_compile_cache()
    rows, checks, perf_checks = run(smoke=args.smoke, seed=args.seed)
    for r in rows:
        print(
            f"{r['job']:>8} [{r['scenario']:>14}] round {r['round']} "
            f"loss={r['loss']:.4f} rho={r['rho']:.3f} "
            f"E={r['energy']:.3f}J t={r['t_fl']:.3f}s"
        )
    print("checks (gating):", checks)
    print("perf checks (informational):", perf_checks)
    sys.exit(0 if all(v is not False for v in checks.values()) else 1)
