"""The closed FedSem loop, end to end: FL-trained SemCom jobs served by the
live allocation stack.

  PYTHONPATH=src python -m repro.launch.fedsem_e2e --smoke
  PYTHONPATH=src python -m repro.launch.fedsem_e2e --jobs 3 --rounds 6

Four phases, one shared compiled-executable cache:

1. **Backend equivalence** (gates exit): for the same round scenarios and
   the same `AllocatorConfig`, the `ServiceBackend` over a virtual-clock
   `AllocService` must return the EXACT hardened assignment X that the
   offline `PlannedBackend` computes, round for round — the guarantee that
   routing `run_fl` through the serving stack changes scheduling, never
   answers (`repro.fl.alloc_backend`). Since the service rides accuracy as
   a stacked per-row runtime argument, this is also the uniform-tenant
   batched-acc == replicated-acc equivalence row, gated end to end.
2. **Feedback loop** (gates exit): one `SemComJob` trains the real
   autoencoder over the virtual-clock service; its proxy-accuracy
   measurements must produce an applied A(rho) refit whose curve is
   monotone nondecreasing on a rho grid (Assumption 1 survives the refit).
3. **Multi-job serving** (gates completeness only): J concurrent
   heterogeneous FL jobs — different scenario families (`hetero_classes`,
   `gauss_markov`, ...), sizes and seeds — share ONE `RealClockDriver`,
   each under its OWN tenant id; their per-round requests co-batch inside
   the service and every job's accuracy/energy trajectory plus the
   service-side p95/occupancy are reported (`benchmarks.bench_fedsem`
   turns them into BENCH rows).
4. **Multi-tenant non-interference** (gates exit): each phase-3 job is
   re-run ALONE — same seed, same tenant id, a fresh virtual-clock service —
   and its full trajectory (per-round loss/rho/energy/objective and every
   proxy-accuracy measurement) must match its co-tenanted run exactly.
   A(rho) refits are per-tenant runtime state and co-batched rows are
   independent under vmap, so sharing a driver with other feedback-pushing
   jobs changes NOTHING about a job's own answers — the mixed-tenant
   as-if-alone equivalence row, gated end to end.

Phases 1–2 run with ``feedback`` disabled where it would break determinism:
a refit mid-run is the POINT of phase 2 but would make phase 1's planned
and served answers diverge, so the equivalence check speaks below `run_fl`,
directly to the backends.
"""
from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import AllocatorConfig, Weights, tree_bits
from repro.core.pgd import PGDConfig
from repro.fl import (
    FLConfig,
    PlannedBackend,
    SemComJob,
    SemComJobConfig,
    SemComJobResult,
    ServiceBackend,
    sample_round_scenarios,
    serve_config_for,
)
from repro.launch.compile_cache import setup_compile_cache
from repro.semcom import AEConfig, init_params
from repro.serve import AllocService, BatchPolicy, RealClockDriver
from repro.serve.service import ServeConfig

#: (name, scenario family, n_clients, n_subcarriers) per concurrent job —
#: heterogeneous on purpose: different populations, channels and shapes,
#: one allocation service
JOB_SPECS = (
    ("hetero", "hetero_classes", 4, 12),
    ("markov", "gauss_markov", 4, 12),
    ("iid", "iid_rayleigh", 6, 16),
)
JOB_SPECS_SMOKE = (
    ("hetero", "hetero_classes", 3, 8),
    ("markov", "gauss_markov", 4, 8),
)


def harness_config(smoke: bool, rounds: int | None, jobs: int | None):
    """Shared knobs for CLI and benchmark: allocator, serve policy, job specs,
    AE size. Smoke shrinks everything to CI scale (same reduced allocator as
    `serve_alloc --smoke`)."""
    if smoke:
        allocator = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=60))
        specs = JOB_SPECS_SMOKE
        rounds = 3 if rounds is None else rounds
        ae = AEConfig(image_size=16, hidden=4, base_latent=4)
        batch, eval_batch = 4, 8
    else:
        allocator = AllocatorConfig(inner="pgd")
        specs = JOB_SPECS
        rounds = 6 if rounds is None else rounds
        ae = AEConfig(image_size=32, hidden=8, base_latent=8)
        batch, eval_batch = 8, 16
    if jobs is not None:
        specs = tuple(specs[i % len(specs)] for i in range(jobs))
    serve_cfg = serve_config_for(
        allocator, policy=BatchPolicy(max_batch=4, max_wait_s=0.02)
    )
    return allocator, serve_cfg, specs, rounds, ae, batch, eval_batch


def make_job(spec, rounds: int, ae: AEConfig, batch: int, eval_batch: int,
             feedback: bool = True) -> SemComJob:
    name, family, n, k = spec
    return SemComJob(
        SemComJobConfig(
            fl=FLConfig(
                n_clients=n, n_subcarriers=k, rounds=rounds, local_steps=2,
                scenario=family,
            ),
            ae=ae,
            batch_size=batch,
            eval_batch=eval_batch,
            feedback=feedback,
            name=name,
        )
    )


def check_backend_equivalence(
    key: jax.Array, fl_cfg: FLConfig, allocator: AllocatorConfig,
    serve_cfg: ServeConfig, d_bits: float, executables: dict,
) -> dict:
    """Phase 1: PlannedBackend vs virtual-clock ServiceBackend on identical
    round scenarios — hardened X must match exactly, rho to float32."""
    w = Weights.ones()
    scenarios = sample_round_scenarios(key, fl_cfg, d_bits)
    planned = PlannedBackend(allocator)
    planned.open(scenarios, w)
    served = ServiceBackend(AllocService(serve_cfg, executables=executables))
    served.open(scenarios, w)
    x_equal, rho_close = True, True
    rhos = []
    for rnd in range(fl_cfg.rounds):
        a, b = planned.allocate(rnd), served.allocate(rnd)
        x_equal &= bool(np.array_equal(np.asarray(a.X), np.asarray(b.X)))
        rho_close &= bool(np.allclose(float(a.rho), float(b.rho), atol=1e-6))
        rhos.append(float(a.rho))
    return {
        "rounds": fl_cfg.rounds,
        "rho_planned": rhos,
        "hardened_x_equal": x_equal,
        "rho_allclose": rho_close,
        "equivalent": x_equal and rho_close,
    }


def run_refit_loop(
    key: jax.Array, job: SemComJob, serve_cfg: ServeConfig, executables: dict,
) -> tuple[SemComJobResult, dict]:
    """Phase 2: one SemComJob over the virtual-clock service with feedback on.
    Gate: a refit was applied and its A(rho) is monotone on a rho grid."""
    backend = ServiceBackend(AllocService(serve_cfg, executables=executables))
    result = job.run(key, backend)
    fit = result.accuracy_fit
    grid = jnp.linspace(0.05, 1.0, 20)
    vals = np.asarray(fit.value(grid)) if fit is not None else np.zeros(1)
    monotone = bool(np.all(np.diff(vals) >= -1e-7))
    return result, {
        "refit_applied": result.refit_applied,
        "refit_round": result.refit_round,
        "fit_a": float(fit.a) if fit is not None else None,
        "fit_b": float(fit.b) if fit is not None else None,
        "fit_monotone": monotone,
        "n_measurements": len(result.measurements),
        "ok": bool(result.refit_applied and monotone),
    }


def tenant_id(job: SemComJob, i: int) -> str:
    """One tenant id per concurrent job slot (names repeat when ``--jobs``
    cycles the spec table, so the slot index disambiguates)."""
    return f"{job.cfg.name}:{i}"


def run_multijob(
    key: jax.Array, jobs: list[SemComJob], serve_cfg: ServeConfig,
    executables: dict,
) -> tuple[list[SemComJobResult], dict]:
    """Phase 3: every job in its own thread, one shared `RealClockDriver`,
    each under its own tenant id.

    The service is warmed on each job's round-0 scenario first so the solver
    thread never pays a compile mid-serve; same-bucket jobs then co-batch.
    The A(rho) refits the jobs push are PER-TENANT: each backend scopes its
    `set_accuracy` to its own tenant registry entry, so co-tenants keep
    their own beliefs (phase 4 gates this bit-for-bit).
    """
    warm = []
    for i, job in enumerate(jobs):
        fl = job.cfg.fl
        d_bits = tree_bits(init_params(jax.random.PRNGKey(0), job.ae))
        warm.append(
            sample_round_scenarios(jax.random.fold_in(key, i), fl, d_bits)[0]
        )
    service = AllocService(serve_cfg, executables=executables)
    service.warmup(warm)
    with RealClockDriver(service) as driver:
        with ThreadPoolExecutor(max_workers=len(jobs)) as pool:
            futs = [
                pool.submit(
                    job.run,
                    jax.random.fold_in(key, i),
                    ServiceBackend(driver, tenant=tenant_id(job, i)),
                )
                for i, job in enumerate(jobs)
            ]
            results = [f.result() for f in futs]
        driver.close(timeout=600.0)
        summary = driver.summary()
    return results, summary


def check_noninterference(
    key: jax.Array, jobs: list[SemComJob], co_results: list[SemComJobResult],
    serve_cfg: ServeConfig, executables: dict,
) -> dict:
    """Phase 4: re-run each phase-3 job ALONE (same seed/tenant, fresh
    virtual-clock service) and require its trajectory to match the
    co-tenanted run exactly — co-tenants' feedback must not leak.

    Exactness is justified, not hoped for: a request solves and scores under
    the A(rho) fit stamped at its OWN admission (per-tenant registry), and
    co-batched rows are independent under vmap, so the only thing sharing a
    driver changes is scheduling. ``key`` must be the phase-3 key (the solo
    runs re-derive the same per-job fold)."""
    per_job = []
    for i, (job, co) in enumerate(zip(jobs, co_results)):
        backend = ServiceBackend(
            AllocService(serve_cfg, executables=executables),
            tenant=tenant_id(job, i),
        )
        solo = job.run(jax.random.fold_in(key, i), backend)
        rounds_equal = len(co.history) == len(solo.history) and all(
            a.loss == b.loss and a.rho == b.rho and a.energy == b.energy
            and a.t_fl == b.t_fl and a.objective == b.objective
            for a, b in zip(co.history, solo.history)
        )
        meas_equal = co.measurements == solo.measurements
        per_job.append(
            {
                "job": co.name,
                "tenant": tenant_id(job, i),
                "trajectory_equal": bool(rounds_equal),
                "measurements_equal": bool(meas_equal),
            }
        )
    ok = all(j["trajectory_equal"] and j["measurements_equal"] for j in per_job)
    return {"jobs": per_job, "ok": bool(ok)}


def trajectory(result: SemComJobResult) -> dict:
    """One job's fig8-style accuracy/energy trajectory (per-round rows)."""
    return {
        "job": result.name,
        "rounds": len(result.history),
        "loss": [h.loss for h in result.history],
        "rho": [h.rho for h in result.history],
        "energy": [h.energy for h in result.history],
        "t_fl": [h.t_fl for h in result.history],
        "objective": [h.objective for h in result.history],
        "proxy_accuracy": [
            a for _, a in result.measurements
        ],
        "refit_applied": result.refit_applied,
        "refit_round": result.refit_round,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--jobs", type=int, default=None,
                    help="concurrent FL jobs in phase 3 (default: all specs)")
    ap.add_argument("--rounds", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: tiny AE, reduced allocator, 2 jobs")
    args = ap.parse_args()
    setup_compile_cache()

    allocator, serve_cfg, specs, rounds, ae, batch, eval_batch = harness_config(
        args.smoke, args.rounds, args.jobs
    )
    key = jax.random.PRNGKey(args.seed)
    executables: dict = {}

    # phase 1: equivalence at the backend level (feedback would break it)
    probe = make_job(specs[0], rounds, ae, batch, eval_batch)
    d_bits = tree_bits(init_params(jax.random.PRNGKey(0), probe.ae))
    eq = check_backend_equivalence(
        jax.random.fold_in(key, 100), probe.cfg.fl, allocator, serve_cfg,
        d_bits, executables,
    )
    print(f"[1/4] backend equivalence over {eq['rounds']} rounds: "
          f"hardened X equal = {eq['hardened_x_equal']}, "
          f"rho allclose = {eq['rho_allclose']}")

    # phase 2: the feedback edge through the virtual-clock service
    result, refit = run_refit_loop(
        jax.random.fold_in(key, 200), make_job(specs[0], rounds, ae, batch, eval_batch),
        serve_cfg, executables,
    )
    print(f"[2/4] refit: applied = {refit['refit_applied']} "
          f"(round {refit['refit_round']}), "
          f"A(rho) = {refit['fit_a']} * rho^{refit['fit_b']}, "
          f"monotone = {refit['fit_monotone']}")

    # phase 3: J heterogeneous jobs, one real-clock driver
    key3 = jax.random.fold_in(key, 300)
    jobs = [make_job(s, rounds, ae, batch, eval_batch) for s in specs]
    results, summary = run_multijob(key3, jobs, serve_cfg, executables)
    completed = all(len(r.history) == rounds for r in results)
    print(f"[3/4] {len(results)} concurrent jobs x {rounds} rounds over one "
          f"driver: all completed = {completed}, "
          f"p95 latency = {summary.get('latency_p95_s', 0) * 1e3:.1f}ms, "
          f"occupancy = {summary.get('batch_occupancy_mean', 0):.2f}")

    # phase 4: each job re-run alone must reproduce its co-tenanted
    # trajectory exactly — per-tenant A(rho) refits never leak
    nonint = check_noninterference(key3, jobs, results, serve_cfg, executables)
    print(f"[4/4] multi-tenant non-interference over {len(jobs)} jobs: "
          f"as-if-alone = {nonint['ok']}")
    print(json.dumps(
        {
            "equivalence": eq,
            "refit": refit,
            "jobs": [trajectory(r) for r in results],
            "noninterference": nonint,
            "service": summary,
        },
        indent=2,
    ))
    ok = eq["equivalent"] and refit["ok"] and completed and nonint["ok"]
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
