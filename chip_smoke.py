"""Run the allocation service and the closed FedSem loop once on a TPU.

  python chip_smoke.py            # one chip: serving phase, then FL phase
  python chip_smoke.py --chips 4  # four chips: the scenario-sharded service only

Everything runs in this one process, through the entry points a user calls
(`AllocService` behind a `RealClockDriver`, `SemComJob` through a
`ServiceBackend`), with the paper's full allocator (`AllocatorConfig(inner=
"pgd")`) and weights drawn from ``--seed``:

* **serve** — an open-loop Poisson stream of 16 requests at the paper's
  Table-I cell (N=10 devices, K=50 subcarriers, i.i.d. Rayleigh; bucket
  16x64), then one flush of 8 requests at the largest default bucket
  (N=50, K=200 in 64x256). Gates: every request answered; every Table-I
  answer feasible; each kernel-scored `Completion.objective` equal to the
  plain `system.objective` to float32 tolerance; the real-clock answers
  replayed on the virtual clock give the exact same hardened X; the 64x256
  answers finite. The 64x256 feasible count is printed, not gated.
* **warm** — 8 time-correlated (`gauss_markov`) Table-I requests through a
  service with the warm-start cache on, so the refine program runs; same
  gates as the Table-I stream.
* **fl** — two rounds of one `SemComJob` training the paper's autoencoder at
  its published widths (`configs/fedsem_autoencoder.CONFIG`) over N=10
  clients and K=50 subcarriers, allocated through the serving driver.
  Gates: finite loss; the served hardened X equal to `PlannedBackend`'s.
* **--chips 4** — the Table-I stream through ``ServeConfig(shard_batch=
  True)`` on a four-chip scenario mesh against the one-chip service. Gates:
  identical hardened X per request; the sharded programs hold the kernel and
  no cross-device collective; a sharded result lives on all four chips.

Every compiled solve, refine and score program must contain the objective
kernel (``tpu_custom_call``). One JSON line per phase reports wall time,
compile seconds and executable-cache hits (`ServiceMetrics`), the device's
``peak_bytes_in_use`` and batch occupancy. These are one-off observations,
not benchmark metrics. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
A failed gate exits nonzero without it, and so does a run that finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.fedsem_autoencoder import CONFIG as AE_CONFIG  # noqa: E402
from repro.core import (  # noqa: E402
    Allocation, AllocatorConfig, Weights, bucket_for, default_accuracy,
    pad_params, stack_accuracy, stack_params, stack_weights,
)
from repro.core.distribute import scenario_sharding  # noqa: E402
from repro.core.system import (  # noqa: E402
    energy_breakdown, feasible, objective, t_fl,
)
from repro.fl import (  # noqa: E402
    FLConfig, PlannedBackend, SemComJob, SemComJobConfig, ServiceBackend,
)
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402
from repro.launch.roofline import collective_bytes  # noqa: E402
from repro.serve import (  # noqa: E402
    AllocService, BatchPolicy, RealClockDriver, ServeConfig, WarmStartConfig,
    pace_stream, poisson_arrivals, run_load, same_hardened_assignments,
    scenario_stream,
)
from repro.serve.service import _score_flush, sharded_score_flush  # noqa: E402

TABLE1 = (10, 50)       # paper Table I: N devices, K subcarriers
LARGEST = (50, 200)     # lands in the largest DEFAULT_BUCKETS entry, 64x256
MAX_BATCH = 8
MAX_WAIT_S = 0.05
#: kernel-scored vs plain objective, relative to the sum of the objective's
#: terms. The TPU's float32 log is less exact than the CPU's: on a v5e the
#: kernel's accuracy term a*exp(b*log(rho)) was off by up to 3.2e-5 of its
#: value over rho in [0.05, 1] (1.9e-7 on the CPU), while system.objective
#: takes a*rho**b; the bound sits a few times above that
OBJ_RTOL = 1e-4


class GateFailed(SystemExit):
    def __init__(self, what: str):
        super().__init__(f"chip_smoke: FAILED: {what}")


def gate(ok, what: str) -> None:
    if not ok:
        raise GateFailed(what)


def require_tpu(chips: int):
    """The device list, or exit: this script has no CPU fallback."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU found (JAX sees platform "
            f"{devices[0].platform!r}); this script runs on a TPU only"
        )
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, found {len(devices)}")
    return devices


class RecordingBackend(ServiceBackend):
    """`ServiceBackend` that keeps the rounds it was given and what it
    answered, so the offline planner can re-solve the same rounds."""

    def open(self, scenarios, weights):
        super().open(scenarios, weights)
        self.scenarios, self.weights, self.allocs = list(scenarios), weights, []

    def allocate(self, rnd):
        alloc = super().allocate(rnd)
        self.allocs.append(alloc)
        return alloc


def serve_config(allocator, **kw) -> ServeConfig:
    policy = BatchPolicy(max_batch=MAX_BATCH, max_wait_s=MAX_WAIT_S)
    return ServeConfig(policy=policy, allocator=allocator, **kw)


def phase_line(name: str, t0: float, service, before: dict | None = None,
               **extra) -> None:
    """Print one phase's JSON line: wall time, the service's compile seconds
    and executable-cache counts in this phase (since ``before``), occupancy
    and the device's peak bytes."""
    now = service.metrics.summary()
    before = before or {}
    delta = {k: now[k] - before.get(k, 0)
             for k in ("compile_s", "cache_hits", "cache_misses", "batches")}
    stats = jax.devices()[0].memory_stats() or {}
    print(json.dumps({
        "phase": name,
        "wall_s": time.perf_counter() - t0,
        **delta,
        "batch_occupancy_mean": now["batch_occupancy_mean"],
        "solve_mean_s": now["solve_mean_s"],
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        **extra,
    }), flush=True)


def objective_error(params, completion) -> float:
    """|kernel-scored objective - plain `system.objective`|, relative to the
    sizes of the eq. 13 terms that are summed."""
    alloc = completion.alloc
    want = float(objective(params, Weights.ones(), alloc))
    e_t, e_c, e_sc = energy_breakdown(params, alloc)
    acc = params.N * default_accuracy().value(alloc.rho)
    scale = float(jnp.sum(e_t + e_c + e_sc) + t_fl(params, alloc) + acc)
    return abs(completion.objective - want) / scale


def finite(alloc) -> bool:
    return all(np.isfinite(np.asarray(x)).all() for x in jax.tree.leaves(alloc))


def serve_real(service, requests, arrivals, driver=None) -> list:
    """Pace ``requests`` on the real clock; completions in request order.
    A given ``driver`` stays open; otherwise one is made and closed."""
    own = driver is None
    driver = RealClockDriver(service) if own else driver
    futures, _ = pace_stream(driver, requests, arrivals)
    done = [f.result(timeout=900.0) for f in futures]
    if own:
        driver.close(timeout=900.0)
    return done


def replay_matches(cfg, executables, requests, arrivals, completions) -> bool:
    """The same stream on the virtual clock (recorded warm starts
    re-injected) gives the exact hardened X of every real-clock answer."""
    starts = [c.warm_start for c in completions]
    replay = run_load(
        AllocService(cfg._replace(warmstart=None), executables=executables),
        requests, arrivals,
        warm_starts=starts if any(s is not None for s in starts) else None,
    )
    return same_hardened_assignments(completions, replay.completions)


def check_table1_stream(requests, completions, what: str) -> int:
    """Gates every Table-I stream shares; returns its feasible count."""
    gate(len(completions) == len(requests), f"{what}: unanswered requests")
    err = max(objective_error(p, c) for p, c in zip(requests, completions))
    gate(err <= OBJ_RTOL, f"{what}: kernel-scored objective differs from "
         f"system.objective by {err:.3g} of its terms (limit {OBJ_RTOL})")
    return sum(bool(feasible(p, c.alloc)) for p, c in zip(requests, completions))


def score_program_text(params, mesh=None) -> str:
    """Compiled text of the flush-scoring program for ``params``' bucket."""
    slots = MAX_BATCH * (mesh.size if mesh is not None else 1)
    padded = pad_params(params, bucket_for(params.N, params.K))
    n, k = padded.N, padded.K
    args = (
        stack_params([padded] * slots),
        stack_weights([Weights.ones()] * slots),
        Allocation(f=jnp.ones((slots, n)), P=jnp.zeros((slots, n, k)),
                   X=jnp.zeros((slots, n, k)), rho=jnp.ones((slots,))),
        stack_accuracy([default_accuracy()] * slots),
    )
    score = _score_flush if mesh is None else sharded_score_flush(mesh)
    return score.lower(*args).compile().as_text()


def bucket_name(cache_key) -> str:
    n, k = cache_key[0][:2]
    return f"{n}x{k}"


# -- one chip ---------------------------------------------------------------


def run_one_chip(allocator, seed: int) -> dict:
    """The serve, warm and fl phases; returns the compiled programs' texts
    (name -> HLO) for the kernel gate."""
    key = jax.random.PRNGKey(seed)
    texts = {}

    # serve: the Table-I stream, then one flush at the largest bucket
    t0 = time.perf_counter()
    cfg = serve_config(allocator)
    service = AllocService(cfg)
    table1 = scenario_stream(jax.random.fold_in(key, 1), 16, sizes=(TABLE1,))
    big = scenario_stream(jax.random.fold_in(key, 2), MAX_BATCH, sizes=(LARGEST,))
    service.warmup([table1[0], big[0]])
    arrivals = poisson_arrivals(jax.random.fold_in(key, 3), len(table1), 20.0)
    driver = RealClockDriver(service)
    done = serve_real(service, table1, arrivals, driver)
    n_feas = check_table1_stream(table1, done, "serve")
    gate(n_feas == len(table1), f"serve: {len(table1) - n_feas} infeasible answers")
    gate(replay_matches(cfg, service.executables, table1, arrivals, done),
         "serve: real-clock and virtual-clock hardened X differ")
    cold_solve_s = service.metrics.summary()["solve_mean_s"]
    big_done = serve_real(service, big, np.zeros(len(big)), driver)
    gate(len(big_done) == len(big), "serve: 64x256 requests unanswered")
    gate(all(finite(c.alloc) and np.isfinite(c.objective) for c in big_done),
         "serve: non-finite 64x256 answer")
    big_feas = sum(bool(feasible(p, c.alloc)) for p, c in zip(big, big_done))
    phase_line("serve", t0, service, table1_requests=len(table1),
               table1_feasible=n_feas, largest_bucket_requests=len(big),
               largest_bucket_feasible=big_feas)
    for k, exe in service.executables.items():
        texts[f"solve {bucket_name(k)}"] = exe.as_text()
    texts["score 16x64"] = score_program_text(table1[0])
    texts["score 64x256"] = score_program_text(big[0])

    # warm: time-correlated users with the warm-start cache on
    t0 = time.perf_counter()
    warm_cfg = serve_config(allocator, warmstart=WarmStartConfig())
    warm = AllocService(warm_cfg, executables=service.executables)
    stream = scenario_stream(jax.random.fold_in(key, 4), 8,
                             scenario="gauss_markov", sizes=(TABLE1,))
    warm.warmup(stream[:1])
    # arrivals about two cold solves apart, so later requests find the
    # earlier answers in the cache and the refine program runs
    rate = 0.5 / max(cold_solve_s, 1e-3)
    w_arrivals = poisson_arrivals(jax.random.fold_in(key, 5), len(stream), rate)
    w_done = serve_real(warm, stream, w_arrivals)
    w_feas = check_table1_stream(stream, w_done, "warm")
    gate(replay_matches(warm_cfg, warm.executables, stream, w_arrivals, w_done),
         "warm: real-clock and virtual-clock hardened X differ")
    refine = [k for k in warm.executables if "warm-refine" in k]
    gate(refine, "warm: no refine program was compiled")
    for k in refine:
        texts[f"refine {bucket_name(k)}"] = warm.executables[k].as_text()
    phase_line("warm", t0, warm, requests=len(stream), feasible=w_feas,
               warm_hits=warm.metrics.warm_hits, rate_rps=rate)

    # fl: two SemComJob rounds through the same driver vs the offline plan
    t0 = time.perf_counter()
    before = service.metrics.summary()
    fl = FLConfig(n_clients=TABLE1[0], n_subcarriers=TABLE1[1], rounds=2,
                  local_steps=2)
    job = SemComJob(SemComJobConfig(fl=fl, ae=AE_CONFIG, name="smoke"))
    backend = RecordingBackend(driver, tenant="fl")
    result = job.run(jax.random.fold_in(key, 6), backend)
    driver.close(timeout=900.0)
    losses = [h.loss for h in result.history]
    gate(len(losses) == fl.rounds and np.isfinite(losses).all(),
         "fl: loss not finite")
    planned = PlannedBackend(allocator)
    planned.open(backend.scenarios, backend.weights)
    gate(all(np.array_equal(np.asarray(a.X), np.asarray(planned.allocate(r).X))
             for r, a in enumerate(backend.allocs)),
         "fl: ServiceBackend and PlannedBackend hardened X differ")
    phase_line("fl", t0, service, before, rounds=fl.rounds, loss=losses,
               rho=[h.rho for h in result.history], ae_hidden=AE_CONFIG.hidden)
    return texts


# -- four chips ---------------------------------------------------------------


def run_sharded(allocator, seed: int) -> dict:
    """The Table-I stream through the scenario-sharded service (real clock)
    against the one-chip service (virtual clock)."""
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    requests = scenario_stream(jax.random.fold_in(key, 1), 16, sizes=(TABLE1,))
    arrivals = poisson_arrivals(jax.random.fold_in(key, 3), len(requests), 20.0)
    sharded = AllocService(serve_config(allocator, shard_batch=True))
    gate(sharded.mesh.size == len(jax.devices()), "mesh does not span the chips")
    sharded.warmup(requests[:1])
    done = serve_real(sharded, requests, arrivals)
    gate(len(done) == len(requests), "sharded: unanswered requests")
    single = AllocService(serve_config(allocator))
    single.warmup(requests[:1])
    ref = run_load(single, requests, arrivals).completions
    gate(same_hardened_assignments(done, ref),
         "sharded and one-chip hardened X differ")

    # a sharded flush's result is spread over every chip of the mesh
    (cache_key, exe), = sharded.executables.items()
    slots = cache_key[1]
    req = sharded.prepare(requests[0])
    args = jax.device_put(
        (stack_params([req.padded] * slots),
         stack_weights([req.weights] * slots),
         stack_accuracy([req.accuracy] * slots)),
        scenario_sharding(sharded.mesh),
    )
    res = jax.block_until_ready(exe(*args))
    homes = {s.device for s in res.alloc.X.addressable_shards}
    gate(homes == set(jax.devices()), f"sharded result on {len(homes)} chip(s)")
    phase_line("sharded", t0, sharded, requests=len(requests), chips=len(homes),
               one_chip_compile_s=single.metrics.compile_s)
    return {
        "sharded solve 16x64": exe.as_text(),
        "sharded score 16x64": score_program_text(requests[0], sharded.mesh),
    }


def check_kernels(texts: dict, sharded: bool) -> None:
    """Every program holds the objective kernel; sharded ones no collective."""
    for name, text in texts.items():
        gate("tpu_custom_call" in text, f"{name}: objective kernel not compiled in")
        if sharded:
            counts = collective_bytes(text)["counts"]
            gate(not any(counts.values()), f"{name}: collectives {counts}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the scenario-sharded service on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    setup_compile_cache()
    allocator = AllocatorConfig(inner="pgd")
    if args.chips == 4:
        check_kernels(run_sharded(allocator, args.seed), sharded=True)
    else:
        check_kernels(run_one_chip(allocator, args.seed), sharded=False)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
