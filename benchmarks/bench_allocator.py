"""Batched vs sequential allocation: the perf case for `solve_batch`.

Solves B i.i.d. scenarios four ways:

  * ``sequential_eager`` — a Python loop of plain `solve` calls, the seed's
    `fl/federated.py` pattern (per-op dispatch every round);
  * ``sequential_jit``   — a jitted single-scenario `solve`, compiled once,
    called B times (one device program per scenario);
  * ``batched``          — ONE jitted `solve_batch` call over the stacked
    scenarios (one device program for the whole sweep, single device);
  * ``sharded``          — the same program with the scenario axis split over
    a `scenario_mesh` of all local devices (B/device_count per device).

The sharded-vs-single-device comparison is only meaningful with >1 device;
run under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to measure
it on CPU (virtual devices share the physical cores, so CPU numbers bound
overhead rather than demonstrate speedup — the sweep exists so accelerator
runs land in the same JSON).

Also sweeps the batched objective-scoring path (`kernels/fedsem_objective`,
PR 4): `solve_batch` with the kernel objective on vs off (same hardened X
asserted), plus a raw scoring microbenchmark — one fused
`ops.objective_grid_batch` call over (B, G) candidates vs a per-scenario
loop of grid evaluations. On CPU the fused path runs the kernel's jnp
oracle (Pallas dispatches on TPU); a Pallas-interpret parity check rides
along so the JSON also records that the kernel path agrees.

Writes ``BENCH_allocator.json`` at the repo root so future PRs have a perf
trajectory to compare against. Run as ``python -m benchmarks.bench_allocator``
(``--smoke`` for the CI-sized quick run).
"""
from __future__ import annotations

import argparse
import json
import pathlib
import platform
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AllocatorConfig,
    Weights,
    sample_params,
    sample_params_batch,
    scenario_mesh,
    solve,
    solve_batch,
    tree_index,
)
from repro.launch.compile_cache import setup_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT_JSON = ROOT / "BENCH_allocator.json"
# quick runs extrapolate the eager baseline — methodologically different
# numbers must not clobber the committed full-run trajectory file
OUT_JSON_QUICK = ROOT / "experiments" / "bench" / "BENCH_allocator_quick.json"


def _bench(fn, warmup: int = 1, reps: int = 1) -> float:
    """Best-of-``reps`` (min is the right location statistic on a small
    shared-core box: scheduler noise only ever adds time)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _objective_sweep(quick: bool, seed: int = 0):
    """Fused batched scoring vs a per-scenario loop, at several (B, G)."""
    from repro.kernels.fedsem_objective import ops, ref

    sizes = [(4, 256)] if quick else [(8, 512), (32, 2048), (64, 8192)]
    n = 8
    rows = []
    for b, g in sizes:
        params = sample_params(jax.random.PRNGKey(seed), N=n, K=2 * n)
        ks = jax.random.split(jax.random.PRNGKey(seed + 1), 4)
        f = jax.random.uniform(ks[0], (b, g, n), minval=1e8, maxval=2e9)
        p = jax.random.uniform(ks[1], (b, g, n), minval=1e-3, maxval=0.1)
        r = jax.random.uniform(ks[2], (b, g, n), minval=1e5, maxval=3e7)
        rho = jax.random.uniform(ks[3], (b, g), minval=0.05, maxval=1.0)
        row = lambda v: jnp.broadcast_to(v[None], (b,) + v.shape)
        vecs = tuple(
            row(v) for v in (params.c, params.d, params.D, params.C,
                             params.t_sc_max, params.f_max)
        )
        kw = dict(
            xi=float(params.xi), eta=float(params.eta),
            dev_mask=row(params.dev_mask),
        )

        fused = jax.jit(
            lambda f, p, r, rho: ops.objective_grid_batch(
                f, p, r, rho, *vecs, 1.0, 1.0, 1.0, **kw
            )
        )
        t_fused = _bench(lambda: fused(f, p, r, rho), warmup=2, reps=3)

        per_scenario = jax.jit(
            lambda f1, p1, r1, rho1: ref.objective_grid(
                f1, p1, r1, rho1,
                params.c, params.d, params.D, params.C,
                params.t_sc_max, params.f_max,
                float(params.xi), float(params.eta), 1.0, 1.0, 1.0,
                dev_mask=params.dev_mask,
            )
        )

        def loop():
            return [per_scenario(f[i], p[i], r[i], rho[i]) for i in range(b)]

        t_loop = _bench(loop, warmup=2, reps=3)

        # Pallas path correctness on a small slice (interpret is an
        # interpreter — timing it would benchmark the interpreter, not TPUs)
        bi, gi = min(b, 2), min(g, 128)
        got = ops.objective_grid_batch(
            f[:bi, :gi], p[:bi, :gi], r[:bi, :gi], rho[:bi, :gi],
            *(v[:bi] for v in vecs), 1.0, 1.0, 1.0,
            xi=kw["xi"], eta=kw["eta"], dev_mask=kw["dev_mask"][:bi],
            use_pallas=True, interpret=True,
        )
        want = ref.objective_grid_batch(
            f[:bi, :gi], p[:bi, :gi], r[:bi, :gi], rho[:bi, :gi],
            *(v[:bi] for v in vecs), 1.0, 1.0, 1.0,
            xi=kw["xi"], eta=kw["eta"], dev_mask=kw["dev_mask"][:bi],
        )
        ok = bool(
            np.allclose(np.asarray(got), np.asarray(want), rtol=5e-7, atol=1e-5)
        )
        rows.append({
            "B": b, "G": g, "N": n,
            "fused_batch_s": t_fused,
            "per_scenario_loop_s": t_loop,
            "speedup_fused_vs_loop": t_loop / t_fused,
            "pallas_interpret_matches_ref": ok,
        })
    return rows


def run(quick: bool = False, seed: int = 0, batch: int = 16, n: int = 4, k: int = 12):
    w = Weights.ones()
    cfg = AllocatorConfig(inner="pgd")                      # kernel objective on
    cfg_jnp = cfg._replace(use_kernel_objective=False)      # plain jnp scoring
    pb = sample_params_batch(jax.random.PRNGKey(seed), batch, N=n, K=k)
    scenarios = [tree_index(pb, i) for i in range(batch)]

    reps = 1 if quick else 3
    t_batched = _bench(lambda: solve_batch(pb, w, cfg).alloc.rho, reps=reps)
    t_batched_jnp = _bench(
        lambda: solve_batch(pb, w, cfg_jnp).alloc.rho, reps=reps
    )

    # sharded sweep: same program, scenario axis split over all local devices
    mesh = scenario_mesh()
    t_sharded = _bench(
        lambda: solve_batch(pb, w, cfg, mesh=mesh).alloc.rho, reps=reps
    )
    x_single = np.asarray(solve_batch(pb, w, cfg).alloc.X)
    x_sharded = np.asarray(solve_batch(pb, w, cfg, mesh=mesh).alloc.X)
    x_jnp_obj = np.asarray(solve_batch(pb, w, cfg_jnp).alloc.X)

    solve_jit = jax.jit(lambda p: solve(p, w, cfg))
    t_seq_jit = _bench(
        lambda: [solve_jit(p).alloc.rho for p in scenarios]
    )

    # eager loop: warm once so jax's eager fragment caches are hot — this is
    # still generous to the baseline relative to the seed's cold-start rounds
    n_eager = 2 if quick else batch
    solve(scenarios[0], w, cfg)
    t0 = time.perf_counter()
    for p in scenarios[:n_eager]:
        jax.block_until_ready(solve(p, w, cfg).alloc.rho)
    t_seq_eager = (time.perf_counter() - t0) / n_eager * batch

    result = {
        "batch": batch,
        "N": n,
        "K": k,
        "inner": cfg.inner,
        "batched_s": t_batched,
        "batched_jnp_objective_s": t_batched_jnp,
        "sharded_s": t_sharded,
        "sharded_devices": mesh.size,
        "sequential_jit_s": t_seq_jit,
        "sequential_eager_s": t_seq_eager,
        "sequential_eager_extrapolated": n_eager != batch,
        "speedup_vs_eager_loop": t_seq_eager / t_batched,
        "speedup_vs_jit_loop": t_seq_jit / t_batched,
        "speedup_sharded_vs_single_device": t_batched / t_sharded,
        "speedup_kernel_vs_jnp_objective": t_batched_jnp / t_batched,
        "objective_sweep": _objective_sweep(quick, seed),
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
        "python_version": platform.python_version(),
        "platform": platform.platform(),
    }
    out = OUT_JSON_QUICK if quick else OUT_JSON
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(result, indent=2) + "\n")

    checks = {
        "batched_3x_faster_than_solve_loop": result["speedup_vs_eager_loop"] >= 3.0,
        "batched_not_slower_than_jit_loop": result["speedup_vs_jit_loop"] >= 1.0,
        # correctness claims, not perf ones: the device split and the kernel
        # objective path must both be invisible in the hardened assignment
        "sharded_matches_single_device": bool((x_sharded == x_single).all()),
        "kernel_objective_matches_jnp_objective": bool(
            (x_jnp_obj == x_single).all()
        ),
        "pallas_interpret_matches_ref": all(
            r["pallas_interpret_matches_ref"] for r in result["objective_sweep"]
        ),
    }
    return [result], checks


#: checks that gate CI (exit nonzero): equivalence claims only — the perf
#: ratios above are informational on shared runners, where a single noisy
#: smoke-mode timing rep must not fail an unrelated PR
GATING_CHECKS = (
    "sharded_matches_single_device",
    "kernel_objective_matches_jnp_objective",
    "pallas_interpret_matches_ref",
)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--smoke", action="store_true",
        help="CI-sized quick run (small batch, extrapolated eager baseline; "
        "writes experiments/bench/BENCH_allocator_quick.json)",
    )
    ap.add_argument("--batch", type=int, default=None)
    args = ap.parse_args(argv)
    setup_compile_cache()
    batch = args.batch if args.batch is not None else (8 if args.smoke else 16)
    rows, checks = run(quick=args.smoke, batch=batch)
    print(json.dumps(rows[0], indent=2))
    print("checks:", checks)
    failed = {k: checks[k] for k in GATING_CHECKS if not checks[k]}
    if failed:
        raise SystemExit(f"benchmark correctness checks failed: {failed}")


if __name__ == "__main__":
    main()
