"""Alg. A2 — the FedSem resource-allocation algorithm (paper §IV-D).

Alternates:
  Step 1: given (P, X), solve P3(f, rho, T) in closed form (Theorem 1);
  Step 2: given (f, rho, T), solve P4 -> P5 for (P, X) — either the
          paper-faithful SCA/KKT path (`inner="sca"`, Alg. A1) or the
          PGD reference solver (`inner="pgd"`, DESIGN.md §8 cross-check);
until |s^(i) - s^(i-1)| <= eps or J_max (we run a fixed J_max scan and return
the trace; convergence is asserted from the trace in tests).

Afterwards X is hardened to binary (every subcarrier to its argmax device,
every device guaranteed >= 1 subcarrier), powers are re-solved given the
binary X, and (f, rho) are re-derived — a beyond-paper robustness step that
guarantees the reported allocation is feasible for the *original* P1.
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .accuracy import AccuracyFn, default_accuracy
from .p3 import solve_p3
from .p5 import P5Config, r_min, solve_p5
from .pgd import PGDConfig, power_given_x, solve_p4_pgd
from .scoring import candidate_objectives, scenario_objective
from .system import objective
from .types import Allocation, SystemParams, Weights


class ExtraStart(NamedTuple):
    """Optional warm-start candidate(s) per scenario (a pytree).

    ``f``/``P``/``X`` are a prior solution at the scenario's (padded) shape —
    e.g. a `repro.serve.warmstart` cache hit or the previous FL round's
    allocation. ``valid`` is a {0, 1} float: scenarios with ``valid == 0``
    carry placeholder arrays and the candidate is excluded from selection
    (its objective is forced to +inf), so a batch can mix hits and misses.
    Batched use stacks a leading B axis on every leaf.

    A CANDIDATE axis may additionally precede the per-scenario shapes
    (``valid``: (C,) single-scenario, (B, C) batched): every candidate is run
    through the same Alg. A2 refine and competes in the same argmin — the
    top-k warm-start path (`repro.serve.warmstart.WarmStartCache.lookup`).
    Candidate-less shapes (scalar / (B,) ``valid``) stay the single-candidate
    program, bit-for-bit.
    """

    f: jax.Array    # (N,) / (B, N) — or (C, N) / (B, C, N)
    P: jax.Array    # (N, K) / (B, N, K) — or (C, N, K) / (B, C, N, K)
    X: jax.Array    # like P
    valid: jax.Array  # scalar / (B,) — or (C,) / (B, C) — in {0., 1.}


class AllocatorConfig(NamedTuple):
    outer_iters: int = 6           # J_max of Alg. A2
    inner: str = "sca"             # "sca" (Alg. A1) | "pgd" (reference) |
                                   # "auto" (run both, keep the better)
    p5: P5Config = P5Config()
    pgd: PGDConfig = PGDConfig()
    #: route objective scoring (multi-start selection, the per-iteration
    #: trace) through the batched `kernels/fedsem_objective` evaluator:
    #: Pallas on TPU, the kernel's fused jnp oracle elsewhere (`core.scoring`
    #: auto-fallback, so CPU and sharded ``mesh=`` solves work unchanged).
    #: False keeps the plain per-candidate `system.objective` path.
    use_kernel_objective: bool = True


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["alloc", "trace"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class AllocatorResult:
    alloc: Allocation
    trace: jax.Array  # objective s^(i) per outer iteration


def equal_start(params: SystemParams):
    """Round-robin X, per-subcarrier power Pmax/|K_n|, f = fmax/2 (warm start).

    Mask-aware: real subcarriers are round-robined over the *real* devices
    (padded entries get nothing), so a padded scenario starts from exactly the
    same assignment as its exact-shape twin.
    """
    k_idx = jnp.arange(params.K)
    n_real = jnp.maximum(jnp.sum(params.dev_mask), 1.0).astype(jnp.int32)
    owner = k_idx % n_real
    X = jnp.zeros((params.N, params.K)).at[owner, k_idx].set(params.sc_mask)
    n_sc = jnp.sum(X, axis=-1, keepdims=True)
    P = X * params.p_max[:, None] / jnp.maximum(n_sc, 1.0)
    f = params.f_max * 0.5
    return f, P, X


def low_power_start(params: SystemParams, margin: float = 1.5):
    """Round-robin X, powers sized to just clear the SemCom rate floor.

    The alternating P3/P4 decomposition has init-dependent fixed points: from
    an equal-power start, Theorem 1 picks f so every uncapped device is
    exactly tight on T, which pins r_min at the *current* rate and blocks any
    power reduction. Starting near the SemCom floor r = C/Tsc_max (the true
    binding rate for the paper's defaults, where E_sc dominates) lets the
    alternation settle at the low-energy fixed point. Multi-start over both
    (paper leaves "the initial feasible solution" unspecified).
    """
    f, _, X = equal_start(params)
    n_sc = jnp.maximum(jnp.sum(X, axis=-1), 1.0)
    target = margin * params.C / params.t_sc_max             # rho=1 worst case
    per_sc = target / n_sc                                   # rate per subcarrier
    snr = jnp.exp2(per_sc / params.bbar) - 1.0
    P = X * (snr[:, None] * params.noise_sc / jnp.maximum(params.g, 1e-18))
    # stay feasible: respect the per-device power budget
    scale = jnp.minimum(1.0, params.p_max / jnp.maximum(jnp.sum(P, -1), 1e-12))
    P = P * scale[:, None]
    return f, P, X


def full_payload_start(
    params: SystemParams, weights: Weights, pgd_cfg: PGDConfig = PGDConfig()
):
    """(P, X) pre-optimised by PGD at rho = 1 (full SemCom payload).

    The alternation's fixed point is init-dependent (see `low_power_start`):
    both existing starts can settle at rho < 1, trading accuracy for energy,
    even when the accuracy weight makes rho ~ 1 optimal. Pre-optimising
    (P, X) against the full payload with the rho = 1 rate floor — exactly the
    communication-only subproblem — gives Alg. A2 a start whose Theorem-1
    step keeps rho high, so the multi-start argmin dominates the
    comm-opt-only baseline by construction (same (P, X) engine, plus the
    closed-form optimal (f, rho, T) on top).
    """
    f, P, X = equal_start(params)
    payload = params.D + params.C                       # rho = 1
    rmin = params.C / params.t_sc_max                   # SemCom deadline floor
    P, X = solve_p4_pgd(params, weights.kappa1, payload, rmin, P, X, pgd_cfg)
    return f, P, X


def repair_rate_floor(params: SystemParams, P, X, rmin, iters: int = 30):
    """Per-device multiplicative power rescale so r_n >= rmin_n (bisection).

    The inner solvers treat the rate floor with multipliers/penalties and can
    exit slightly infeasible; left unrepaired the violation compounds across
    Alg. A2 iterations (rho_max = Tsc_max r / C collapses). Rates increase
    monotonically in a per-device power scale, so a bisection on the scale
    restores feasibility; devices that cannot reach rmin even at Pmax are
    clamped to their budget.
    """
    from .system import device_rate

    p_tot = jnp.maximum(jnp.sum(P, -1), 1e-12)
    s_cap = params.p_max / p_tot                       # max admissible scale

    def rate_at(s):
        return device_rate(params, P * s[:, None], X)

    need = rate_at(jnp.ones_like(p_tot)) < rmin
    lo = jnp.ones_like(p_tot)
    hi = jnp.maximum(s_cap, 1.0)

    def body(_, state):
        lo, hi = state
        mid = 0.5 * (lo + hi)
        ok = rate_at(mid) >= rmin
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    s = jnp.where(need, jnp.minimum(hi, s_cap), 1.0)
    return P * s[:, None]


def harden_x(X: jnp.ndarray, N: int, K: int, dev_mask=None, sc_mask=None) -> jnp.ndarray:
    """Binary X: argmax per subcarrier, then guarantee >=1 subcarrier/device.

    With masks (padded scenarios, see `pad_params`): padded devices never win
    or steal a subcarrier, padded subcarriers stay unassigned, and ownership
    counts / donor checks consider real subcarriers only — so the real block
    of the hardened assignment is identical to hardening the exact-shape
    scenario.
    """
    if dev_mask is None:
        dev_mask = jnp.ones((N,), X.dtype)
    if sc_mask is None:
        sc_mask = jnp.ones((K,), X.dtype)
    assign = jnp.argmax(jnp.where(dev_mask[:, None] > 0.0, X, -jnp.inf), axis=0)

    def fix_device(n, assign):
        counts = jnp.zeros((N,), X.dtype).at[assign].add(sc_mask)  # real subcarriers
        need = (counts[n] < 0.5) & (dev_mask[n] > 0.0)
        donor_ok = (counts[assign] > 1.5) & (sc_mask > 0.0)  # only steal real sc from the rich
        score = jnp.where(donor_ok, X[n], -jnp.inf)
        k_star = jnp.argmax(score)
        return jnp.where(need, assign.at[k_star].set(n), assign)

    assign = jax.lax.fori_loop(0, N, fix_device, assign)
    return jnp.zeros((N, K)).at[assign, jnp.arange(K)].set(sc_mask)


def solve(
    params: SystemParams,
    weights: Weights,
    cfg: AllocatorConfig = AllocatorConfig(),
    accuracy: AccuracyFn | None = None,
    extra_start: ExtraStart | None = None,
) -> AllocatorResult:
    """Alg. A2 with multi-start (equal + low-power + full-payload inits),
    best kept.

    inner="auto" additionally races the paper-faithful SCA path against the
    PGD cross-check solver and keeps the better allocation. With
    ``cfg.use_kernel_objective`` (default) the multi-start selection and the
    per-iteration trace score through the batched `kernels/fedsem_objective`
    evaluator (`core.scoring`); scores agree with `system.objective` to
    float32 round-off, so the hardened result is unchanged.

    ``extra_start`` optionally adds one more multi-start candidate — a prior
    solution (warm start) run through the same Alg. A2 pipeline and competing
    in the same best-of selection (see `refine_with_start` for the dominance
    and cold-equivalence guarantees). ``None`` leaves this function
    bit-for-bit identical to the pre-warm-start solver.
    """
    acc = accuracy or default_accuracy()
    base = _solve_multi_start(params, weights, cfg, acc)
    if extra_start is None:
        return base
    return refine_with_start(params, weights, cfg, acc, extra_start, base)


def _solve_multi_start(
    params: SystemParams, weights: Weights, cfg: AllocatorConfig, acc: AccuracyFn
) -> AllocatorResult:
    """The cold multi-start solve (the original `solve` body, unchanged)."""
    inners = ("sca", "pgd") if cfg.inner == "auto" else (cfg.inner,)
    starts = (
        equal_start(params),
        low_power_start(params),
        full_payload_start(params, weights, cfg.pgd),
    )
    results = [
        _solve_from(params, weights, cfg._replace(inner=inner), acc, start)
        for inner in inners
        for start in starts
    ]
    if cfg.use_kernel_objective:
        # one fused batched-kernel call scores every start (G = #candidates);
        # under solve_batch's vmap this batches further into (B, G)
        cand = jax.tree.map(lambda *xs: jnp.stack(xs), *[r.alloc for r in results])
        objs = candidate_objectives(params, weights, cand, acc)
    else:
        objs = jnp.stack([objective(params, weights, r.alloc, acc) for r in results])
    best = jnp.argmin(objs)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *results)
    return jax.tree.map(lambda x: x[best], stacked)


def sanitize_start(params: SystemParams, extra: ExtraStart):
    """Clamp an externally supplied (f, P, X) into the solver's domain.

    Warm starts come from outside the solver (a cache, a previous FL round —
    possibly for a *different* scenario under the same signature), so nothing
    about them can be trusted: non-finite entries become benign values, f is
    clipped into (0, f_max], P into [0, p_max] per entry, X into [0, 1], and
    masked (padded) rows/columns are zeroed so a cached exact-shape entry
    padded into a bucket stays inert exactly like the built-in starts. A
    degenerate start (e.g. a device with no subcarrier) may still yield an
    infinite objective downstream — `refine_with_start` masks those out of
    the selection, so garbage can never win, only lose.
    """
    f = jnp.nan_to_num(extra.f, nan=0.0, posinf=0.0, neginf=0.0)
    f = jnp.clip(f, 1e-6 * params.f_max, params.f_max)
    P = jnp.nan_to_num(extra.P, nan=0.0, posinf=0.0, neginf=0.0)
    P = jnp.clip(P, 0.0, params.p_max[:, None])
    X = jnp.nan_to_num(extra.X, nan=0.0, posinf=0.0, neginf=0.0)
    X = jnp.clip(X, 0.0, 1.0)
    live = params.dev_mask[:, None] * params.sc_mask[None, :]
    return f, P * live, X * live


def refine_with_start(
    params: SystemParams,
    weights: Weights,
    cfg: AllocatorConfig,
    acc: AccuracyFn,
    extra: ExtraStart,
    base: AllocatorResult,
) -> AllocatorResult:
    """Fold one extra multi-start candidate into an already-solved result.

    Runs the full Alg. A2 pipeline (P3/P5/PGD inner solvers, repair,
    hardening) from ``extra``'s (f, P, X) — under every inner the config
    races, like the built-in starts — then picks the better of {base
    result, extra candidate(s)} by the same objective scoring the multi-start
    selection uses.

    Guarantees (the warm-start equivalence rows, tests/test_warmstart.py):

    * **Dominance**: the selected objective is ``min(base, candidates)``, so
      a warm start can only help or tie — never hurt — no matter how stale
      or wrong-scenario the cached entry is (a garbage candidate scores +inf
      via the finiteness guard and loses).
    * **Cold equivalence**: with ``extra.valid == 0`` the candidates are
      masked to +inf and ``argmin`` (first-occurrence tie-break) returns the
      ``base`` leaves unchanged — bit-for-bit, because selection is a gather
      over stacked results, and ``base`` itself was produced by the
      unmodified cold program.

    ``extra`` may carry a leading candidate axis (``valid`` of shape (C,)):
    each candidate is refined under every inner and all compete in one
    argmin, per-candidate validity masking each one independently. C == 1
    and the axis-less form trace the same candidate order, so the single-hit
    program is the legacy one.
    """
    multi = jnp.ndim(extra.valid) > 0
    n_cand = int(extra.valid.shape[0]) if multi else 1
    extras = (
        [jax.tree.map(lambda x: x[c], extra) for c in range(n_cand)]
        if multi
        else [extra]
    )
    inners = ("sca", "pgd") if cfg.inner == "auto" else (cfg.inner,)
    cands, valids = [], []
    for inner in inners:
        for e in extras:
            start = sanitize_start(params, e)
            cands.append(
                _solve_from(params, weights, cfg._replace(inner=inner), acc, start)
            )
            valids.append(e.valid)
    results = [base] + cands
    if cfg.use_kernel_objective:
        stacked_allocs = jax.tree.map(
            lambda *xs: jnp.stack(xs), *[r.alloc for r in results]
        )
        objs = candidate_objectives(params, weights, stacked_allocs, acc)
    else:
        objs = jnp.stack([objective(params, weights, r.alloc, acc) for r in results])
    # candidates (every index > 0) only compete when their start was real AND
    # their objective is finite; the base result is never masked
    is_cand = jnp.arange(len(results)) > 0
    valid_vec = jnp.concatenate(
        [jnp.ones((1,), jnp.float32), jnp.stack(valids).astype(jnp.float32)]
    )
    ok = (valid_vec > 0.0) & jnp.isfinite(objs)
    objs = jnp.where(is_cand & ~ok, jnp.inf, objs)
    best = jnp.argmin(objs)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *results)
    return jax.tree.map(lambda x: x[best], stacked)


def _solve_batch_impl(
    params_batch, weights, acc, cfg, weights_batched, acc_batched=False
):
    w_axis = 0 if weights_batched else None
    a_axis = 0 if acc_batched else None
    return jax.vmap(
        lambda p, w, a: solve(p, w, cfg, a), in_axes=(0, w_axis, a_axis)
    )(params_batch, weights, acc)


_solve_batch_jit = jax.jit(
    _solve_batch_impl, static_argnames=("cfg", "weights_batched", "acc_batched")
)


def _refine_batch_impl(
    params_batch, weights, acc, extra, base, cfg, weights_batched, acc_batched=False
):
    """Per-scenario `refine_with_start` vmapped over the batch axis.

    ``base`` is the cold `solve_batch` result for the same batch; scenarios
    whose ``extra.valid`` is 0 pass their base row through bit-for-bit (the
    selection gathers the base leaves), so a mixed hit/miss batch never
    perturbs the misses.
    """
    w_axis = 0 if weights_batched else None
    a_axis = 0 if acc_batched else None
    return jax.vmap(
        lambda p, w, a, e, b: refine_with_start(p, w, cfg, a, e, b),
        in_axes=(0, w_axis, a_axis, 0, 0),
    )(params_batch, weights, acc, extra, base)


_refine_batch_jit = jax.jit(
    _refine_batch_impl, static_argnames=("cfg", "weights_batched", "acc_batched")
)


@functools.lru_cache(maxsize=None)
def sharded_refine_solver(mesh, weights_batched: bool, acc_batched: bool = False):
    """Jitted `_refine_batch_impl` with the scenario axis sharded on ``mesh``
    (the warm-start sibling of `sharded_batch_solver`: extra starts and the
    base result shard with the scenarios; the accuracy fit shards with them
    when ``acc_batched``, else replicates)."""
    from .distribute import replicated, scenario_map, scenario_sharding

    scen = scenario_sharding(mesh)
    rep = replicated(mesh)

    def run(params_batch, weights, acc, extra, base, cfg, weights_batched,
            acc_batched=False):
        body = functools.partial(
            _refine_batch_impl, cfg=cfg, weights_batched=weights_batched,
            acc_batched=acc_batched,
        )
        split = (True, weights_batched, acc_batched, True, True)
        return scenario_map(body, mesh, split)(
            params_batch, weights, acc, extra, base
        )

    return jax.jit(
        run,
        static_argnames=("cfg", "weights_batched", "acc_batched"),
        in_shardings=(
            scen,
            scen if weights_batched else rep,
            scen if acc_batched else rep,
            scen,
            scen,
        ),
        out_shardings=scen,
    )


@functools.lru_cache(maxsize=None)
def sharded_batch_solver(mesh, weights_batched: bool, acc_batched: bool = False):
    """Jitted `solve_batch` body with the scenario axis sharded on ``mesh``.

    The vmapped per-scenario body runs under ``jax.shard_map`` over the 1-D
    scenario mesh (`core.distribute.scenario_map`): each device solves its
    own B/mesh.size slice — the objective kernel included — and the program
    holds no cross-device communication. Cached per
    (mesh, weights_batched, acc_batched) — `AllocatorConfig` stays a static
    jit arg, so one cache entry covers every config. The jit object is also
    the serving layer's AOT entry point (``.lower(...).compile()``).
    """
    from .distribute import replicated, scenario_map, scenario_sharding

    scen = scenario_sharding(mesh)
    rep = replicated(mesh)

    def run(params_batch, weights, acc, cfg, weights_batched, acc_batched=False):
        body = functools.partial(
            _solve_batch_impl, cfg=cfg, weights_batched=weights_batched,
            acc_batched=acc_batched,
        )
        split = (True, weights_batched, acc_batched)
        return scenario_map(body, mesh, split)(params_batch, weights, acc)

    return jax.jit(
        run,
        static_argnames=("cfg", "weights_batched", "acc_batched"),
        in_shardings=(
            scen,
            scen if weights_batched else rep,
            scen if acc_batched else rep,
        ),
        out_shardings=scen,
    )


def solve_batch(
    params_batch: SystemParams,
    weights: Weights,
    cfg: AllocatorConfig = AllocatorConfig(),
    accuracy: AccuracyFn | None = None,
    *,
    weights_batched: bool = False,
    acc_batched: bool = False,
    mesh=None,
    extra_starts: ExtraStart | None = None,
) -> AllocatorResult:
    """Batched Alg. A2: solve B scenarios in one jitted, vmapped call.

    ``params_batch`` is a batch-stacked ``SystemParams`` (`stack_params` /
    `sample_params_batch`), ``g`` of shape (B, N, K). The full pipeline —
    multi-start, the P3/P5/PGD inner solvers, rate-floor repair, objective
    scoring (the batched `kernels/fedsem_objective` path when
    ``cfg.use_kernel_objective``, see `core.scoring`) and
    `harden_x` — is vmapped, so the whole sweep is a single XLA program:
    tracing happens once per (shape, cfg), not once per scenario, and the
    per-scenario math batches into wide kernels. Returns an `AllocatorResult`
    whose leaves carry a leading B axis (use `repro.core.tree_index` to pick
    one scenario out).

    ``weights`` is broadcast to every scenario unless ``weights_batched`` is
    set, in which case its leaves must carry a matching leading B axis (used
    for weight sweeps, paper Fig. 3).

    ``accuracy`` likewise broadcasts one A(rho) fit to every scenario unless
    ``acc_batched`` is set, in which case its leaves must carry a matching
    leading B axis (`stack_accuracy`) — one power-law fit per scenario, the
    multi-tenant serving path. Rows are independent under vmap, so a uniform
    stack matches the broadcast program and mixed stacks match per-row
    as-if-alone solves, exactly (tests/test_multitenant_accuracy.py).

    ``mesh`` optionally shards the scenario axis across devices (a 1-D
    `core.distribute.scenario_mesh`): the same vmapped program compiles once
    with the batch split device_count ways and no cross-device communication.
    Batches not divisible by ``mesh.size`` are padded by replicating the tail
    scenario and sliced back — exact, since scenarios are independent.

    ``extra_starts`` optionally injects warm-start candidate(s) per scenario
    (an `ExtraStart` with leading-B leaves — optionally a (B, C) candidate
    axis for top-k hits — e.g. `repro.serve.warmstart` cache lookups): the
    cold batch solves first through the UNCHANGED program,
    then a second jitted pass (`_refine_batch_impl`) runs Alg. A2 from each
    valid start and keeps the per-scenario better of the two. ``None`` (the
    default) is exactly the cold program — bit-for-bit, which is the
    cold==disabled row of the equivalence table.
    """
    if params_batch.g.ndim != 3:
        raise ValueError(
            "solve_batch expects batch-stacked params with g of shape "
            f"(B, N, K); got g.shape={tuple(params_batch.g.shape)}. "
            "Stack scenarios with stack_params() or sample them with "
            "sample_params_batch()."
        )
    if weights_batched:
        b = params_batch.g.shape[0]
        for path, leaf in jax.tree_util.tree_leaves_with_path(weights):
            shape = jnp.shape(leaf)
            if len(shape) < 1 or shape[0] != b:
                raise ValueError(
                    "solve_batch(weights_batched=True) requires every weights "
                    f"leaf to carry a leading batch axis of size B={b} matching "
                    f"params_batch; leaf 'weights{jax.tree_util.keystr(path)}' "
                    f"has shape {shape}. Stack per-scenario weights with "
                    "stack_weights(weights_list), or drop weights_batched to "
                    "broadcast one Weights to all scenarios."
                )
    if acc_batched:
        b = params_batch.g.shape[0]
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            accuracy if accuracy is not None else default_accuracy()
        ):
            shape = jnp.shape(leaf)
            if len(shape) < 1 or shape[0] != b:
                raise ValueError(
                    "solve_batch(acc_batched=True) requires every accuracy "
                    f"leaf to carry a leading batch axis of size B={b} matching "
                    f"params_batch; leaf 'accuracy{jax.tree_util.keystr(path)}' "
                    f"has shape {shape}. Stack per-scenario fits with "
                    "stack_accuracy(acc_list), or drop acc_batched to "
                    "broadcast one AccuracyFn to all scenarios."
                )
    if extra_starts is not None:
        b = params_batch.g.shape[0]
        v = jnp.shape(extra_starts.valid)
        if len(v) not in (1, 2) or v[0] != b:
            raise ValueError(
                "solve_batch(extra_starts=...) requires extra_starts.valid of "
                f"shape (B,) or (B, C) with B={b} matching params_batch; got "
                f"{v}. Stack per-scenario warm starts with a leading batch "
                "axis (repro.serve.warmstart builds these from cache hits)."
            )
    acc = accuracy or default_accuracy()
    if mesh is None:
        base = _solve_batch_jit(
            params_batch, weights, acc, cfg, weights_batched, acc_batched
        )
        if extra_starts is None:
            return base
        return _refine_batch_jit(
            params_batch,
            weights,
            acc,
            extra_starts,
            base,
            cfg,
            weights_batched,
            acc_batched,
        )

    from .distribute import pad_batch, round_up, slice_batch

    b = params_batch.g.shape[0]
    b_pad = round_up(b, mesh.size)
    if b_pad != b:
        params_batch = pad_batch(params_batch, b_pad)
        if weights_batched:
            weights = pad_batch(weights, b_pad)
        if acc_batched:
            acc = pad_batch(acc, b_pad)
        if extra_starts is not None:
            extra_starts = pad_batch(extra_starts, b_pad)
    res = sharded_batch_solver(mesh, weights_batched, acc_batched)(
        params_batch, weights, acc, cfg, weights_batched, acc_batched
    )
    if extra_starts is not None:
        res = sharded_refine_solver(mesh, weights_batched, acc_batched)(
            params_batch, weights, acc, extra_starts, res, cfg, weights_batched,
            acc_batched,
        )
    return slice_batch(res, b) if b_pad != b else res


def _solve_from(
    params: SystemParams,
    weights: Weights,
    cfg: AllocatorConfig,
    acc: AccuracyFn,
    start,
) -> AllocatorResult:
    """One Alg. A2 run from a given (f, P, X) start."""
    f, P, X = start

    def outer(carry, _):
        f, P, X = carry
        p3 = solve_p3(params, weights, P, X, acc)           # Step 1 (Theorem 1)
        payload = params.D + p3.rho * params.C
        rmin = r_min(params, p3.rho, p3.T, p3.f)
        if cfg.inner == "sca":                               # Step 2 (Alg. A1)
            sol = solve_p5(params, weights, p3.rho, p3.T, p3.f, P, X, cfg.p5)
            P_new, X_new = sol.P, sol.X
        else:
            P_new, X_new = solve_p4_pgd(
                params, weights.kappa1, payload, rmin, P, X, cfg.pgd
            )
        P_new = repair_rate_floor(params, P_new, X_new, rmin)
        cand = Allocation(p3.f, P_new, X_new, p3.rho)
        s = (
            scenario_objective(params, weights, cand, acc)
            if cfg.use_kernel_objective
            else objective(params, weights, cand, acc)
        )
        return (p3.f, P_new, X_new), s

    (f, P, X), trace = jax.lax.scan(outer, (f, P, X), None, length=cfg.outer_iters)

    # ---- hardening: binary X, re-solved powers, re-derived (f, rho) ----
    Xb = harden_x(X, params.N, params.K, params.dev_mask, params.sc_mask)
    p3 = solve_p3(params, weights, P * Xb, Xb, acc)
    payload = params.D + p3.rho * params.C
    rmin = r_min(params, p3.rho, p3.T, p3.f)
    P = power_given_x(params, weights.kappa1, payload, rmin, Xb, P0=P * Xb)
    P = repair_rate_floor(params, P, Xb, rmin)
    p3 = solve_p3(params, weights, P, Xb, acc)               # final (f, rho, T)
    alloc = Allocation(f=p3.f, P=P, X=Xb, rho=p3.rho)
    return AllocatorResult(alloc=alloc, trace=trace)
