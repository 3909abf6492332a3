"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU), with
shape/dtype sweeps per kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_default_matmul_precision", "highest")


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

def _qkv(key, B, S, H, KV, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd), dtype)
    return q, k, v


@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 4, 4, 64),      # MHA
    (2, 256, 4, 2, 64),      # GQA
    (1, 256, 8, 1, 32),      # MQA, small head
    (1, 192, 2, 2, 128),     # S not a block multiple (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_causal(B, S, H, KV, hd, dtype):
    from repro.kernels.flash_attention import ops, ref

    q, k, v = _qkv(jax.random.PRNGKey(0), B, S, H, KV, hd, dtype)
    got = ops.flash_attention(q, k, v, causal=True, use_pallas=True,
                              interpret=True, bq=64, bk=64)
    want = ref.naive_attention(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("window,cap,causal", [
    (64, None, True),        # sliding window
    (None, 50.0, True),      # gemma softcap
    (None, None, False),     # encoder (bidirectional)
])
def test_flash_attention_variants(window, cap, causal):
    from repro.kernels.flash_attention import ops, ref

    q, k, v = _qkv(jax.random.PRNGKey(1), 2, 256, 4, 2, 64, jnp.float32)
    got = ops.flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                              use_pallas=True, interpret=True, bq=64, bk=64)
    want = ref.naive_attention(q, k, v, causal=causal, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_jnp_flash_matches_naive():
    """The model's chunked-jnp path is itself validated against the oracle."""
    from repro.kernels.flash_attention import ref
    from repro.models.attention import flash_attention as jnp_flash

    q, k, v = _qkv(jax.random.PRNGKey(2), 2, 200, 4, 2, 64, jnp.float32)
    pos = jnp.arange(200, dtype=jnp.int32)
    got = jnp_flash(q, k, v, q_positions=pos, kv_positions=pos,
                    causal=True, window=64, q_chunk=64, kv_chunk=64)
    want = ref.naive_attention(q, k, v, causal=True, window=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# rwkv6 scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,H,S,hd", [(1, 2, 128, 64), (2, 4, 96, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_scan(B, H, S, hd, dtype):
    from repro.kernels.rwkv6_scan import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    r = jax.random.normal(ks[0], (B, H, S, hd), dtype)
    k = jax.random.normal(ks[1], (B, H, S, hd), dtype)
    v = jax.random.normal(ks[2], (B, H, S, hd), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, H, S, hd))).astype(jnp.float32) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, hd), dtype)
    got = ops.rwkv6_scan(r, k, v, w.astype(dtype), u, use_pallas=True,
                         interpret=True, ct=32)
    want = ref.rwkv6_scan_ref(r, k, v, w.astype(dtype), u)[0]
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


def test_rwkv6_model_uses_equivalent_recurrence():
    """The model's time_mix scan equals the kernel oracle on matched inputs."""
    from repro.kernels.rwkv6_scan import ref

    B, H, S, hd = 1, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r, k, v = (jax.random.normal(ks[i], (B, H, S, hd)) for i in range(3))
    w = jnp.full((B, H, S, hd), 0.9)
    u = jax.random.normal(ks[4], (H, hd))
    y, _ = ref.rwkv6_scan_ref(r, k, v, w, u)
    # manual recurrence
    S_state = np.zeros((B, H, hd, hd), np.float32)
    outs = np.zeros((B, H, S, hd), np.float32)
    rn, kn, vn, wn, un = map(np.asarray, (r, k, v, w, u))
    for t in range(S):
        kv = kn[:, :, t, :, None] * vn[:, :, t, None, :]
        outs[:, :, t] = np.einsum(
            "bhk,bhkv->bhv", rn[:, :, t], S_state + un[None, :, :, None] * kv
        )
        S_state = wn[:, :, t, :, None] * S_state + kv
    np.testing.assert_allclose(np.asarray(y), outs, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# mamba scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,di,N", [(1, 64, 128, 8), (2, 96, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan(B, S, di, N, dtype):
    from repro.kernels.mamba_scan import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(ks[0], (B, S, di), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di))).astype(jnp.float32) * 0.1
    Bm = jax.random.normal(ks[2], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    A = -jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (di, N)))
    D = jnp.ones((di,), jnp.float32)
    got = ops.mamba_scan(x, dt.astype(dtype), Bm, Cm, A, D, use_pallas=True,
                         interpret=True, ct=32, bd=32)
    want = ref.mamba_scan_ref(x, dt.astype(dtype), Bm, Cm, A, D)[0]
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol, rtol=tol
    )


# ---------------------------------------------------------------------------
# fedsem objective grid
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("G,N", [(512, 4), (1024, 10), (700, 6)])
@pytest.mark.parametrize("masked", [False, True], ids=["full", "masked"])
def test_fedsem_objective_grid(G, N, masked):
    from repro.core import Weights, sample_params
    from repro.kernels.fedsem_objective import ops, ref

    params = sample_params(jax.random.PRNGKey(7), N=N, K=2 * N)
    ks = jax.random.split(jax.random.PRNGKey(8), 4)
    f = jax.random.uniform(ks[0], (G, N), minval=1e8, maxval=2e9)
    p = jax.random.uniform(ks[1], (G, N), minval=1e-3, maxval=0.1)
    r = jax.random.uniform(ks[2], (G, N), minval=1e5, maxval=3e7)
    rho = jax.random.uniform(ks[3], (G,), minval=0.05, maxval=1.0)
    dev_mask = (
        jnp.asarray([1.0] * (N - N // 2) + [0.0] * (N // 2)) if masked else None
    )
    args = (f, p, r, rho, params.c, params.d, params.D, params.C,
            params.t_sc_max, params.f_max, float(params.xi), float(params.eta),
            1.0, 1.0, 1.0)
    got = ops.objective_grid(*args, dev_mask=dev_mask, use_pallas=True, interpret=True)
    want = ref.objective_grid(*args, dev_mask=dev_mask)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-4
    )


def test_fedsem_objective_grid_masked_matches_system_objective():
    """Regression: the grid evaluator was mask-unaware — it scored accuracy
    with the raw padded device count and ran feasibility checks over padded
    rows, so the exhaustive/random-search baselines (which route through
    `ops.objective_grid`) disagreed with the mask-aware `system.objective` on
    any `pad_params`-padded scenario."""
    from repro.core import Allocation, Weights, pad_params, sample_params
    from repro.core.allocator import equal_start, harden_x
    from repro.core.system import device_power, device_rate, objective
    from repro.kernels.fedsem_objective import ops

    p = sample_params(jax.random.PRNGKey(9), N=4, K=8)
    pp = pad_params(p, 8, 16)
    f, P, X = equal_start(pp)
    X = harden_x(X, pp.N, pp.K, pp.dev_mask, pp.sc_mask)
    rho = jnp.float32(0.7)
    # padded rows carry garbage the masks must neutralise: candidate f above
    # the padded f_max (= 1.0) used to trip the feasibility check to +inf
    f = jnp.where(pp.dev_mask > 0, f, 2.0)
    r = device_rate(pp, P, X)
    p_n = device_power(P)
    for use_pallas in (False, True):
        got = ops.objective_grid(
            f[None], p_n[None], r[None], rho[None],
            pp.c, pp.d, pp.D, pp.C, pp.t_sc_max, pp.f_max,
            float(pp.xi), float(pp.eta), 1.0, 1.0, 1.0,
            dev_mask=pp.dev_mask, use_pallas=use_pallas, interpret=use_pallas,
        )
        want = objective(pp, Weights.ones(), Allocation(f=f, P=P, X=X, rho=rho))
        assert np.isfinite(float(got[0])), "masked feasibility flagged padded row"
        np.testing.assert_allclose(float(got[0]), float(want), rtol=1e-5)


# ---------------------------------------------------------------------------
# fedsem objective — batched-over-scenarios kernel (PR 4)
# ---------------------------------------------------------------------------

def _batch_grid_inputs(key, B, G, N, masked_rows=True):
    """Random (B, G, N) candidate grids + per-scenario parameter rows."""
    ks = jax.random.split(key, 9)
    f = jax.random.uniform(ks[0], (B, G, N), minval=1e8, maxval=2e9)
    p = jax.random.uniform(ks[1], (B, G, N), minval=1e-3, maxval=0.1)
    r = jax.random.uniform(ks[2], (B, G, N), minval=1e5, maxval=3e7)
    rho = jax.random.uniform(ks[3], (B, G), minval=0.05, maxval=1.0)
    c = jax.random.uniform(ks[4], (B, N), minval=1e3, maxval=1e4)
    d = jax.random.uniform(ks[5], (B, N), minval=1e5, maxval=1e6)
    D = jax.random.uniform(ks[6], (B, N), minval=1e5, maxval=1e6)
    C = jax.random.uniform(ks[7], (B, N), minval=1e5, maxval=1e6)
    tsc = jnp.full((B, N), 0.5)
    fmax = jnp.full((B, N), 2e9)
    mask = (
        (jax.random.uniform(ks[8], (B, N)) > 0.4).astype(jnp.float32)
        .at[:, 0].set(1.0)                       # >= 1 real device per scenario
        if masked_rows
        else jnp.ones((B, N), jnp.float32)
    )
    return (f, p, r, rho, c, d, D, C, tsc, fmax), mask


@pytest.mark.parametrize("B,G,N", [
    (3, 700, 4),     # padded candidate axis (700 -> 768), per-row masks
    (1, 6, 5),       # B=1 degenerate batch, tiny multi-start-sized G
    (8, 1, 6),       # G=1: one allocation per scenario (the serving path)
    (8, 1, 16),      # the serving flush at the Table-I bucket: 8 slots x N=16
    (3, 1, 16),      # a scenario count that is not a multiple of 8
])
@pytest.mark.parametrize("feasible_mask", [True, False], ids=["feas", "raw"])
def test_fedsem_objective_batch_kernel_matches_ref(B, G, N, feasible_mask):
    """Batched Pallas grid (interpret) vs the batched jnp oracle, per-scenario
    dev_mask rows and per-scenario runtime weights. The infeasibility mask
    must agree exactly; finite scores to a couple of float32 ulps (the kernel
    is jit-compiled, the oracle eager — XLA's FMA/reduction codegen differs
    at that level between the two layouts)."""
    from repro.kernels.fedsem_objective import ops, ref

    args, mask = _batch_grid_inputs(jax.random.PRNGKey(11), B, G, N)
    kap = (jnp.linspace(0.5, 2.0, B), jnp.ones((B,)), jnp.full((B,), 1.3))
    kw = dict(xi=1e-28, eta=10, accuracy_ab=(0.6356, 0.4025), dev_mask=mask,
              check_feasible=feasible_mask)
    got = np.asarray(ops.objective_grid_batch(
        *args, *kap, use_pallas=True, interpret=True, **kw
    ))
    want = np.asarray(ref.objective_grid_batch(*args, *kap, **kw))
    assert got.shape == (B, G)
    np.testing.assert_array_equal(np.isinf(got), np.isinf(want))
    finite = np.isfinite(want)
    np.testing.assert_allclose(got[finite], want[finite], rtol=5e-7, atol=1e-5)


def test_fedsem_objective_batch_ref_equals_per_scenario_ref():
    """The batched oracle is exactly B stacked single-scenario oracles."""
    from repro.kernels.fedsem_objective import ref

    B, G, N = 4, 33, 5
    args, mask = _batch_grid_inputs(jax.random.PRNGKey(12), B, G, N)
    f, p, r, rho, c, d, D, C, tsc, fmax = args
    kap = np.linspace(0.7, 1.4, B)
    batch = ref.objective_grid_batch(
        *args, kap, 1.0, 1.0, xi=1e-28, eta=10, dev_mask=mask
    )
    for b in range(B):
        one = ref.objective_grid(
            f[b], p[b], r[b], rho[b], c[b], d[b], D[b], C[b], tsc[b], fmax[b],
            1e-28, 10, float(kap[b]), 1.0, 1.0, dev_mask=mask[b],
        )
        np.testing.assert_array_equal(np.asarray(batch[b]), np.asarray(one))


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_scoring_matches_system_objective_across_padded_buckets(use_pallas):
    """`core.scoring` (the allocator/serving scoring path) == mask-aware
    `system.objective`, scenario by scenario, on a padded-bucket batch with
    per-scenario weights — the kernel==ref==system three-way parity the
    batched objective path rests on."""
    from repro.core import (
        Weights, pad_params, sample_params, stack_params, stack_weights,
    )
    from repro.core.allocator import equal_start, harden_x
    from repro.core.scoring import batch_objectives
    from repro.core.system import objective
    from repro.core.types import Allocation

    scenarios, allocs, weights = [], [], []
    bbar = 20e6 / 8                      # shared so the padded B meta matches
    for i, (n, k) in enumerate([(3, 7), (4, 8), (2, 5), (4, 8)]):
        p = sample_params(jax.random.PRNGKey(20 + i), N=n, K=k, B=bbar * k)
        pp = pad_params(p, 4, 8)
        f, P, X = equal_start(pp)
        X = harden_x(X, pp.N, pp.K, pp.dev_mask, pp.sc_mask)
        # padded rows carry garbage the masks must neutralise
        f = jnp.where(pp.dev_mask > 0, f, 2.0)
        scenarios.append(pp)
        allocs.append(Allocation(f=f, P=P, X=X, rho=jnp.float32(0.4 + 0.1 * i)))
        weights.append(Weights(jnp.float32(0.5 + i), jnp.float32(1.0),
                               jnp.float32(1.5)))

    pb = stack_params(scenarios)
    ab = jax.tree.map(lambda *xs: jnp.stack(xs), *allocs)
    wb = stack_weights(weights)
    got = batch_objectives(
        pb, wb, ab, weights_batched=True,
        use_pallas=use_pallas, interpret=use_pallas,
    )
    for i, (pp, alloc, w) in enumerate(zip(scenarios, allocs, weights)):
        want = float(objective(pp, w, alloc))
        np.testing.assert_allclose(float(got[i]), want, rtol=1e-5)


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
def test_candidate_scoring_matches_system_objective(use_pallas):
    """Per-scenario multi-start scoring (`candidate_objectives`) matches a
    python loop of `system.objective` calls — including under vmap, which is
    exactly how `solve_batch` reaches the batched kernel."""
    from repro.core import Weights, sample_params
    from repro.core.allocator import equal_start, low_power_start
    from repro.core.scoring import candidate_objectives
    from repro.core.system import objective
    from repro.core.types import Allocation

    p = sample_params(jax.random.PRNGKey(30), N=4, K=12)
    w = Weights.ones()
    cands = []
    for start, rho in [(equal_start(p), 0.9), (low_power_start(p), 0.5)]:
        f, P, X = start
        cands.append(Allocation(f=f, P=P, X=X, rho=jnp.float32(rho)))
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *cands)
    got = candidate_objectives(
        p, w, stacked, use_pallas=use_pallas, interpret=use_pallas
    )
    want = np.asarray([float(objective(p, w, a)) for a in cands])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_solve_batch_kernel_objective_matches_jnp_objective():
    """Regression for the default `use_kernel_objective` routing: scoring the
    multi-start selection through the batched kernel path picks the identical
    hardened X (and bitwise-identical alloc — selection is all it changes
    post-hardening) as the plain `system.objective` path."""
    from repro.core import AllocatorConfig, Weights, sample_params_batch, solve_batch

    pb = sample_params_batch(jax.random.PRNGKey(40), 4, N=4, K=12)
    w = Weights.ones()
    on = solve_batch(pb, w, AllocatorConfig(inner="pgd"))
    off = solve_batch(
        pb, w, AllocatorConfig(inner="pgd", use_kernel_objective=False)
    )
    np.testing.assert_array_equal(np.asarray(on.alloc.X), np.asarray(off.alloc.X))
    np.testing.assert_array_equal(np.asarray(on.alloc.P), np.asarray(off.alloc.P))
    np.testing.assert_array_equal(
        np.asarray(on.alloc.rho), np.asarray(off.alloc.rho)
    )
    # the trace IS scored differently (kernel vs jnp) — but only to fp noise
    np.testing.assert_allclose(
        np.asarray(on.trace), np.asarray(off.trace), rtol=1e-5
    )


def test_serve_completion_objective_scored_through_kernel():
    """Serving flushes score their padded-bucket batch through the batched
    kernel: `Completion.objective` == `system.objective` of the returned
    exact-shape allocation."""
    from repro.core import Weights, sample_params
    from repro.core.system import objective
    from repro.serve import AllocService, ServeConfig

    svc = AllocService(ServeConfig())
    reqs = [sample_params(jax.random.PRNGKey(50 + i), N=3 + i % 2, K=8)
            for i in range(4)]
    for i, p in enumerate(reqs):
        svc.submit(p, now=0.01 * i)
    done, _ = svc.drain(now=1.0)
    assert len(done) == len(reqs)
    for comp in done:
        p = reqs[comp.req_id]
        want = float(objective(p, Weights.ones(), comp.alloc))
        np.testing.assert_allclose(comp.objective, want, rtol=1e-5)
    # and the switch exists for latency-critical deployments
    svc_off = AllocService(ServeConfig(score_objective=False))
    svc_off.submit(reqs[0], now=0.0)
    done_off, _ = svc_off.drain(now=1.0)
    assert done_off[0].objective is None


def test_exhaustive_padded_scores_like_exact():
    """`solve_exhaustive` through the mask-aware grid on a padded scenario:
    before the fix every candidate tripped the f > f_max check on the padded
    row (padded f_max = 1.0) and scored accuracy with the padded device count,
    so the search returned +inf / wrong values. Masked, the padded best is
    finite and at least as good as the exact-shape best — the padded space is
    a superset (a real subcarrier owned by a padded device == legally
    unassigned, an option the exact owner-per-subcarrier enumeration lacks)."""
    from repro.core import Weights, pad_params, sample_params
    from repro.core.exhaustive import solve_exhaustive

    p = sample_params(jax.random.PRNGKey(10), N=2, K=3)
    pp = pad_params(p, 3, 4)
    grids = (np.array([5e8, 1e9]), np.array([10.0, 17.0]), np.array([0.5, 1.0]))
    exact = solve_exhaustive(p, Weights.ones(), *grids)
    padded = solve_exhaustive(pp, Weights.ones(), *grids)
    assert np.isfinite(float(padded.value))
    assert float(padded.value) <= float(exact.value) + 1e-6
