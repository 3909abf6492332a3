"""Serving subsystem: padded-bucket solves == exact-shape solves, every
admitted request gets a feasible hardened allocation, micro-batching policy,
compiled-executable cache, and the batched-weights validation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AllocatorConfig,
    ShapeBucket,
    Weights,
    bucket_for,
    pad_params,
    sample_params,
    sample_request_stream,
    solve,
    solve_batch,
    stack_params,
    stack_weights,
    tree_index,
    unpad_alloc,
)
from repro.core.allocator import harden_x
from repro.core.p5 import P5Config
from repro.core.pgd import PGDConfig
from repro.core.system import feasible, objective
from repro.serve import AllocService, BatchPolicy, ServeConfig, poisson_arrivals, run_load

W = Weights.ones()
# reduced iteration counts keep compiles/solves test-sized; equivalence holds
# per-config (padded and exact sides always share the config)
PGD_CFG = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=80))
SCA_CFG = AllocatorConfig(inner="sca", outer_iters=2, p5=P5Config(outer_iters=2, inner_iters=40))
SERVE_CFG = ServeConfig(
    policy=BatchPolicy(max_batch=2, max_wait_s=0.01),
    allocator=AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=40)),
)


# ---------------------------------------------------------------------------
# padding / mask helpers
# ---------------------------------------------------------------------------


def test_pad_params_shapes_masks_meta():
    p = sample_params(jax.random.PRNGKey(0), N=3, K=8)
    pp = pad_params(p, 4, 12)
    assert pp.g.shape == (4, 12) and pp.N == 4 and pp.K == 12
    np.testing.assert_array_equal(np.asarray(pp.dev_mask), [1, 1, 1, 0])
    assert np.asarray(pp.sc_mask).sum() == 8 and np.asarray(pp.sc_mask)[8:].sum() == 0
    # real block preserved, padding inert
    np.testing.assert_array_equal(np.asarray(pp.g[:3, :8]), np.asarray(p.g))
    assert float(jnp.abs(pp.g[3:]).max()) == 0.0
    assert float(jnp.abs(pp.C[3:]).max()) == 0.0 and float(jnp.abs(pp.d[3:]).max()) == 0.0
    # per-subcarrier bandwidth is what the rate math sees — preserved exactly
    assert pp.bbar == pytest.approx(p.bbar, rel=1e-12)


def test_pad_params_identity_and_reject_shrink():
    p = sample_params(jax.random.PRNGKey(0), N=4, K=12)
    assert pad_params(p, 4, 12) is p
    with pytest.raises(ValueError, match="shrink"):
        pad_params(p, 3, 12)


def _device_pad(p, n_pad, k_pad):
    """The eager device padding `pad_params` replaced (the reference)."""
    dn, dk = n_pad - p.N, k_pad - p.K
    pn = lambda x, fill=0.0: jnp.pad(x, (0, dn), constant_values=fill)  # noqa: E731
    return dict(
        g=jnp.pad(p.g, ((0, dn), (0, dk))), c=pn(p.c, 1.0), d=pn(p.d), D=pn(p.D),
        C=pn(p.C), p_max=pn(p.p_max, 1.0), f_max=pn(p.f_max, 1.0),
        t_sc_max=pn(p.t_sc_max, 1.0), dev_mask=pn(p.dev_mask),
        sc_mask=jnp.pad(p.sc_mask, (0, dk)),
    )


@pytest.mark.parametrize("leaves", ["device", "host_float32", "host_float64"])
def test_pad_params_host_padding_equals_device_padding(leaves):
    """Host padding gives the eager device padding's arrays bit for bit, on
    the device, whatever the request's arrays are."""
    p = sample_params(jax.random.PRNGKey(4), N=3, K=8)
    if leaves != "device":
        dtype = np.float32 if leaves == "host_float32" else np.float64
        p = jax.tree.map(lambda x: np.asarray(x, dtype), p)
    pp = pad_params(p, 4, 12)
    for name, want in _device_pad(p, 4, 12).items():
        got = getattr(pp, name)
        assert isinstance(got, jax.Array)
        assert (got.dtype, got.shape, got.weak_type) == (want.dtype, want.shape, want.weak_type)
        assert np.asarray(got).tobytes() == np.asarray(want).tobytes()


def test_bucket_for_picks_smallest_fit():
    assert bucket_for(3, 8) == ShapeBucket(4, 8)
    assert bucket_for(4, 12) == ShapeBucket(4, 16)
    assert bucket_for(10, 50) == ShapeBucket(16, 64)
    with pytest.raises(ValueError, match="bucket"):
        bucket_for(1000, 4000)


def test_default_masks_are_ones():
    p = sample_params(jax.random.PRNGKey(1), N=4, K=12)
    assert float(jnp.min(p.dev_mask)) == 1.0 and p.dev_mask.shape == (4,)
    assert float(jnp.min(p.sc_mask)) == 1.0 and p.sc_mask.shape == (12,)


def test_harden_x_masked_ignores_padding():
    key = jax.random.PRNGKey(2)
    X = jax.random.uniform(key, (5, 9))
    dev_mask = jnp.asarray([1.0, 1.0, 1.0, 0.0, 0.0])
    sc_mask = jnp.asarray([1.0] * 6 + [0.0] * 3)
    Xb = np.asarray(harden_x(X * dev_mask[:, None] * sc_mask[None, :], 5, 9, dev_mask, sc_mask))
    # padded rows/columns stay empty; every real device owns >= 1 real sc
    assert Xb[3:].sum() == 0 and Xb[:, 6:].sum() == 0
    assert (Xb[:3, :6].sum(axis=1) >= 1).all()
    assert (Xb.sum(axis=0) <= 1).all()
    # real block identical to hardening the exact-shape problem
    np.testing.assert_array_equal(Xb[:3, :6], np.asarray(harden_x(X[:3, :6], 3, 6)))


# ---------------------------------------------------------------------------
# padded solve == exact solve
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [PGD_CFG, SCA_CFG], ids=["pgd", "sca"])
def test_padded_solve_matches_exact(cfg):
    p = sample_params(jax.random.PRNGKey(0), N=4, K=12)
    pp = pad_params(p, 8, 16)
    ref = jax.jit(lambda q: solve(q, W, cfg))(p)
    pad = jax.jit(lambda q: solve(q, W, cfg))(pp)
    # padded slots get nothing
    assert float(jnp.abs(pad.alloc.P[4:]).max()) == 0.0
    assert float(jnp.abs(pad.alloc.X[:, 12:]).max()) == 0.0
    a = unpad_alloc(pad.alloc, 4, 12)
    # discrete assignment must agree exactly; continuous vars to fp-chaos tol
    np.testing.assert_array_equal(np.asarray(a.X), np.asarray(ref.alloc.X))
    np.testing.assert_allclose(np.asarray(a.rho), np.asarray(ref.alloc.rho), rtol=5e-3)
    np.testing.assert_allclose(np.asarray(a.f), np.asarray(ref.alloc.f), rtol=5e-2)
    np.testing.assert_allclose(
        float(objective(p, W, a)), float(objective(p, W, ref.alloc)), rtol=1e-2
    )
    # the padded scenario's own objective sees the same value (masked accuracy
    # term, inert padding) — the bucket does not distort the decision problem
    np.testing.assert_allclose(
        float(objective(pp, W, pad.alloc)), float(objective(p, W, a)), rtol=1e-5
    )
    assert bool(feasible(p, a))


def test_padded_mixed_batch_all_feasible():
    scenarios = sample_request_stream(
        jax.random.PRNGKey(3), 4, sizes=((3, 8), (4, 8))
    )
    padded = [pad_params(s, 4, 8) for s in scenarios]
    res = solve_batch(stack_params(padded), W, PGD_CFG)
    for i, s in enumerate(scenarios):
        a = unpad_alloc(tree_index(res.alloc, i), s.N, s.K)
        assert bool(feasible(s, a)), f"scenario {i} infeasible"


# ---------------------------------------------------------------------------
# service: admission, micro-batching, cache, metrics
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def load_run():
    requests = sample_request_stream(
        jax.random.PRNGKey(7), 6, sizes=((3, 8), (4, 8))
    )
    service = AllocService(SERVE_CFG)
    arrivals = poisson_arrivals(jax.random.PRNGKey(8), len(requests), rate_hz=200.0)
    result = run_load(service, requests, arrivals)
    return requests, service, result


def test_service_answers_every_request_feasibly(load_run):
    requests, _, result = load_run
    assert len(result.completions) == len(requests)
    assert sorted(c.req_id for c in result.completions) == list(range(len(requests)))
    for c in result.completions:
        p = requests[c.req_id]
        assert c.alloc.P.shape == (p.N, p.K)         # exact shape back
        assert bool(feasible(p, c.alloc)), f"request {c.req_id} infeasible"
        # hardened: binary X, every device serviced
        X = np.asarray(c.alloc.X)
        assert set(np.unique(X)).issubset({0.0, 1.0})
        assert (X.sum(axis=1) >= 1).all()


def test_service_metrics(load_run):
    _, service, result = load_run
    s = result.summary
    assert s["completed"] == s["requests"] == 6
    assert s["latency_p95_s"] >= s["latency_p50_s"] > 0
    assert 0 < s["batch_occupancy_mean"] <= 1
    assert s["queue_depth_max"] >= 1
    assert result.throughput_rps > 0
    # both sizes share the (4, 8) bucket -> exactly one compiled executable
    assert s["cache_misses"] == 1
    assert s["cache_hits"] == s["batches"] - 1
    assert len(service.executables) == 1


def test_flush_on_max_batch():
    service = AllocService(SERVE_CFG)
    p = sample_params(jax.random.PRNGKey(0), N=4, K=8)
    service.submit(p, now=0.0)
    assert service.pending() == 1
    done, _ = service.flush_full(now=0.0)
    assert done == [] and service.pending() == 1     # not full yet
    service.submit(p, now=0.001)
    done, _ = service.flush_full(now=0.001)          # max_batch=2 reached
    assert len(done) == 2 and service.pending() == 0
    assert done[0].wait_s == pytest.approx(0.001)


def test_flush_on_max_wait():
    service = AllocService(SERVE_CFG)
    p = sample_params(jax.random.PRNGKey(0), N=4, K=8)
    service.submit(p, now=0.0)
    assert service.next_deadline() == pytest.approx(0.01)
    done, _ = service.flush_due(now=0.005)
    assert done == []                                # not due yet
    done, _ = service.flush_due(now=0.01)            # max_wait_s hit
    assert len(done) == 1
    assert done[0].latency_s >= 0.01                 # waited + solve time


def test_per_request_weights_respected():
    # a request served in the same batch with different weights must see its
    # own objective trade-off: huge kappa3 pushes rho to ~1
    p = sample_params(jax.random.PRNGKey(11), N=4, K=8)
    service = AllocService(SERVE_CFG)
    service.submit(p, Weights(jnp.float32(1.0), jnp.float32(1.0), jnp.float32(0.0)), now=0.0)
    service.submit(p, Weights(jnp.float32(1.0), jnp.float32(1.0), jnp.float32(500.0)), now=0.0)
    (c_lo, c_hi), _ = service.flush_full(now=0.0)
    assert float(c_hi.alloc.rho) >= float(c_lo.alloc.rho)
    assert float(c_hi.alloc.rho) > 0.99


def test_shared_cache_keyed_by_allocator_config():
    """A shared executables dict must never serve config A's solver to a
    service running config B (the cache key includes AllocatorConfig)."""
    p = sample_params(jax.random.PRNGKey(0), N=4, K=8)
    a = AllocService(SERVE_CFG)
    a.warmup([p])
    assert a.metrics.cache_misses == 1
    other = SERVE_CFG._replace(
        allocator=AllocatorConfig(inner="pgd", outer_iters=1, pgd=PGDConfig(steps=20))
    )
    b = AllocService(other, executables=a.executables)
    b.warmup([p])
    assert b.metrics.cache_misses == 1      # same bucket/slots, different cfg
    assert len(a.executables) == 2          # both entries live in the shared dict


def test_same_bbar_different_k_share_bucket():
    """Requests built from one bbar with different K must co-batch: the
    service canonicalises the padded B, so fp round-trip drift (bbar*12/12*16
    vs bbar*16) cannot split the bucket queue (regression)."""
    bbar = 8357815.274094777            # reproduces a 1-ulp B split unrounded
    p12 = sample_params(jax.random.PRNGKey(0), N=4, K=12, B=bbar * 12)
    p16 = sample_params(jax.random.PRNGKey(1), N=4, K=16, B=bbar * 16)
    service = AllocService(SERVE_CFG)
    k1 = service._bucket_key(service._pad(p12))
    k2 = service._bucket_key(service._pad(p16))
    assert k1 == k2
    service.submit(p12, now=0.0)
    service.submit(p16, now=0.0)
    done, _ = service.flush_full(now=0.0)   # max_batch=2: only fires co-bucketed
    assert len(done) == 2
    for c, p in zip(done, (p12, p16)):
        assert bool(feasible(p, c.alloc))


def test_exact_mode_canonicalises_b_ulp_split():
    """Regression: exact-shape mode (``buckets=None``) skipped the B
    canonicalisation, so two equal-bbar requests whose B was reconstructed
    through different float round-trips (1 ulp apart) landed in different
    queues — neither bucket ever filled, and had they shared a key,
    `stack_params` would have rejected mixing them. Both modes now
    canonicalise at `_pad`."""
    bbar = 84457742.9673523       # bbar * 12 != sum([bbar] * 12): 1 ulp apart
    b_mul, b_sum = bbar * 12, sum([bbar] * 12)
    assert b_mul != b_sum
    pa = sample_params(jax.random.PRNGKey(0), N=4, K=12, B=b_mul)
    pb = sample_params(jax.random.PRNGKey(1), N=4, K=12, B=b_sum)
    service = AllocService(SERVE_CFG._replace(buckets=None))
    assert service._bucket_key(service._pad(pa)) == service._bucket_key(service._pad(pb))
    service.submit(pa, now=0.0)
    service.submit(pb, now=0.0)
    done, _ = service.flush_full(now=0.0)    # max_batch=2: only fires co-queued
    assert len(done) == 2
    for c, p in zip(done, (pa, pb)):
        assert c.alloc.P.shape == (4, 12)
        assert bool(feasible(p, c.alloc))


# ---------------------------------------------------------------------------
# serving-loop correctness regressions (PR 5 satellites)
# ---------------------------------------------------------------------------


def test_arrival_tied_with_deadline_joins_the_flush():
    """Regression: the loadgen's deadline branch used to flush BEFORE
    admitting an arrival with t_arr == deadline, violating the documented
    invariant (everything with t_arr <= clock is queued before any flush
    decision at clock). The tied arrival must ride the due flush's batch."""
    p = sample_params(jax.random.PRNGKey(0), N=4, K=8)
    service = AllocService(SERVE_CFG)    # max_batch=2, max_wait_s=0.01
    service.warmup([p])
    # second arrival lands EXACTLY on the first request's bucket deadline
    result = run_load(service, [p, p], arrivals=[0.0, 0.01])
    assert len(result.completions) == 2
    # one batch of two: the tied arrival was admitted first, filling the
    # bucket (pre-fix: two solo flushes, batches == 2, occupancy 0.5)
    assert result.summary["batches"] == 1
    assert result.summary["mean_batch_size"] == 2.0
    waits = {c.req_id: c.wait_s for c in result.completions}
    assert waits[0] == pytest.approx(0.01)   # waited out max_wait_s
    assert waits[1] == pytest.approx(0.0)    # flushed on arrival


def test_run_load_validates_weights_length():
    """Regression: a short weights list used to IndexError mid-run; it must
    fail at admission."""
    p = sample_params(jax.random.PRNGKey(0), N=4, K=8)
    service = AllocService(SERVE_CFG)
    with pytest.raises(ValueError, match="weights \\(1\\)"):
        run_load(service, [p, p], arrivals=[0.0, 0.0], weights=[Weights.ones()])


def test_warmup_has_no_dead_now_param():
    """Regression: warmup() accepted (and ignored) a ``now`` timestamp."""
    import inspect

    assert "now" not in inspect.signature(AllocService.warmup).parameters


def test_metrics_reservoirs_are_bounded():
    """Regression: ServiceMetrics grew unbounded python lists — a leak under
    the indefinitely-running real-clock driver. Reservoirs cap retained
    samples while count/mean/max stay exact."""
    from repro.serve import Reservoir, ServiceMetrics

    r = Reservoir(cap=64, seed=0)
    for i in range(1000):
        r.add(float(i))
    assert len(r.sample) == 64              # bounded retention
    assert r.count == len(r) == 1000        # exact count
    assert r.mean() == pytest.approx(499.5)  # exact running mean
    assert r.max() == 999.0                 # exact running max
    assert 0.0 <= r.percentile(50.0) <= 999.0

    # below the cap the reservoir is exact, including percentiles
    small = Reservoir(cap=64)
    for i in range(10):
        small.add(float(i))
    assert small.sample == [float(i) for i in range(10)]
    assert small.percentile(100.0) == 9.0

    m = ServiceMetrics()
    for i in range(10_000):
        m.observe_submit(depth=i)
        m.observe_completion(latency_s=1.0, wait_s=0.5)
    for reservoir in (m.queue_depth, m.latencies_s, m.waits_s):
        assert len(reservoir.sample) <= reservoir.cap
    s = m.summary()                          # schema unchanged, values sane
    assert s["requests"] == s["completed"] == 10_000
    assert s["queue_depth_max"] == 9_999 and isinstance(s["queue_depth_max"], int)
    assert s["latency_p50_s"] == 1.0 and s["wait_p50_s"] == 0.5


def test_service_prepare_admit_round_trip():
    """The driver-facing split of submit(): prepare is pure (no queue state),
    admit stamps id/arrival and enqueues — together == submit."""
    p = sample_params(jax.random.PRNGKey(0), N=3, K=8)
    service = AllocService(SERVE_CFG)
    prepared = service.prepare(p)
    assert service.pending() == 0            # prepare touched no queue
    assert prepared.padded.N == 4 and prepared.padded.K == 8
    rid = service.admit(prepared, now=1.5)
    assert rid == 0 and service.pending() == 1
    assert prepared.arrival_t == 1.5
    assert service.next_deadline() == pytest.approx(1.5 + SERVE_CFG.policy.max_wait_s)


def test_set_buckets_mid_stream_keeps_queued_requests():
    """A ladder refit between admissions must not strand queued requests:
    they flush in the bucket they were admitted into."""
    from repro.serve import learn_buckets

    p = sample_params(jax.random.PRNGKey(0), N=3, K=8)
    service = AllocService(SERVE_CFG)
    service.submit(p, now=0.0)               # padded into DEFAULT (4, 8)
    service.set_buckets(learn_buckets({(3, 8): 1}))
    service.submit(p, now=0.0)               # padded into learned (3, 8)
    done, _ = service.drain(now=0.0)
    assert sorted(c.bucket for c in done) == [(3, 8), (4, 8)]
    for c in done:
        assert c.alloc.P.shape == (3, 8)
        assert bool(feasible(p, c.alloc))


# ---------------------------------------------------------------------------
# solve_batch weights validation (satellite)
# ---------------------------------------------------------------------------


def test_weights_batched_rejects_scalar_weights():
    pb = stack_params([sample_params(jax.random.PRNGKey(0), N=4, K=8)] * 3)
    with pytest.raises(ValueError, match="leading batch axis"):
        solve_batch(pb, Weights.ones(), PGD_CFG, weights_batched=True)


def test_weights_batched_rejects_wrong_batch():
    pb = stack_params([sample_params(jax.random.PRNGKey(0), N=4, K=8)] * 3)
    wb = stack_weights([Weights.ones()] * 2)
    with pytest.raises(ValueError, match="size B=3"):
        solve_batch(pb, wb, PGD_CFG, weights_batched=True)


def test_weights_batched_matches_per_scenario():
    p = sample_params(jax.random.PRNGKey(1), N=4, K=8)
    ws = [
        Weights(jnp.float32(1.0), jnp.float32(1.0), jnp.float32(1.0)),
        Weights(jnp.float32(4.0), jnp.float32(1.0), jnp.float32(1.0)),
    ]
    pb = stack_params([p, p])
    wb = stack_weights(ws)
    res = solve_batch(pb, wb, PGD_CFG, weights_batched=True)
    solve_jit = jax.jit(lambda w: solve(p, w, PGD_CFG))
    for i, w in enumerate(ws):
        ref = solve_jit(w)
        np.testing.assert_array_equal(
            np.asarray(tree_index(res.alloc.X, i)), np.asarray(ref.alloc.X)
        )
        np.testing.assert_allclose(
            np.asarray(tree_index(res.alloc.rho, i)), np.asarray(ref.alloc.rho),
            rtol=1e-4,
        )
