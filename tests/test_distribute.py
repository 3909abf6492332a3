"""Scenario-parallel sharding: sharded `solve_batch` == single-device
`solve_batch` (exact hardened X, aggregate rho/objective tolerances), batch
padding for non-divisible meshes, and the sharded serving path.

Runs on the conftest's forced host devices (4 locally; CI adds a step under
``XLA_FLAGS=--xla_force_host_platform_device_count=8``), so the sharded
executable really partitions over multiple devices on CPU.

Tolerance contract (same as the padded-solve tests): the hardened discrete
assignment must match EXACTLY; continuous leaves are compared through
aggregate rho/objective, never per-entry P — fp reduction reordering across
device partitions enters at denormal scale and Adam amplifies it.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AllocatorConfig,
    Weights,
    pad_batch,
    sample_params,
    sample_params_batch,
    scenario_mesh,
    shard_batch,
    slice_batch,
    solve_batch,
    stack_weights,
    tree_index,
)
from repro.core.distribute import SCENARIO_AXIS, round_up, scenario_sharding
from repro.core.pgd import PGDConfig
from repro.core.system import feasible, objective
from repro.serve import AllocService, BatchPolicy, ServeConfig

W = Weights.ones()
CFG = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=60))


def _assert_batches_equivalent(params_batch, got, ref, weights=None):
    """Exact hardened X; rho and per-scenario objective to fp-chaos tol."""
    np.testing.assert_array_equal(np.asarray(got.alloc.X), np.asarray(ref.alloc.X))
    np.testing.assert_allclose(
        np.asarray(got.alloc.rho), np.asarray(ref.alloc.rho), rtol=5e-3
    )
    b = got.alloc.rho.shape[0]
    for i in range(b):
        p = tree_index(params_batch, i)
        w = tree_index(weights, i) if weights is not None else W
        np.testing.assert_allclose(
            float(objective(p, w, tree_index(got.alloc, i))),
            float(objective(p, w, tree_index(ref.alloc, i))),
            rtol=1e-2,
        )


def test_scenario_mesh_covers_local_devices():
    mesh = scenario_mesh()
    assert mesh.size == jax.device_count() > 1  # conftest forces >= 4
    assert mesh.axis_names == (SCENARIO_AXIS,)


def test_shard_batch_splits_leading_axis():
    mesh = scenario_mesh()
    pb = sample_params_batch(jax.random.PRNGKey(0), mesh.size * 2, N=4, K=8)
    sharded = shard_batch(pb, mesh)
    assert sharded.g.sharding == scenario_sharding(mesh)
    # each device holds B/device_count scenarios, whole on trailing axes
    shard_shapes = {s.data.shape for s in sharded.g.addressable_shards}
    assert shard_shapes == {(2, 4, 8)}


def test_pad_slice_batch_roundtrip():
    pb = sample_params_batch(jax.random.PRNGKey(1), 3, N=4, K=8)
    padded = pad_batch(pb, 8)
    assert padded.g.shape == (8, 4, 8)
    # tail replicas of the last scenario, real block untouched
    np.testing.assert_array_equal(np.asarray(padded.g[:3]), np.asarray(pb.g))
    np.testing.assert_array_equal(np.asarray(padded.g[7]), np.asarray(pb.g[2]))
    back = slice_batch(padded, 3)
    np.testing.assert_array_equal(np.asarray(back.g), np.asarray(pb.g))
    with pytest.raises(ValueError, match="shrink"):
        pad_batch(pb, 2)


def test_sharded_solve_batch_matches_single_device():
    mesh = scenario_mesh()
    pb = sample_params_batch(jax.random.PRNGKey(2), mesh.size * 2, N=4, K=8)
    ref = solve_batch(pb, W, CFG)
    got = solve_batch(pb, W, CFG, mesh=mesh)
    _assert_batches_equivalent(pb, got, ref)
    for i in range(pb.g.shape[0]):
        assert bool(feasible(tree_index(pb, i), tree_index(got.alloc, i)))


def test_sharded_solve_batch_result_spans_the_mesh():
    """Each device keeps its own slice of the answer: the result is split
    over every device of the mesh, not gathered onto the first."""
    mesh = scenario_mesh()
    pb = sample_params_batch(jax.random.PRNGKey(2), mesh.size * 2, N=4, K=8)
    got = solve_batch(pb, W, CFG, mesh=mesh)
    for leaf in jax.tree.leaves(got):
        homes = {s.device for s in leaf.addressable_shards}
        assert homes == set(mesh.devices.flat)
        assert {s.data.shape[0] for s in leaf.addressable_shards} == {2}


def test_sharded_solve_batch_pads_non_divisible():
    mesh = scenario_mesh()
    b = mesh.size + 1                        # forces the pad/slice path
    pb = sample_params_batch(jax.random.PRNGKey(3), b, N=4, K=8)
    got = solve_batch(pb, W, CFG, mesh=mesh)
    assert got.alloc.rho.shape == (b,)       # sliced back to the real batch
    ref = solve_batch(pb, W, CFG)
    _assert_batches_equivalent(pb, got, ref)


def test_sharded_weights_batched():
    mesh = scenario_mesh()
    p = sample_params(jax.random.PRNGKey(4), N=4, K=8)
    ws = [
        Weights(jnp.float32(1.0 + i), jnp.float32(1.0), jnp.float32(1.0))
        for i in range(mesh.size)
    ]
    pb = jax.tree.map(lambda x: jnp.stack([x] * mesh.size), p)
    wb = stack_weights(ws)
    ref = solve_batch(pb, wb, CFG, weights_batched=True)
    got = solve_batch(pb, wb, CFG, weights_batched=True, mesh=mesh)
    _assert_batches_equivalent(pb, got, ref, weights=wb)


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------

SHARD_SERVE = ServeConfig(
    policy=BatchPolicy(max_batch=2, max_wait_s=0.01),
    allocator=AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=40)),
    shard_batch=True,
)


def test_sharded_service_slots_and_cache():
    """shard_batch sizes bucket slots to device_count x max_batch, and the
    executable cache keys on the mesh (a shared dict must never hand a
    single-device program to a sharded service or vice versa)."""
    n_dev = jax.device_count()
    sharded = AllocService(SHARD_SERVE)
    assert sharded.mesh is not None and sharded.mesh.size == n_dev
    assert sharded._full_slots == 2 * n_dev
    assert sharded.batcher.policy.max_batch == 2 * n_dev
    p = sample_params(jax.random.PRNGKey(5), N=4, K=8)
    sharded.warmup([p])
    assert sharded.metrics.cache_misses == 1
    single = AllocService(
        SHARD_SERVE._replace(shard_batch=False), executables=sharded.executables
    )
    single.warmup([p])
    assert single.metrics.cache_misses == 1     # same bucket/cfg, no mesh -> miss
    assert len(sharded.executables) == 2


def test_sharded_service_matches_unsharded():
    """The same requests answered by a sharded and an unsharded service get
    identical hardened assignments (the batch axis split is invisible)."""
    requests = [sample_params(jax.random.PRNGKey(10 + i), N=4, K=8) for i in range(3)]
    results = {}
    for name, shard in (("sharded", True), ("single", False)):
        service = AllocService(SHARD_SERVE._replace(shard_batch=shard))
        for i, p in enumerate(requests):
            service.submit(p, now=0.0)
        done, _ = service.drain(now=0.0)
        results[name] = {c.req_id: c.alloc for c in done}
    assert sorted(results["sharded"]) == sorted(results["single"]) == [0, 1, 2]
    for rid, p in enumerate(requests):
        a, b = results["sharded"][rid], results["single"][rid]
        np.testing.assert_array_equal(np.asarray(a.X), np.asarray(b.X))
        np.testing.assert_allclose(
            float(objective(p, W, a)), float(objective(p, W, b)), rtol=1e-2
        )
        assert bool(feasible(p, a))
