"""Compile guards for the TPU v5e: the serving path's kernels and executables
compiled for a described (not attached) ``v5e:2x2`` topology.

Interpret-mode Pallas tests cannot see what Mosaic refuses (block shapes whose
last two dims are neither whole nor tile-aligned) or what XLA cannot
partition (a Pallas call under automatic sharding), so these tests run the
chip's own compiler on shapes only. Nothing executes; a compile that passes
here is not a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and a worker that imports this
file without running it must not take it. The persistent compilation cache
is switched off around these compiles (an entry written for a described
chip cannot be read back without one). ``ops`` picks its Pallas branch from
``jax.default_backend()``, which still reports the CPU here, so each test
steers it with monkeypatch.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.core import (
    AllocatorConfig,
    Weights,
    pad_params,
    sample_params,
    stack_params,
    stack_weights,
    tree_index,
)
from repro.core.accuracy import default_accuracy, stack_accuracy
from repro.core.distribute import SCENARIO_AXIS
from repro.core.pgd import PGDConfig
from repro.core.types import Allocation, ShapeBucket
from repro.launch.roofline import collective_bytes
from repro.serve.warmstart import CacheEntry

#: the Table-I request's bucket and the service's default batch slots
BUCKET = ShapeBucket(16, 64)
SLOTS = 8
#: the allocator structure is what compiles; loop lengths do not change it
CFG = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=60))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh(topo):
    return Mesh(np.asarray(topo.devices), (SCENARIO_AXIS,))


@pytest.fixture
def pallas_branch(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _abstract(tree, sharding):
    """Shapes of ``tree`` placed on ``sharding`` (meta fields kept)."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x),
                                       sharding=sharding),
        tree,
    )


def _flush_inputs(slots: int):
    """One Table-I request (N=10, K=50) padded to its bucket, ``slots`` rows,
    as the service stacks a flush."""
    p = pad_params(sample_params(jax.random.PRNGKey(0), N=10, K=50), BUCKET)
    pb = stack_params([p] * slots)
    wb = stack_weights([Weights.ones()] * slots)
    accb = stack_accuracy([default_accuracy()] * slots)
    N, K = BUCKET
    alloc = Allocation(
        f=jnp.zeros((slots, N)), P=jnp.zeros((slots, N, K)),
        X=jnp.zeros((slots, N, K)), rho=jnp.zeros((slots,)),
    )
    return pb, wb, accb, alloc


def _assert_kernel_no_collective(compiled):
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    counts = collective_bytes(text)["counts"]
    assert not any(counts.values()), counts


@pytest.mark.parametrize(
    "B,G,N",
    [
        (1, 1, 16),       # one request
        (3, 1, 16),       # a batch that is not a multiple of 8
        (8, 1, 16),       # the flush shape: 8 slots, one allocation each
        (64, 3645, 3),    # an exhaustive-search chunk (3^3 f x 3^3 p x 5 rho)
    ],
)
def test_objective_batch_kernel_compiles(topo, one_chip, pallas_branch, B, G, N):
    from repro.kernels.fedsem_objective import ops

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    grid = functools.partial(
        ops.objective_grid_batch, xi=1e-28, eta=10.0, check_feasible=True
    )
    compiled = jax.jit(grid).lower(
        f32(B, G, N), f32(B, G, N), f32(B, G, N), f32(B, G),
        *[f32(B, N)] * 6, f32(B), f32(B), f32(B),
        accuracy_ab=(f32(B), f32(B)), dev_mask=f32(B, N),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_objective_grid_kernel_compiles(topo, one_chip):
    from repro.kernels.fedsem_objective import kernel

    N, G = 4, 4096
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    compiled = kernel.objective_grid_pallas.lower(
        f32(N, G), f32(N, G), f32(N, G), f32(G), *[f32(N)] * 7,
        xi=1e-28, eta=10.0, k1=1.0, k2=1.0, k3=1.0, a_acc=0.6356, b_acc=0.4025,
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_score_flush_compiles(topo, one_chip, pallas_branch):
    from repro.serve.service import _score_flush

    args = _abstract(_flush_inputs(SLOTS), one_chip)
    pb, wb, accb, alloc = args
    compiled = _score_flush.lower(pb, wb, alloc, accb).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["solve", "refine"])
def test_sharded_solver_compiles_without_collectives(topo, mesh, pallas_branch,
                                                     program):
    """The sharded cold and warm-refine solvers on four chips: the kernel is
    inside each device's program (shard_map), and no collective joins them."""
    from repro.core.allocator import (
        _solve_batch_impl, sharded_batch_solver, sharded_refine_solver,
    )
    from repro.core.distribute import scenario_sharding
    from repro.serve.warmstart import batch_starts

    scen = scenario_sharding(mesh)
    pb, wb, accb, _ = _flush_inputs(SLOTS)
    if program == "solve":
        args = (*_abstract((pb, wb, accb), scen), CFG, True, True)
        jitted = sharded_batch_solver(mesh, True, True)
    else:
        base = jax.eval_shape(
            functools.partial(_solve_batch_impl, cfg=CFG, weights_batched=True,
                              acc_batched=True),
            pb, wb, accb,
        )
        padded = tree_index(pb, 0)
        extra = batch_starts(
            [CacheEntry(f=np.ones(padded.N, np.float32),
                        P=np.zeros((padded.N, padded.K), np.float32),
                        X=np.zeros((padded.N, padded.K), np.float32),
                        objective=0.0)] + [None] * (SLOTS - 1),
            [padded] * SLOTS,
        )
        args = (*_abstract((pb, wb, accb, extra, base), scen), CFG, True, True)
        jitted = sharded_refine_solver(mesh, True, True)
    _assert_kernel_no_collective(jitted.lower(*args).compile())


def test_sharded_score_flush_compiles_without_collectives(topo, mesh, pallas_branch):
    from repro.core.distribute import scenario_sharding
    from repro.serve.service import sharded_score_flush

    pb, wb, accb, alloc = _abstract(_flush_inputs(SLOTS), scenario_sharding(mesh))
    compiled = sharded_score_flush(mesh).lower(pb, wb, alloc, accb).compile()
    _assert_kernel_no_collective(compiled)
