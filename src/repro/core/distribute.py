"""Scenario-parallel sharding for the batched allocator (ROADMAP item 1).

`solve_batch` vmaps Alg. A2 over a leading scenario axis and the per-scenario
solves never talk to each other — the batch is embarrassingly parallel. This
module builds a 1-D ``jax.sharding.Mesh`` over the local devices (axis name
``"scenario"``, the `launch/mesh.py` pattern: functions, never module-level
device state), the `NamedSharding`s that split that leading axis, and
`scenario_map`, which maps a batch function over the mesh with
``jax.shard_map`` — so B scenarios compile into ONE executable in which each
device solves its own B/device_count slice, Pallas kernels included, with
zero cross-device communication. (XLA's automatic partitioner cannot split a
Pallas call, so a plain ``jit`` with in/out shardings does not compile on a
TPU; the explicit per-device body does.)

Everything works on CPU under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the `launch/dryrun.py`
trick), which is how CI exercises the sharded path without an accelerator.

Equivalence guarantee (asserted in `tests/test_distribute.py`): a sharded
`solve_batch(..., mesh=...)` returns the *same hardened assignment* X as the
single-device solve for every scenario — the device split is invisible to
callers; continuous leaves (P, f, rho, trace) agree to float32 round-off.
Non-divisible batches are padded by replicating the tail scenario
(`pad_batch`) and sliced back (`slice_batch`) — exact, because the
per-scenario solves are independent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

#: Mesh axis the batch (leading) dimension of stacked scenario pytrees lives on.
SCENARIO_AXIS = "scenario"


def scenario_mesh(devices=None) -> Mesh:
    """1-D mesh over ``devices`` (default: all local devices)."""
    devices = jax.devices() if devices is None else list(devices)
    return Mesh(np.asarray(devices), (SCENARIO_AXIS,))


def scenario_sharding(mesh: Mesh) -> NamedSharding:
    """Split the leading (scenario) axis across the mesh; trailing axes whole.

    This is the only sharding the batched allocator ever uses: applied to the
    in/out leaves of `sharded_batch_solver`, it partitions `solve_batch` into
    B/mesh.size independent per-device solves with zero cross-device
    communication (scenarios never interact), which is why sharded results
    match single-device results exactly on the hardened X.
    """
    return NamedSharding(mesh, PartitionSpec(SCENARIO_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully replicated placement on the mesh (broadcast weights, accuracy fit)."""
    return NamedSharding(mesh, PartitionSpec())


def scenario_map(fn, mesh: Mesh, split: tuple[bool, ...]):
    """``jax.shard_map`` of a batch function over the scenario axis.

    ``split[i]`` says whether positional argument ``i`` carries the leading
    scenario axis (split over the mesh) or is one value for every scenario
    (replicated: broadcast weights, one accuracy fit). Every output is split
    on its leading axis. Each device runs ``fn`` on its own B/mesh.size
    slice, so the per-scenario rows never meet and the program holds no
    collective — the exact-hardened-X guarantee above.
    """
    in_specs = tuple(
        PartitionSpec(SCENARIO_AXIS) if s else PartitionSpec() for s in split
    )
    # check_vma off: the solver's loops start their carries from constants
    # (bisection brackets, Adam moments) that only become per-device inside
    # the loop, which the varying-axes type check rejects; nothing here is
    # ever reduced across devices, so there is nothing for it to protect
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs,
        out_specs=PartitionSpec(SCENARIO_AXIS), check_vma=False,
    )


def round_up(b: int, multiple: int) -> int:
    """Smallest multiple of ``multiple`` that is >= ``b``."""
    return -(-b // multiple) * multiple


def pad_batch(tree, to_size: int):
    """Pad every leaf's leading axis to ``to_size`` by replicating the tail.

    The per-scenario solves are independent, so tail replicas are exact
    throwaway work: slice the result back with `slice_batch`. Used to make a
    batch divisible by the mesh size before sharding.
    """

    def leaf(x):
        b = x.shape[0]
        if b == to_size:
            return x
        if b > to_size:
            raise ValueError(f"pad_batch cannot shrink: batch {b} > {to_size}")
        reps = jnp.broadcast_to(x[-1:], (to_size - b,) + x.shape[1:])
        return jnp.concatenate([x, reps], axis=0)

    return jax.tree.map(leaf, tree)


def slice_batch(tree, b: int):
    """Undo `pad_batch`: keep the first ``b`` entries of every leaf."""
    return jax.tree.map(lambda x: x[:b], tree)


def shard_batch(tree, mesh: Mesh):
    """Place a batch-stacked pytree with its leading axis split on the mesh.

    Every data leaf must carry the batch axis (the `stack_params` /
    `stack_weights` contract) with size divisible by ``mesh.size``.
    """
    return jax.device_put(tree, scenario_sharding(mesh))
