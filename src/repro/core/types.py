"""Pytree dataclasses for the FedSem wireless system (paper Table I).

Everything is a registered JAX pytree so the whole allocator jits and vmaps
over batches of channel realisations.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# unit helpers
# ---------------------------------------------------------------------------


def dbm_to_watt(dbm):
    return 10.0 ** ((jnp.asarray(dbm, jnp.float32) - 30.0) / 10.0)


def db_to_linear(db):
    return 10.0 ** (jnp.asarray(db, jnp.float32) / 10.0)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=[
        "g", "c", "d", "D", "C", "p_max", "f_max", "t_sc_max",
        "dev_mask", "sc_mask",
    ],
    meta_fields=["N", "K", "B", "N0", "xi", "eta", "q"],
)
@dataclasses.dataclass(frozen=True)
class SystemParams:
    """Static description of one FedSem wireless scenario.

    Shapes: ``g`` is (N, K) channel gain (linear); ``c, d, D, C, p_max,
    f_max, t_sc_max`` are (N,).

    ``dev_mask`` (N,) / ``sc_mask`` (K,) are {0,1} validity masks used by the
    serving layer's shape buckets (`pad_params`): real devices/subcarriers
    occupy the *leading* indices, padded ones carry mask 0 and must not
    perturb the objective or the hardened allocation. Defaults to all-ones
    (every entry real), so the masks are invisible outside padded solves.

    Meta (python scalars, hashable for jit):
      N devices, K subcarriers, B total bandwidth [Hz], N0 noise PSD [W/Hz],
      xi effective switched capacitance, eta local iterations,
      q binary-tightening exponent of (35a).
    """

    g: jax.Array
    c: jax.Array        # CPU cycles / sample
    d: jax.Array        # samples per device
    D: jax.Array        # FL upload size [bits]
    C: jax.Array        # total SemCom payload L * C_{n,l} [bits]
    p_max: jax.Array    # [W]
    f_max: jax.Array    # [Hz]
    t_sc_max: jax.Array  # SemCom deadline [s]
    dev_mask: jax.Array | None = None   # (N,) 1 = real device, 0 = padding
    sc_mask: jax.Array | None = None    # (K,) 1 = real subcarrier, 0 = padding
    N: int = 10
    K: int = 50
    B: float = 20e6
    N0: float = 10.0 ** ((-174.0 - 30.0) / 10.0)
    xi: float = 1e-28
    eta: int = 10
    q: int = 2

    def __post_init__(self):
        if self.dev_mask is None:
            object.__setattr__(self, "dev_mask", jnp.ones((self.N,), jnp.float32))
        if self.sc_mask is None:
            object.__setattr__(self, "sc_mask", jnp.ones((self.K,), jnp.float32))
        # Constraint (13d) allocates each subcarrier to at most one device and
        # the allocator guarantees >= 1 subcarrier per device after hardening
        # (`harden_x`) — both are only satisfiable when K >= N. Validate here
        # (meta fields are python ints, so this is jit/vmap-safe) instead of
        # letting `equal_start` silently leave devices with no subcarriers.
        if self.K < self.N:
            raise ValueError(
                f"SystemParams requires K >= N (each of the N={self.N} devices "
                f"needs at least one of the K={self.K} subcarriers to satisfy "
                "the rate floor); got K < N"
            )

    @property
    def bbar(self) -> float:
        """Per-subcarrier bandwidth B/K [Hz]."""
        return self.B / self.K

    @property
    def noise_sc(self) -> float:
        """Noise power per subcarrier N0 * Bbar [W]."""
        return self.N0 * self.bbar


def stack_params(params_list) -> "SystemParams":
    """Stack SystemParams pytrees over a new leading batch axis.

    All scenarios must share the meta fields (N, K, B, N0, xi, eta, q) —
    those are static under jit, so a batch is one compiled program. Shapes
    become ``g: (B, N, K)`` and ``(B, N)`` for the per-device vectors.
    """
    params_list = list(params_list)
    if not params_list:
        raise ValueError("stack_params needs at least one SystemParams")
    ref = params_list[0]
    meta = ("N", "K", "B", "N0", "xi", "eta", "q")
    for i, p in enumerate(params_list[1:], start=1):
        bad = [f for f in meta if getattr(p, f) != getattr(ref, f)]
        if bad:
            raise ValueError(
                f"stack_params: scenario {i} differs from scenario 0 in static "
                f"field(s) {bad}; batched solves require identical meta"
            )
    return jax.tree.map(lambda *xs: jnp.stack(xs), *params_list)


def tree_index(tree, i):
    """Select scenario ``i`` from a batch-stacked pytree (inverse of stack)."""
    return jax.tree.map(lambda x: x[i], tree)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["kappa1", "kappa2", "kappa3"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class Weights:
    """Objective weights (kappa1 [1/J], kappa2 [1/s], kappa3 [unitless])."""

    kappa1: jax.Array
    kappa2: jax.Array
    kappa3: jax.Array

    @staticmethod
    def ones() -> "Weights":
        one = jnp.float32(1.0)
        return Weights(one, one, one)


def stack_weights(weights_list) -> "Weights":
    """Stack per-scenario `Weights` over a new leading batch axis.

    The result feeds ``solve_batch(..., weights_batched=True)`` (sibling of
    `stack_params` for the weights pytree).
    """
    weights_list = list(weights_list)
    if not weights_list:
        raise ValueError("stack_weights needs at least one Weights")
    return jax.tree.map(lambda *xs: jnp.stack(xs), *weights_list)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=["f", "P", "X", "rho"],
    meta_fields=[],
)
@dataclasses.dataclass(frozen=True)
class Allocation:
    """Decision variables of problem P1.

    f: (N,) CPU frequency [Hz]; P: (N, K) transmit power [W];
    X: (N, K) subcarrier indicator (relaxed in [0,1] inside the solver,
    ~binary at the end); rho: scalar compression rate in (0, 1].
    """

    f: jax.Array
    P: jax.Array
    X: jax.Array
    rho: jax.Array


# ---------------------------------------------------------------------------
# shape buckets — the serving layer's padding contract
# ---------------------------------------------------------------------------


class ShapeBucket(NamedTuple):
    """Canonical padded (N, K) shape: every scenario padded into the same
    bucket shares one compiled solver program (the serving layer's unit of
    batching). Buckets must satisfy K >= N (same constraint as the scenarios
    they hold).

    Equivalence guarantee (asserted in `tests/test_serve_alloc.py`): solving
    a `pad_params`-padded scenario yields the same hardened assignment as
    solving the exact-shape scenario — padding affects shapes, never answers
    (see `pad_params` for the mask/bandwidth invariants that make this hold).
    """

    N: int
    K: int

    @property
    def area(self) -> int:
        """Padded problem area N*K — the cost proxy the serving layer's
        bucket ladders minimise (solve time scales with the padded shape,
        not the real one)."""
        return self.N * self.K

    def fits(self, n: int, k: int) -> bool:
        """Whether an (n, k) scenario can pad into this bucket."""
        return self.N >= n and self.K >= k


#: Default bucket ladder for the serving layer: a coarse geometric grid so a
#: handful of compiled programs covers everything from toy scenarios to the
#: paper's (10, 50) and beyond. ~2x area steps keep worst-case padding waste
#: bounded while keeping the executable cache small. `repro.serve.ladder`
#: learns a replacement ladder fitted to an observed shape mix.
DEFAULT_BUCKETS = (
    ShapeBucket(4, 8),
    ShapeBucket(4, 16),
    ShapeBucket(8, 16),
    ShapeBucket(8, 32),
    ShapeBucket(16, 64),
    ShapeBucket(32, 128),
    ShapeBucket(64, 256),
)


def bucket_for(n: int, k: int, buckets=DEFAULT_BUCKETS) -> ShapeBucket:
    """Smallest bucket (by padded area N*K) that fits an (n, k) scenario."""
    fits = [b for b in buckets if b.fits(n, k)]
    if not fits:
        raise ValueError(
            f"no bucket in {tuple(buckets)} fits a scenario with N={n}, K={k}; "
            "extend the bucket ladder"
        )
    return min(fits, key=lambda b: (b.area, b.N))


def pad_params(params: SystemParams, n_pad: int, k_pad: int | None = None) -> SystemParams:
    """Pad a scenario to a canonical (n_pad, k_pad) bucket with validity masks.

    Accepts ``pad_params(params, bucket)`` or ``pad_params(params, N, K)``.
    Real devices/subcarriers stay at the leading indices. Padded entries are
    inert by construction: zero channel gain, zero data/payload (``d = D =
    C = 0``) so every energy/delay term vanishes, and ``dev_mask``/``sc_mask``
    zero so the mask-aware pieces of the solver (accuracy sums, warm starts,
    `harden_x`, the PGD softmax) ignore them. ``B`` is rescaled so the
    per-subcarrier bandwidth ``bbar = B/K`` — the only way bandwidth enters
    the rate math — is preserved exactly; a padded solve therefore matches
    the exact-shape solve on the real block (asserted in tests).

    The padding runs on the host (numpy) and the padded arrays reach the
    device in one `jax.device_put`. Eager device padding (a program per
    array) can wait behind the long programs another thread keeps the device
    busy with: on a TPU v5e, beside back-to-back 167 ms programs, it took
    74 ms median against 8.6 ms alone.
    """
    if k_pad is None:
        n_pad, k_pad = n_pad  # a ShapeBucket / (N, K) tuple
    if n_pad < params.N or k_pad < params.K:
        raise ValueError(
            f"pad_params cannot shrink: scenario is (N={params.N}, K={params.K}), "
            f"requested bucket ({n_pad}, {k_pad})"
        )
    if n_pad == params.N and k_pad == params.K:
        return params
    dn, dk = n_pad - params.N, k_pad - params.K

    def pad_n(x, fill=0.0):
        return np.pad(np.asarray(x), (0, dn), constant_values=fill)

    arrays = dict(
        g=np.pad(np.asarray(params.g), ((0, dn), (0, dk))),
        c=pad_n(params.c, 1.0),          # value irrelevant: d = 0 zeroes comp terms
        d=pad_n(params.d),
        D=pad_n(params.D),
        C=pad_n(params.C),
        p_max=pad_n(params.p_max, 1.0),  # positive: avoids 0-division in solvers
        f_max=pad_n(params.f_max, 1.0),
        t_sc_max=pad_n(params.t_sc_max, 1.0),
        dev_mask=pad_n(params.dev_mask),
        sc_mask=np.pad(np.asarray(params.sc_mask), (0, dk)),
    )
    return SystemParams(
        **jax.device_put(arrays),
        N=n_pad,
        K=k_pad,
        B=params.bbar * k_pad,           # preserve bbar = B/K exactly
        N0=params.N0,
        xi=params.xi,
        eta=params.eta,
        q=params.q,
    )


def unpad_alloc(alloc: Allocation, n: int, k: int) -> Allocation:
    """Slice the real (n, k) block back out of a padded `Allocation`.

    Works on batched allocations too (slices the trailing device/subcarrier
    axes, leaves leading batch axes alone).
    """
    return Allocation(
        f=alloc.f[..., :n],
        P=alloc.P[..., :n, :k],
        X=alloc.X[..., :n, :k],
        rho=alloc.rho,
    )
