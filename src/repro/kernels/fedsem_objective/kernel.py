"""Pallas TPU kernel: FedSem objective (eq. 13) over a grid of candidates.

The exhaustive / random-search baselines evaluate the P1 objective for ~1e8
candidate allocations; this is the paper-core's only compute hot-spot
(DESIGN.md §4). Layout is transposed to (N, G) so the candidate axis G sits on
the 128-wide lane dimension of the VPU; device axis N (4..16) rides sublanes.
Each grid step processes a (N, BG) VMEM tile; the N-reductions and max happen
on-chip, emitting a (1, BG) objective tile.

Two entry points:

* `objective_grid_pallas` — one scenario, G candidates, *static* objective
  weights (the exhaustive-search path, where weights are python floats).
* `objective_batch_pallas` — a leading scenario axis B (grid `(B, G/BG)`),
  per-scenario parameter rows and *runtime* weight / accuracy scalars, so the
  batched evaluation paths (`solve_batch` multi-start scoring, serving's
  padded-bucket batches) trace it with per-scenario `Weights` under jit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_EPS = 1e-12
BLOCK_G = 512  # lane-aligned candidate tile (4 x 128)
LANE = 128     # TPU lane width: smallest useful candidate tile


def _kernel(
    f_ref, p_ref, r_ref, rho_ref,       # (N, BG), (N, BG), (N, BG), (1, BG)
    c_ref, d_ref, D_ref, C_ref, tsc_ref, fmax_ref, mask_ref,  # (N, 1) each
    obj_ref,                            # out: (1, BG)
    *, xi: float, eta: float, k1: float, k2: float, k3: float,
    a_acc: float, b_acc: float,
):
    f = f_ref[...]
    p = p_ref[...]
    r = jnp.maximum(r_ref[...], _EPS)
    rho = rho_ref[...]                  # (1, BG)
    real = mask_ref[...] > 0.0          # (N, 1) validity (pad_params contract)

    cd = c_ref[...] * d_ref[...]        # (N, 1)
    tau = D_ref[...] / r
    t_c = eta * cd / jnp.maximum(f, _EPS)
    e_t = p * tau
    e_c = xi * eta * cd * (f * f)
    e_sc = p * rho * C_ref[...] / r
    # padded rows must not leak into any device-axis reduction: select, don't
    # multiply (a masked multiply turns inf garbage into nan)
    e_dev = jnp.where(real, e_t + e_c + e_sc, 0.0)
    t_fl = jnp.max(
        jnp.where(real, tau + t_c, -jnp.inf), axis=0, keepdims=True
    )                                                          # (1, BG)
    acc = a_acc * jnp.exp(b_acc * jnp.log(jnp.maximum(rho, 1e-9)))
    n_dev = jnp.sum(mask_ref[...], axis=0, keepdims=True)      # (1, 1) real count

    obj = (
        k1 * jnp.sum(e_dev, axis=0, keepdims=True)
        + k2 * t_fl
        - k3 * n_dev * acc
    )
    t_sc = rho * C_ref[...] / r
    bad = jnp.any((t_sc > tsc_ref[...]) & real, axis=0, keepdims=True) | jnp.any(
        (f > fmax_ref[...] * (1.0 + 1e-6)) & real, axis=0, keepdims=True
    )
    obj_ref[...] = jnp.where(bad, jnp.inf, obj)


@functools.partial(
    jax.jit,
    static_argnames=("xi", "eta", "k1", "k2", "k3", "a_acc", "b_acc", "interpret"),
)
def objective_grid_pallas(
    f_t, p_t, r_t, rho,                 # (N, G) x3, (G,)
    c, d, D, C, t_sc_max, f_max,        # (N,) each
    dev_mask,                           # (N,) 1 = real device, 0 = padding
    *, xi, eta, k1, k2, k3, a_acc, b_acc, interpret: bool = False,
):
    N, G = f_t.shape
    assert G % BLOCK_G == 0, "pad G to a multiple of BLOCK_G before calling"
    col = lambda v: jnp.asarray(v, jnp.float32).reshape(N, 1)
    rho2 = jnp.asarray(rho, jnp.float32).reshape(1, G)

    grid = (G // BLOCK_G,)
    cand_spec = pl.BlockSpec((N, BLOCK_G), lambda i: (0, i))
    row_spec = pl.BlockSpec((1, BLOCK_G), lambda i: (0, i))
    vec_spec = pl.BlockSpec((N, 1), lambda i: (0, 0))

    out = pl.pallas_call(
        functools.partial(
            _kernel, xi=xi, eta=eta, k1=k1, k2=k2, k3=k3, a_acc=a_acc, b_acc=b_acc
        ),
        grid=grid,
        in_specs=[cand_spec, cand_spec, cand_spec, row_spec] + [vec_spec] * 7,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((1, G), jnp.float32),
        interpret=interpret,
    )(
        f_t.astype(jnp.float32),
        p_t.astype(jnp.float32),
        r_t.astype(jnp.float32),
        rho2,
        col(c), col(d), col(D), col(C), col(t_sc_max), col(f_max),
        col(dev_mask),
    )
    return out[0]


# ---------------------------------------------------------------------------
# batched-over-scenarios kernel
# ---------------------------------------------------------------------------


def _batch_kernel(
    f_ref, p_ref, r_ref,                # (1, N, BG) candidate tiles
    rho_ref,                            # (1, 1, BG)
    c_ref, d_ref, D_ref, C_ref, tsc_ref, fmax_ref, mask_ref,  # (1, N, 1)
    k1_ref, k2_ref, k3_ref, aa_ref, ab_ref,                   # (1, 1, 1)
    obj_ref,                            # out: (1, 1, BG)
    *, xi: float, eta: float, check_feasible: bool,
):
    f = f_ref[0]                        # (N, BG)
    p = p_ref[0]
    r = jnp.maximum(r_ref[0], _EPS)
    rho = rho_ref[0]                    # (1, BG)
    real = mask_ref[0] > 0.0            # (N, 1) validity (pad_params contract)
    k1 = k1_ref[0]                      # (1, 1) runtime scalars
    k2 = k2_ref[0]
    k3 = k3_ref[0]

    cd = c_ref[0] * d_ref[0]            # (N, 1)
    tau = D_ref[0] / r
    t_c = eta * cd / jnp.maximum(f, _EPS)
    e_t = p * tau
    e_c = xi * eta * cd * (f * f)
    e_sc = p * rho * C_ref[0] / r
    # padded rows must not leak into any device-axis reduction: select, don't
    # multiply (a masked multiply turns inf garbage into nan)
    e_dev = jnp.where(real, e_t + e_c + e_sc, 0.0)
    t_fl = jnp.max(
        jnp.where(real, tau + t_c, -jnp.inf), axis=0, keepdims=True
    )                                                          # (1, BG)
    acc = aa_ref[0] * jnp.exp(ab_ref[0] * jnp.log(jnp.maximum(rho, 1e-9)))
    n_dev = jnp.sum(mask_ref[0], axis=0, keepdims=True)        # (1, 1) real count

    obj = (
        k1 * jnp.sum(e_dev, axis=0, keepdims=True)
        + k2 * t_fl
        - k3 * n_dev * acc
    )
    if check_feasible:
        t_sc = rho * C_ref[0] / r
        bad = jnp.any(
            (t_sc > tsc_ref[0]) & real, axis=0, keepdims=True
        ) | jnp.any(
            (f > fmax_ref[0] * (1.0 + 1e-6)) & real, axis=0, keepdims=True
        )
        obj = jnp.where(bad, jnp.inf, obj)
    obj_ref[0] = obj


@functools.partial(
    jax.jit,
    static_argnames=("xi", "eta", "check_feasible", "interpret", "block_g"),
)
def objective_batch_pallas(
    f_t, p_t, r_t,                      # (B, N, G) each
    rho,                                # (B, G)
    c, d, D, C, t_sc_max, f_max,        # (B, N) each
    dev_mask,                           # (B, N) 1 = real device, 0 = padding
    k1, k2, k3, a_acc, b_acc,           # (B,) runtime weights / accuracy fit
    *, xi, eta,
    check_feasible: bool = True,
    interpret: bool = False,
    block_g: int = BLOCK_G,
):
    """Batched objective grid: one scenario per leading-grid step.

    The grid is (B, G // block_g): scenario b's parameter rows and weight
    scalars are re-fetched per candidate tile, candidates ride the lane
    dimension exactly as in the single-scenario kernel. Weights and the
    accuracy power-law coefficients are *runtime* (B,) inputs, so the same
    compiled kernel serves every `Weights`, including per-scenario batches.

    Every per-scenario operand carries the scenario on a LEADING axis of its
    own — rho and the output as (B, 1, G), the scalars as (B, 1, 1) — so the
    last two dims of each block are either whole array dims or lane-aligned,
    which Mosaic requires for any B (a (1, block_g) block of a (B, G) array
    is refused unless B == 1). Callers still pass and get back (B, G).
    """
    B, N, G = f_t.shape
    assert G % block_g == 0, "pad G to a multiple of block_g before calling"
    vec = lambda v: jnp.asarray(v, jnp.float32).reshape(B, N, 1)
    scal = lambda v: jnp.broadcast_to(
        jnp.asarray(v, jnp.float32).reshape(-1, 1, 1), (B, 1, 1)
    )

    grid = (B, G // block_g)
    cand_spec = pl.BlockSpec((1, N, block_g), lambda b, i: (b, 0, i))
    row_spec = pl.BlockSpec((1, 1, block_g), lambda b, i: (b, 0, i))
    vec_spec = pl.BlockSpec((1, N, 1), lambda b, i: (b, 0, 0))
    scal_spec = pl.BlockSpec((1, 1, 1), lambda b, i: (b, 0, 0))

    out = pl.pallas_call(
        functools.partial(
            _batch_kernel, xi=xi, eta=eta, check_feasible=check_feasible
        ),
        grid=grid,
        in_specs=(
            [cand_spec] * 3 + [row_spec] + [vec_spec] * 7 + [scal_spec] * 5
        ),
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, G), jnp.float32),
        interpret=interpret,
    )(
        f_t.astype(jnp.float32),
        p_t.astype(jnp.float32),
        r_t.astype(jnp.float32),
        jnp.asarray(rho, jnp.float32).reshape(B, 1, G),
        vec(c), vec(d), vec(D), vec(C), vec(t_sc_max), vec(f_max),
        vec(dev_mask),
        scal(k1), scal(k2), scal(k3), scal(a_acc), scal(b_acc),
    )
    return out.reshape(B, G)
