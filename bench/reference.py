"""Plain reference of the allocation problem (paper eq. 1-13), in numpy.

It imports nothing of the program. Given the requests a run sent and the
allocations (X, P, f, rho) the service answered, it computes, per request and
vectorised over requests:

* the objective of eq. 13, k1 sum_n E_n + k2 T_FL - k3 sum_n A(rho), with the
  rates of eq. 1-2, the energies of eq. 5, 7, 12 and the delays of eq. 4, 6, 8;
* the relative violation of each constraint of P1 (13a-13g): power only on a
  device's own subcarriers and within its budget, f within its cap, each
  subcarrier to at most one device, the SemCom deadline, 0 < rho <= 1, X
  binary;
* the equal-share allocation (every subcarrier round-robin to a device, the
  device's budget split evenly over its subcarriers, f at half its cap, the
  largest rho the deadline allows), the point an allocator has to beat.

``dtype`` is the precision every operation rounds to: float32 as the
deployments state it, bfloat16 for the control (``ml_dtypes``).
"""
from __future__ import annotations

from typing import NamedTuple

import ml_dtypes
import numpy as np

BF16 = ml_dtypes.bfloat16
_EPS = 1e-12


class Requests(NamedTuple):
    """Stacked requests of one deployment: ``g`` (n, N, K), ``c`` (n, N)."""

    g: np.ndarray
    c: np.ndarray


class Answers(NamedTuple):
    """Stacked answers: ``X``/``P`` (n, N, K), ``f`` (n, N), ``rho`` (n,)."""

    X: np.ndarray
    P: np.ndarray
    f: np.ndarray
    rho: np.ndarray


def _cast(x, dtype):
    return np.asarray(x, np.float32).astype(dtype)


def rates(dep: dict, g, X, P, dtype=np.float32):
    """Per-device rate r_n = sum_k x_nk bbar log2(1 + p_nk g_nk / (N0 bbar))."""
    bbar = dtype(dep["B_hz"] / dep["K"])
    noise = dtype(10.0 ** ((dep["law"]["N0_dbm_hz"] - 30.0) / 10.0) * dep["B_hz"] / dep["K"])
    g, X, P = (_cast(a, dtype) for a in (g, X, P))
    per_sc = bbar * np.log1p(P * g / noise) / dtype(np.log(2.0))
    return np.sum(X * per_sc, axis=-1, dtype=dtype)


def terms(dep: dict, req: Requests, ans: Answers, dtype=np.float32):
    """(energy sum, T_FL, accuracy sum) per request, each (n,)."""
    law = dep["law"]
    n_dev = dep["N"]
    r = np.maximum(rates(dep, req.g, ans.X, ans.P, dtype), dtype(_EPS))
    p_n = np.sum(_cast(ans.P, dtype), axis=-1, dtype=dtype)
    f = np.maximum(_cast(ans.f, dtype), dtype(_EPS))
    rho = _cast(ans.rho, dtype)[:, None]
    c = _cast(req.c, dtype)
    eta, d = dtype(law["eta"]), dtype(law["d_samples"])
    D = dtype(law["D_bits"])
    C = dtype(law["C_round_bits"] * law["L_rounds"])
    xi = dtype(law["xi"])
    tau = D / r
    t_c = eta * c * d / f
    e_t = p_n * tau
    e_c = xi * eta * c * d * f * f
    e_sc = p_n * rho * C / r
    energy = np.sum(e_t + e_c + e_sc, axis=-1, dtype=dtype)
    t_fl = np.max(tau + t_c, axis=-1)
    a, b = (dtype(v) for v in dep["accuracy"])
    acc = dtype(n_dev) * a * np.power(np.maximum(rho[:, 0], dtype(1e-9)), b)
    return energy, t_fl, acc


def objective(dep: dict, req: Requests, ans: Answers, dtype=np.float32):
    """Eq. 13 per request (n,), and the sum of its terms' sizes (n,)."""
    energy, t_fl, acc = terms(dep, req, ans, dtype)
    k1, k2, k3 = (dtype(v) for v in dep["weights"])
    obj = k1 * energy + k2 * t_fl - k3 * acc
    size = np.abs(k1 * energy) + np.abs(k2 * t_fl) + np.abs(k3 * acc)
    return obj.astype(np.float32), size.astype(np.float32)


def violation(dep: dict, req: Requests, ans: Answers) -> np.ndarray:
    """Largest relative violation of P1's constraints per request (n,)."""
    law = dep["law"]
    p_max = np.float32(10.0 ** ((law["p_max_dbm"] - 30.0) / 10.0))
    f_max = np.float32(law["f_max_hz"])
    t_sc = np.float32(law["t_sc_max_s"])
    C = np.float32(law["C_round_bits"] * law["L_rounds"])
    X = np.asarray(ans.X, np.float32)
    P = np.asarray(ans.P, np.float32)
    f = np.asarray(ans.f, np.float32)
    rho = np.asarray(ans.rho, np.float32)
    r = np.maximum(rates(dep, req.g, X, P), np.float32(_EPS))
    parts = [
        np.max(np.abs(X - np.round(X)), axis=(1, 2)),                  # X binary
        np.max(np.sum(np.round(X), axis=1) - 1.0, axis=-1),           # 13d
        np.max(np.where(np.round(X) > 0, 0.0, P), axis=(1, 2)) / p_max,  # off-own power
        np.max(-P, axis=(1, 2)) / p_max,                               # P >= 0
        np.max(np.sum(P, axis=-1) - p_max, axis=-1) / p_max,          # budget
        np.max(f - f_max, axis=-1) / f_max,                            # f cap
        np.max(-f, axis=-1) / f_max,                                   # f >= 0
        np.max(rho[:, None] * C / r - t_sc, axis=-1) / t_sc,           # deadline
        rho - 1.0,                                                     # rho <= 1
        -rho,                                                          # rho > 0
    ]
    worst = np.max(np.stack(parts), axis=0)
    return np.maximum(worst, 0.0).astype(np.float64)


def equal_share(dep: dict, req: Requests) -> Answers:
    """The equal-share allocation of every request (class docstring)."""
    law = dep["law"]
    n, N, K = req.g.shape
    X = np.zeros((N, K), np.float32)
    X[np.arange(K) % N, np.arange(K)] = 1.0
    X = np.broadcast_to(X, (n, N, K))
    p_max = np.float32(10.0 ** ((law["p_max_dbm"] - 30.0) / 10.0))
    P = X * p_max / np.sum(X, axis=-1, keepdims=True)
    f = np.full((n, N), 0.5 * law["f_max_hz"], np.float32)
    r = rates(dep, req.g, X, P)
    C = np.float32(law["C_round_bits"] * law["L_rounds"])
    rho = np.minimum(1.0, np.min(law["t_sc_max_s"] * r / C, axis=-1)).astype(np.float32)
    return Answers(np.array(X), P.astype(np.float32), f, rho)


def least_objective(dep: dict) -> float:
    """A lower bound of eq. 13: no energy, no delay, every device at A(1)."""
    return -dep["weights"][2] * dep["N"] * dep["accuracy"][0]


def in_bf16(ans: Answers) -> Answers:
    """An answer as a bfloat16 path would return it."""
    return Answers(*(np.asarray(a, np.float32).astype(BF16).astype(np.float32)
                     for a in ans))
