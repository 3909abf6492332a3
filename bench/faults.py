"""Faults planted under the timed path, to show that the check catches them.

Each fault wraps the service's compiled solve (and refine) executables so
that what they return is broken the way a faulty change could break it; the
program's own scoring, unpadding and driver then carry the broken answers
to the caller, and the check has to come out false:

* ``state_unchanged``: the solve returns its starting point unchanged, the
  equal-share allocation (subcarriers round-robin, the budget split evenly,
  f at half its cap, the largest rho the deadline allows);
* ``half_batch``: only the first half of each flush's slots is solved; the
  other half gets the first half's answers;
* ``answer_altered``: each answer's first two devices swap their subcarriers
  and powers where the solve produces them;
* ``under_converged``: the service's solver runs its inner PGD for
  ``UNDER_CONVERGED`` of its steps: feasible answers, less optimised.
"""
from __future__ import annotations

import contextlib

NAMES = ("state_unchanged", "half_batch", "answer_altered", "under_converged")
#: share of the inner PGD's steps the ``under_converged`` solver keeps
UNDER_CONVERGED = 0.01


def _equal_share(params):
    import jax.numpy as jnp

    N, K = params.g.shape[-2:]
    k = jnp.arange(K)
    n_real = jnp.maximum(jnp.sum(params.dev_mask, -1), 1.0).astype(jnp.int32)
    owner = k[None, :] % n_real[:, None]                                # (B, K)
    X = (owner[:, None, :] == jnp.arange(N)[None, :, None]) * params.sc_mask[:, None, :]
    X = X.astype(jnp.float32)
    P = X * params.p_max[..., None] / jnp.maximum(jnp.sum(X, -1, keepdims=True), 1.0)
    f = 0.5 * params.f_max
    bbar = params.B / params.K
    r = jnp.sum(X * bbar * jnp.log2(1.0 + P * params.g / (params.N0 * bbar)), -1)
    ratio = jnp.where(params.dev_mask > 0, params.t_sc_max * r / jnp.maximum(params.C, 1e-30), jnp.inf)
    rho = jnp.minimum(1.0, jnp.min(ratio, -1))
    return f, P, X, rho


def _broken(name: str, res, params):
    import dataclasses

    import jax.numpy as jnp

    a = res.alloc
    if name == "state_unchanged":
        f, P, X, rho = _equal_share(params)
        alloc = dataclasses.replace(a, f=f, P=P, X=X, rho=rho)
    elif name == "half_batch":
        B = a.f.shape[0]
        src = jnp.arange(B) % (B // 2)
        alloc = type(a)(**{k: getattr(a, k)[src] for k in ("f", "P", "X", "rho")})
    elif name == "answer_altered":
        swap = jnp.arange(a.f.shape[-1]).at[0].set(1).at[1].set(0)
        alloc = dataclasses.replace(a, P=a.P[:, swap], X=a.X[:, swap])
    else:
        raise ValueError(f"unknown fault {name!r}; known: {NAMES}")
    return dataclasses.replace(res, alloc=alloc)


@contextlib.contextmanager
def planted(name: str | None):
    """Plant fault ``name`` in every `AllocService` while the block runs."""
    if name is None:
        yield
        return
    from repro.serve.service import AllocService

    if name == "under_converged":
        init = AllocService.__init__

        def fewer_steps(self, cfg, *a, **kw):
            al = cfg.allocator
            steps = max(1, int(al.pgd.steps * UNDER_CONVERGED))
            init(self, cfg._replace(allocator=al._replace(pgd=al.pgd._replace(steps=steps))),
                 *a, **kw)

        AllocService.__init__ = fewer_steps
        try:
            yield
        finally:
            AllocService.__init__ = init
        return

    solver, refiner = AllocService._solver, AllocService._refiner

    def wrap(get):
        def patched(self, key, slots, pb, *rest):
            exe = get(self, key, slots, pb, *rest)
            return lambda p, *a: _broken(name, exe(p, *a), p)
        return patched

    AllocService._solver, AllocService._refiner = wrap(solver), wrap(refiner)
    try:
        yield
    finally:
        AllocService._solver, AllocService._refiner = solver, refiner
