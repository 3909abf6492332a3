"""The one general traffic generator: a mix's data file in, a run's requests out.

A traffic mix is a JSON file ``bench/traffic/<mix>.json`` of parameters:

* ``arrivals``: ``"poisson"`` (open loop at ``rate_rps``) or ``"closed"``
  (``clients_per_slot`` clients for each batch slot of a flush, each sending
  its next request when its answer arrives, with no think time);
* ``law``: ``"iid"``, each request an independent draw of the deployment's
  channel law;
* ``pool``: for a closed loop, how many distinct requests the clients cycle
  through;
* ``warmup_flushes``: full flushes pushed through the service before the
  window.

Everything is drawn from ``--seed`` in one vectorised pass at set-up.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from . import channel
from .reference import Requests


class Traffic(NamedTuple):
    closed: bool
    due: np.ndarray | None     # open loop: due times, seconds from window start
    clients: int               # closed loop: concurrent clients
    window: Requests           # the window's requests (a closed loop cycles)
    warm: Requests             # the warm-up requests


def poisson_arrivals(rng: np.random.Generator, n: int, seconds: float) -> np.ndarray:
    """``n`` arrival times of a Poisson process on [0, seconds), ascending.

    A Poisson process conditioned on ``n`` arrivals in the window places them
    as sorted uniform draws, so every seed offers the same number of requests
    at the mix's rate and only their times differ.
    """
    return np.sort(rng.uniform(0.0, seconds, n))


def make(dep: dict, mix: dict, seed: int, seconds: float, slots: int) -> Traffic:
    """Draw a run's traffic for deployment ``dep`` under ``mix``."""
    rng = np.random.default_rng(seed)
    N, K, law = dep["N"], dep["K"], dep["law"]
    closed = mix["arrivals"] == "closed"
    if closed:
        due, n = None, int(mix["pool"])
        clients = int(mix["clients_per_slot"]) * slots
    elif mix["arrivals"] == "poisson":
        n = int(round(mix["rate_rps"] * seconds))
        due, clients = poisson_arrivals(rng, n, seconds), 0
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    if mix["law"] != "iid":
        raise ValueError(f"unknown law {mix['law']!r}")
    n_warm = int(mix["warmup_flushes"]) * slots
    g, c = channel.iid(rng, n_warm + n, N, K, law)
    return Traffic(
        closed=closed, due=due, clients=clients,
        window=Requests(g[n_warm:], c[n_warm:]),
        warm=Requests(g[:n_warm], c[:n_warm]),
    )
