"""Load on the program's served path: `AllocService` behind `RealClockDriver`.

The benchmark's only contact with the program is here: it builds the service
a deployment's file describes, turns generated requests into the program's
`SystemParams`, and submits them through `RealClockDriver.submit`, the call
users make. Each request's latency runs from its due time to the moment its
Future resolves (a done-callback stamps it on the client's side).
"""
from __future__ import annotations

import queue
import threading
import time
from contextlib import nullcontext
from typing import NamedTuple

import numpy as np

from .reference import Answers, Requests


def program():
    """The program's serving entry points, imported on first use."""
    import repro.core as core
    import repro.serve as serve
    return core, serve


def service(dep: dict, executables: dict | None = None):
    """An `AllocService` as the deployment's ``serve`` block states it
    (``executables``: a compiled-program cache shared between services)."""
    core, serve = program()
    s = dep["serve"]
    ws = s.get("warmstart")
    cfg = serve.ServeConfig(
        policy=serve.BatchPolicy(**s["policy"]),
        allocator=core.AllocatorConfig(**s["allocator"]),
        shard_batch=bool(s.get("shard_batch", False)),
        warmstart=serve.WarmStartConfig(**ws) if ws is not None else None,
    )
    return serve.AllocService(cfg, executables=executables)


def slots(dep: dict, n_devices: int) -> int:
    """Batch slots of one flush: the per-device batch times the devices a
    scenario-sharded service spans."""
    s = dep["serve"]
    return s["policy"]["max_batch"] * (n_devices if s.get("shard_batch") else 1)


def requests(dep: dict, req: Requests) -> list:
    """The program's `SystemParams` of each request, on host arrays."""
    core, _ = program()
    law = dep["law"]
    N, K = dep["N"], dep["K"]
    ones = np.ones(N, np.float32)
    fixed = dict(
        d=law["d_samples"] * ones,
        D=law["D_bits"] * ones,
        C=law["C_round_bits"] * law["L_rounds"] * ones,
        p_max=np.float32(10.0 ** ((law["p_max_dbm"] - 30.0) / 10.0)) * ones,
        f_max=law["f_max_hz"] * ones,
        t_sc_max=law["t_sc_max_s"] * ones,
        dev_mask=ones,
        sc_mask=np.ones(K, np.float32),
    )
    meta = dict(N=N, K=K, B=dep["B_hz"], N0=10.0 ** ((law["N0_dbm_hz"] - 30.0) / 10.0),
                xi=law["xi"], eta=law["eta"], q=law["q"])
    return [core.SystemParams(g=req.g[i], c=req.c[i], **fixed, **meta)
            for i in range(len(req.g))]


def weights(dep: dict):
    core, _ = program()
    import jax.numpy as jnp
    return core.Weights(*(jnp.float32(k) for k in dep["weights"]))


class Window(NamedTuple):
    """What one window sent and got back, request by request."""

    t0: float                   # window start (perf_counter)
    seconds: float
    index: np.ndarray           # which traffic request each send was
    due: np.ndarray             # due time of each send (perf_counter)
    sent: np.ndarray            # when submit() was called
    admit_s: np.ndarray         # how long submit() took
    done: np.ndarray            # when the Future resolved; nan if never
    completions: list           # Completion or None
    errors: list                # exception or None

    def outside(self, t0: float, t1: float) -> Window:
        """The sends whose span from due time to answer lies outside
        [t0, t1], so that host timers leave out a profiled stretch."""
        end = np.where(np.isfinite(self.done), self.done, np.inf)
        keep = np.nonzero((end < t0) | (np.minimum(self.due, self.sent) > t1))[0]
        return self._replace(
            index=self.index[keep], due=self.due[keep], sent=self.sent[keep],
            admit_s=self.admit_s[keep], done=self.done[keep],
            completions=[self.completions[k] for k in keep],
            errors=[self.errors[k] for k in keep])


class _Book:
    """Per-send records filled from the load thread and done-callbacks."""

    def __init__(self):
        self.index, self.due, self.sent, self.admit = [], [], [], []
        self.done, self.completions, self.errors, self.futures = [], [], [], []

    def send(self, driver, params, w, i, due, annotate, on_done=None):
        k = len(self.index)
        self.index.append(i)
        self.due.append(due)
        self.done.append(np.nan)
        self.completions.append(None)
        self.errors.append(None)
        t = time.perf_counter()
        self.sent.append(t)
        try:
            with annotate("bench.submit") if annotate else nullcontext():
                fut = driver.submit(params, w)
        except Exception as e:  # noqa: BLE001 -- a refused request is a failed one
            self.admit.append(time.perf_counter() - t)
            self.errors[k] = e
            if on_done is not None:
                on_done()
            return
        self.admit.append(time.perf_counter() - t)

        def resolved(f, k=k):
            self.done[k] = time.perf_counter()
            if on_done is not None:
                on_done()

        fut.add_done_callback(resolved)
        self.futures.append((k, fut))

    def collect(self, deadline: float, t0: float, seconds: float) -> Window:
        for k, fut in self.futures:
            try:
                self.completions[k] = fut.result(timeout=max(deadline - time.perf_counter(), 0.0))
            except Exception as e:  # noqa: BLE001 -- late or raised: failed
                self.errors[k] = e
                self.done[k] = np.nan
        return Window(t0, seconds, np.asarray(self.index, np.int64),
                      np.asarray(self.due), np.asarray(self.sent),
                      np.asarray(self.admit), np.asarray(self.done, np.float64),
                      self.completions, self.errors)


def warm_up(driver, params: list, w, timeout: float) -> None:
    """Push ``params`` through the driver and wait for every answer."""
    futs = [driver.submit(p, w) for p in params]
    for f in futs:
        f.result(timeout=timeout)


def open_loop(driver, params: list, due: np.ndarray, w, seconds: float,
              drain_s: float, annotate=None) -> Window:
    """Send request i at its due time ``due[i]`` from the window's start."""
    book = _Book()
    t0 = time.perf_counter()
    for i, d in enumerate(due):
        target = t0 + float(d)
        lag = target - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        book.send(driver, params[i], w, i, target, annotate)
    return book.collect(t0 + seconds + drain_s, t0, seconds)


def closed_loop(driver, params: list, clients: int, w, seconds: float,
                drain_s: float, annotate=None) -> Window:
    """``clients`` callers, each sending its next request (the pool's next,
    cycling) as soon as its answer arrives, until the window closes."""
    book = _Book()
    ready = queue.SimpleQueue()
    for _ in range(clients):
        ready.put(None)
    t0 = time.perf_counter()
    end = t0 + seconds
    i = 0
    while True:
        left = end - time.perf_counter()
        if left <= 0:
            break
        try:
            ready.get(timeout=left)
        except queue.Empty:
            break
        now = time.perf_counter()
        if now >= end:
            break
        book.send(driver, params[i % len(params)], w, i % len(params), now,
                  annotate, on_done=lambda: ready.put(None))
        i += 1
    return book.collect(end + drain_s, t0, seconds)


def answers(win: Window, dep: dict) -> tuple[np.ndarray, Answers, np.ndarray]:
    """(traffic index, stacked answers, reported objectives) of the answered
    sends of a window."""
    ok = [k for k, c in enumerate(win.completions) if c is not None]
    N, K = dep["N"], dep["K"]
    X = np.empty((len(ok), N, K), np.float32)
    P = np.empty((len(ok), N, K), np.float32)
    f = np.empty((len(ok), N), np.float32)
    rho = np.empty(len(ok), np.float32)
    obj = np.empty(len(ok), np.float64)
    for j, k in enumerate(ok):
        a = win.completions[k].alloc
        X[j], P[j], f[j] = np.asarray(a.X), np.asarray(a.P), np.asarray(a.f)
        rho[j] = float(np.asarray(a.rho))
        obj[j] = np.nan if win.completions[k].objective is None else win.completions[k].objective
    return win.index[ok], Answers(X, P, f, rho), obj


def start_stop(fn_start, fn_stop, at: float, length: float,
               span: list) -> threading.Thread:
    """Call ``fn_start`` at perf_counter ``at`` and ``fn_stop`` ``length``
    seconds later, on a thread of its own so the load keeps its pace;
    ``span`` gets the perf_counter before the start and after the stop."""
    def body():
        time.sleep(max(at - time.perf_counter(), 0.0))
        span.append(time.perf_counter())
        fn_start()
        time.sleep(length)
        fn_stop()
        span.append(time.perf_counter())

    t = threading.Thread(target=body, name="bench-profiler", daemon=True)
    t.start()
    return t
