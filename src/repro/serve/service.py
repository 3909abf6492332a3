"""`AllocService`: micro-batched scenario-allocation serving over `solve_batch`.

Heterogeneous `SystemParams` requests are padded into canonical `ShapeBucket`s
(`pad_params` masks keep padding inert), queued per bucket, and flushed
through ONE AOT-compiled `solve_batch` executable per (bucket, batch-slots,
`AllocatorConfig`, mesh). The batch axis is padded to a fixed number of slots
by replicating the last request, so each bucket compiles exactly once no
matter how full its flushes run — the compiled-executable cache is the whole
point: steady-state serving never re-traces. With ``shard_batch`` the slots
grow to ``device_count x max_batch`` and each flush runs one scenario-sharded
executable over all local devices (`core.distribute`).

Equivalence guarantees this layer asserts (tests/test_serve_alloc.py):
a padded-bucket solve returns the *same hardened assignment* as the
exact-shape solve of the submitted scenario, with objective drift at float32
round-off; batch-axis padding replicates the tail request, whose replicas are
solved and discarded, so co-batching never changes any caller's answer.
Each flushed bucket batch is also *scored* through the batched
`kernels/fedsem_objective` evaluator (`core.scoring.batch_objectives`) in one
fused call over the padded batch — `Completion.objective` reports the
eq. 13 value of the returned allocation, equal to `system.objective` on the
exact-shape scenario to float32 round-off.

The A(rho) accuracy model is PER-REQUEST, not service-global: every request
is stamped with its own `AccuracyFn` at `prepare` (explicit ``accuracy=``
arg > per-tenant registry (`set_accuracy(acc, tenant=...)`) > the service
default), the flush stacks the per-row fits (`stack_accuracy`) and the AOT
executables take the stacked fit as a runtime argument
(``exe(pb, wb, accb)``, `solve_batch(..., acc_batched=True)`), so co-batched
tenants with different beliefs solve AND score under their own model in one
program — a refit never recompiles and never touches a co-tenant's rows
(the multi-tenant equivalence rows, tests/test_multitenant_accuracy.py).

The service is sans-IO: callers pass ``now`` timestamps and decide when to
flush (`flush_full` after submits, `flush_due` on timer ticks, `drain` at
shutdown), which makes it drivable by a real clock (`repro.launch.serve_alloc`)
or a virtual one (`repro.serve.loadgen`, benchmarks).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import NamedTuple

import jax
import numpy as np

from repro.core import (
    Allocation,
    AllocatorConfig,
    SystemParams,
    Weights,
    bucket_for,
    pad_params,
    scenario_mesh,
    scenario_sharding,
    sharded_batch_solver,
    stack_params,
    stack_weights,
    tree_index,
    unpad_alloc,
)
from repro.core.accuracy import AccuracyFn, default_accuracy, stack_accuracy
from repro.core.allocator import (
    _refine_batch_jit,
    _solve_batch_impl,
    _solve_batch_jit,
    sharded_refine_solver,
)
from repro.core.distribute import scenario_map
from repro.core.scoring import batch_objectives
from repro.core.types import DEFAULT_BUCKETS, ShapeBucket

from .batching import BatchPolicy, MicroBatcher, PendingRequest
from .metrics import ServiceMetrics
from .warmstart import (
    CacheEntry,
    WarmStartCache,
    WarmStartConfig,
    batch_starts,
    entry_from_alloc,
    iters_to_converge,
    request_signature,
)


class ServeConfig(NamedTuple):
    policy: BatchPolicy = BatchPolicy()
    #: bucket ladder; None = exact shapes (no padding — every distinct request
    #: shape compiles its own program; the solve-per-request baseline)
    buckets: tuple[ShapeBucket, ...] | None = DEFAULT_BUCKETS
    allocator: AllocatorConfig = AllocatorConfig(inner="pgd")
    #: pad the batch axis to ``policy.max_batch`` slots so each bucket
    #: compiles once; False recompiles per observed batch size
    pad_batch: bool = True
    #: shard the batch axis over a scenario mesh of all local devices
    #: (`core.distribute`): bucket slots grow to ``device_count x max_batch``
    #: (``policy.max_batch`` becomes the per-device batch) and each flush runs
    #: one sharded executable with no cross-device communication
    shard_batch: bool = False
    #: score every flushed bucket batch through the batched
    #: `kernels/fedsem_objective` evaluator (one fused call per flush) and
    #: report the eq. 13 value on each `Completion.objective`
    score_objective: bool = True
    #: warm-start solution-reuse cache (`repro.serve.warmstart`): record each
    #: completed request's hardened solution under a quantized channel/
    #: accuracy signature and inject hits into later flushes as an extra
    #: multi-start candidate. None (default) disables it — the cold path,
    #: bit-for-bit (the cold==disabled equivalence row)
    warmstart: WarmStartConfig | None = None


#: one fused batched-kernel scoring call per flush; jit-cached per bucket
#: shape (a tiny program next to the solver executables)
_score_flush = jax.jit(functools.partial(batch_objectives, weights_batched=True))


@functools.lru_cache(maxsize=None)
def sharded_score_flush(mesh):
    """`_score_flush` over a scenario mesh: every argument (params, weights,
    allocation, accuracy fit) is split on its batch axis and each device
    scores its own rows with the kernel (`core.distribute.scenario_map`)."""
    score = functools.partial(batch_objectives, weights_batched=True)
    return jax.jit(scenario_map(score, mesh, (True, True, True, True)))


def _round_sig(x: float, digits: int = 12) -> float:
    """Round to ``digits`` significant figures (canonical bucket-key floats).

    Requests built from the same per-subcarrier bandwidth but different K
    reconstruct the padded ``B = bbar * K_pad`` through different float
    round-trips and can disagree by an ulp; keyed raw, they would silently
    land in different queues (and `stack_params` would reject mixing them).
    12 significant figures absorbs ulp noise (~1e-16 rel) while keeping any
    physically distinct bandwidth (>= 1e-10 rel apart) distinct.
    """
    if x == 0.0 or not math.isfinite(x):
        return x
    return round(x, digits - 1 - math.floor(math.log10(abs(x))))


class Completion(NamedTuple):
    """One answered request (exact-shape, hardened, feasible-by-construction)."""

    req_id: int
    alloc: Allocation
    bucket: tuple       # (N_pad, K_pad)
    latency_s: float    # arrival -> answer (queue wait + batched solve)
    wait_s: float       # arrival -> flush
    solve_s: float      # the batched solve this request rode in
    #: eq. 13 objective of ``alloc``, scored on the padded bucket batch by the
    #: batched kernel (== `system.objective` on the exact-shape scenario to
    #: float32 round-off); None when ``ServeConfig.score_objective`` is off
    objective: float | None = None
    #: True when this request rode a warm-start candidate (cache hit or
    #: explicit injection) into its flush
    warm_hit: bool = False
    #: the exact-shape warm-start entry (or tuple of entries, top-k) that
    #: rode along (None for a cold request). Recorded so a virtual-clock
    #: replay can re-inject the SAME starts explicitly — real==virtual
    #: equivalence stays exact even though cache contents are
    #: timing-dependent (batch boundaries move)
    warm_start: CacheEntry | tuple | None = None


class AllocService:
    """Micro-batched allocation server (see module docstring)."""

    def __init__(
        self,
        cfg: ServeConfig = ServeConfig(),
        executables: dict[tuple, object] | None = None,
    ):
        """``executables`` optionally shares a compiled-solver cache built by
        another service with the SAME ServeConfig (e.g. a warmed instance in a
        benchmark sweep); the dict is used and extended in place."""
        self.cfg = cfg
        # with shard_batch, policy.max_batch is the PER-DEVICE batch: buckets
        # fill (and pad) to device_count x max_batch slots, so each device in
        # the sharded executable solves a max_batch-sized sub-batch
        self.mesh = scenario_mesh() if cfg.shard_batch else None
        n_dev = self.mesh.size if self.mesh is not None else 1
        self._score = (
            _score_flush if self.mesh is None else sharded_score_flush(self.mesh)
        )
        self._full_slots = cfg.policy.max_batch * n_dev
        self.batcher = MicroBatcher(cfg.policy._replace(max_batch=self._full_slots))
        self.metrics = ServiceMetrics()
        self._executables = executables if executables is not None else {}
        #: all-tenants default A(rho); per-tenant overrides live in
        #: `_tenant_acc` and win for their own tenant's admissions
        self._acc = default_accuracy()
        self._tenant_acc: dict = {}
        self._next_id = 0
        #: warm-start solution cache (None when disabled). Thread-safe on its
        #: own lock: `prepare` reads it from caller threads, the solver
        #: thread writes it after each flush
        self.warm_cache = (
            WarmStartCache(cfg.warmstart) if cfg.warmstart is not None else None
        )

    @property
    def executables(self) -> dict[tuple, object]:
        """The compiled-solver cache, keyed by (bucket key, batch slots,
        AllocatorConfig, mesh) — pass to another AllocService to skip its
        compiles; a service with a different allocator config or sharding
        (``shard_batch``, so mesh None vs a scenario mesh) safely misses and
        compiles its own entries."""
        return self._executables

    # -- admission ----------------------------------------------------------

    def _pad(self, params: SystemParams) -> SystemParams:
        # canonicalise B at the service boundary — in BOTH bucket modes — so
        # equal-bbar requests that reconstructed B through different float
        # round-trips land in one queue (see `_round_sig`). Exact-shape mode
        # used to skip this: two requests whose B differed by an ulp got equal
        # shapes but different bucket keys, and even with equal keys
        # `stack_params` would reject mixing them (regression-tested).
        # The core `pad_params` itself stays bit-exact on bbar.
        if self.cfg.buckets is None:
            return dataclasses.replace(params, B=_round_sig(params.B))
        padded = pad_params(params, bucket_for(params.N, params.K, self.cfg.buckets))
        return dataclasses.replace(padded, B=_round_sig(padded.B))

    @staticmethod
    def _bucket_key(padded: SystemParams) -> tuple:
        # shape + every static meta field: one queue == one compiled program
        return (
            padded.N, padded.K, padded.B, padded.N0,
            padded.xi, padded.eta, padded.q,
        )

    def _resolve_accuracy(self, accuracy=None, tenant=None) -> AccuracyFn:
        """The A(rho) fit a request is stamped with at admission: an explicit
        ``accuracy`` wins, else the ``tenant``'s registered fit
        (`set_accuracy(acc, tenant=...)`), else the all-tenants default."""
        if accuracy is not None:
            return accuracy
        if tenant is not None and tenant in self._tenant_acc:
            return self._tenant_acc[tenant]
        return self._acc

    def prepare(
        self,
        params: SystemParams,
        weights: Weights | None = None,
        warm_start=None,
        accuracy: AccuracyFn | None = None,
        tenant=None,
    ) -> PendingRequest:
        """Pad/canonicalise one scenario into its bucket WITHOUT touching any
        queue state (``req_id``/``arrival_t`` are placeholders until `admit`).

        This is the pure, stateless half of admission: the real-clock driver
        runs it on the *caller's* thread, so the host-side padding work
        overlaps the solver thread's device solves (which release the GIL).
        The request's A(rho) fit is resolved and STAMPED here
        (`_resolve_accuracy`) — it rides the request to its flush, so a
        `set_accuracy` racing the queue never re-steers or re-scores an
        already-admitted request. The warm-cache lookup happens here too (the
        cache has its own lock), keyed on the request's OWN fit: an explicit
        ``warm_start`` entry (or tuple of entries) — e.g. the previous FL
        round's solution, or a replay re-injecting recorded hits — takes
        precedence over whatever the cache holds."""
        w = weights if weights is not None else Weights.ones()
        acc = self._resolve_accuracy(accuracy, tenant)
        sig = None
        if self.warm_cache is not None:
            sig = request_signature(params, w, acc, self.cfg.warmstart)
        entry = warm_start
        # CacheEntry IS a tuple (NamedTuple): only normalise genuine
        # candidate lists, never a bare entry
        if isinstance(entry, (list, tuple)) and not isinstance(entry, CacheEntry):
            entry = tuple(entry) if entry else None
        if entry is None and self.warm_cache is not None:
            hits = self.warm_cache.lookup(sig, self.cfg.warmstart.top_k)
            entry = hits[0] if len(hits) == 1 else (tuple(hits) or None)
        return PendingRequest(
            req_id=-1,
            params=params,
            padded=self._pad(params),
            weights=w,
            arrival_t=0.0,
            accuracy=acc,
            warm_start=entry,
            warm_sig=sig,
        )

    def admit(self, req: PendingRequest, now: float) -> int:
        """Assign a request id and enqueue a `prepare`d request (arrival
        stamped at ``now``). Cheap — a deque append — and, like every other
        state mutation on this sans-IO service, must be called from a single
        thread (the driver's solver thread)."""
        req.req_id = self._next_id
        self._next_id += 1
        req.arrival_t = now
        self.batcher.add(self._bucket_key(req.padded), req)
        self.metrics.observe_submit(self.batcher.depth())
        return req.req_id

    def submit(
        self,
        params: SystemParams,
        weights: Weights | None = None,
        now: float = 0.0,
        warm_start=None,
        accuracy: AccuracyFn | None = None,
        tenant=None,
    ) -> int:
        """Admit one scenario; returns its request id. Does not solve — call
        `flush_full` / `flush_due` / `drain` to get completions.
        ``accuracy``/``tenant`` select the A(rho) fit the request solves
        under (see `prepare`)."""
        return self.admit(
            self.prepare(params, weights, warm_start, accuracy, tenant), now
        )

    def set_buckets(self, buckets: tuple[ShapeBucket, ...] | None) -> None:
        """Swap the bucket ladder (e.g. a learned `repro.serve.ladder` refit
        between epochs). Safe mid-stream: already-queued requests keep the
        bucket they were admitted into (their padded params and key travel
        with them), only new admissions see the new ladder, and the
        executable cache simply compiles entries for new buckets on first
        flush (old entries stay valid)."""
        self.cfg = self.cfg._replace(buckets=buckets)

    def set_accuracy(self, acc, tenant=None) -> None:
        """Update the A(rho) model subsequent ADMISSIONS are stamped with
        (e.g. an `AccuracyFn` re-fit from a SemCom job's own proxy-accuracy
        measurements — the FedSem feedback edge, `repro.fl.semcom_job`).

        With ``tenant`` the refit scopes to that tenant's registry entry:
        only requests admitted under the same tenant id (or with this fit
        passed explicitly) see it — co-tenants on a shared driver keep their
        own beliefs, bit-for-bit (the multi-tenant non-interference row).
        Without ``tenant`` the all-tenants DEFAULT is swapped — the legacy
        service-global behaviour, which unregistered-tenant requests keep
        getting unchanged (the compatibility shim, pinned by regression).

        Zero recompiles either way: the stacked per-row fit is a runtime
        argument of every compiled executable, not part of its cache key, so
        a refit is a dict/attribute store (atomic under the GIL, same safety
        argument as `set_buckets`). Requests stamp their fit at `prepare` —
        already-queued requests solve and score under the model they were
        admitted with, not the refit.

        Warm-start cache entries recorded under the OLD model stay valid and
        need no invalidation: a hit is only ever a *start point* — the refine
        pass re-solves and re-scores it under the rider's current fit, so
        a stale entry competes on the new objective and can only help or tie
        (regression-tested in tests/test_warmstart.py).
        """
        if tenant is None:
            self._acc = acc
        else:
            self._tenant_acc[tenant] = acc

    def pending(self) -> int:
        return self.batcher.depth()

    def next_deadline(self) -> float | None:
        return self.batcher.next_deadline()

    # -- the compiled-solver cache ------------------------------------------

    def _slots(self, n_real: int) -> int:
        """Batch-axis slots for a flush of ``n_real`` requests.

        ``pad_batch``: fixed at ``device_count x max_batch`` so each bucket
        compiles once. Otherwise slots follow the observed size, rounded up to
        the device count when sharding (the mesh needs a divisible axis).
        """
        if self.cfg.pad_batch:
            return self._full_slots
        if self.mesh is not None:
            n_dev = self.mesh.size
            return -(-n_real // n_dev) * n_dev
        return n_real

    def _place(self, params_batch, weights_batch, acc_batch):
        """Commit a flush's inputs to the mesh (scenario-sharded batch axis —
        including the stacked per-row accuracy fit, whose leaves are (B,))
        so AOT executables see the shardings they were compiled for. No-op
        placement cost on a single device."""
        if self.mesh is None:
            return params_batch, weights_batch, acc_batch
        scen = scenario_sharding(self.mesh)
        return (
            jax.device_put(params_batch, scen),
            jax.device_put(weights_batch, scen),
            jax.device_put(acc_batch, scen),
        )

    def _solver(self, key: tuple, slots: int, params_batch, weights_batch, acc_batch):
        # AllocatorConfig AND the mesh are part of the key: a shared
        # `executables` dict must never hand config A's solver to a service
        # running config B, nor a single-device program to a sharded service
        cache_key = (key, slots, self.cfg.allocator, self.mesh)
        exe = self._executables.get(cache_key)
        if exe is None:
            cfg = self.cfg.allocator
            jitted = (
                _solve_batch_jit
                if self.mesh is None
                else sharded_batch_solver(self.mesh, True, True)
            )
            pb, wb, accb = self._place(params_batch, weights_batch, acc_batch)
            t0 = time.perf_counter()
            exe = jitted.lower(pb, wb, accb, cfg, True, True).compile()
            self._executables[cache_key] = exe
            self.metrics.observe_cache(hit=False, compile_s=time.perf_counter() - t0)
        else:
            self.metrics.observe_cache(hit=True)
        return exe

    def _place_extra(self, extra):
        """Commit a flush's warm-start batch to the device(s) the executables
        expect (scenario-sharded like the params when running on a mesh)."""
        if self.mesh is None:
            return jax.tree.map(jax.numpy.asarray, extra)
        return jax.device_put(extra, scenario_sharding(self.mesh))

    def _refiner(self, key: tuple, slots: int, pb, wb, accb, extra):
        """AOT-compiled warm-refine executable for one (bucket, slots,
        candidate-count) triple — the second program of a warm flush: takes
        the cold result plus the flush's `ExtraStart` batch and returns the
        per-scenario best (`core.allocator._refine_batch_impl`). Cached
        beside the cold executables under a distinct key so cold-only
        services never pay its compile, and flushes with zero hits never run
        it. Single-candidate flushes ((B,)-valid `ExtraStart`) and top-k
        flushes ((B, top_k)) are different programs; `batch_starts` pads
        every multi-candidate flush to exactly ``top_k`` candidates, so a
        service compiles at most two refine programs per bucket."""
        n_cand = 1 if np.ndim(extra.valid) == 1 else int(extra.valid.shape[1])
        cache_key = (key, slots, self.cfg.allocator, self.mesh, "warm-refine", n_cand)
        exe = self._executables.get(cache_key)
        if exe is None:
            cfg = self.cfg.allocator
            jitted = (
                _refine_batch_jit
                if self.mesh is None
                else sharded_refine_solver(self.mesh, True, True)
            )
            pb, wb, accb = self._place(pb, wb, accb)
            extra = self._place_extra(extra)
            # the cold result's abstract shape is all lowering needs — no
            # solve happens here, so compile time stays out of solve_s
            base = jax.eval_shape(
                functools.partial(
                    _solve_batch_impl, cfg=cfg, weights_batched=True,
                    acc_batched=True,
                ),
                pb, wb, accb,
            )
            t0 = time.perf_counter()
            exe = jitted.lower(pb, wb, accb, extra, base, cfg, True, True).compile()
            self._executables[cache_key] = exe
            self.metrics.observe_cache(hit=False, compile_s=time.perf_counter() - t0)
        else:
            self.metrics.observe_cache(hit=True)
        return exe

    def warmup(self, example_params) -> None:
        """Pre-compile executables for the buckets the given example scenarios
        land in (serving warm-up, so first requests don't pay compile time).

        With ``pad_batch=True`` (default) every flush uses ``max_batch`` slots,
        so one compile per bucket covers steady state. With ``pad_batch=False``
        the slot count follows the observed batch size and only single-request
        flushes are prewarmed — larger batches still trace on first sight
        (that recompile churn is why ``pad_batch=False`` is not the default).
        """
        seen: dict[tuple, SystemParams] = {}
        for p in example_params:
            padded = self._pad(p)
            seen.setdefault(self._bucket_key(padded), padded)
        slots = self._slots(1)
        for key, padded in seen.items():
            pb = stack_params([padded] * slots)
            wb = stack_weights([Weights.ones()] * slots)
            accb = stack_accuracy([self._acc] * slots)
            self._solver(key, slots, pb, wb, accb)
            if self.cfg.warmstart is not None:
                # pre-compile the warm-refine program(s) too (a placeholder
                # entry fixes the shapes; contents are irrelevant to tracing)
                dummy = CacheEntry(
                    f=0.5 * np.asarray(padded.f_max, dtype=np.float32),
                    P=np.zeros((padded.N, padded.K), dtype=np.float32),
                    X=np.zeros((padded.N, padded.K), dtype=np.float32),
                    objective=float("nan"),
                )
                extra = batch_starts(
                    [dummy] + [None] * (slots - 1), [padded] * slots
                )
                self._refiner(key, slots, pb, wb, accb, extra)
                top_k = self.cfg.warmstart.top_k
                if top_k > 1:
                    # top-k flushes run the (B, top_k)-candidate program
                    extra_k = batch_starts(
                        [[dummy] * top_k] + [None] * (slots - 1),
                        [padded] * slots,
                        k=top_k,
                    )
                    self._refiner(key, slots, pb, wb, accb, extra_k)

    # -- flushing ------------------------------------------------------------

    def _flush_bucket(self, key: tuple, now: float) -> tuple[list[Completion], float]:
        pending = self.batcher.pop(key)
        n_real = len(pending)
        slots = self._slots(n_real)
        # pad the batch axis by replicating the last request: same shape ->
        # same executable; replicas are solved and discarded
        filled = pending + [pending[-1]] * (slots - n_real)
        pb = stack_params([r.padded for r in filled])
        wb = stack_weights([r.weights for r in filled])
        # each row rides ITS OWN A(rho) fit (stamped at `prepare`) as one row
        # of the stacked runtime accuracy argument — mixed-tenant co-batching
        # solves and scores every request under its own belief
        accb = stack_accuracy(
            [r.accuracy if r.accuracy is not None else self._acc for r in filled]
        )
        exe = self._solver(key, slots, pb, wb, accb)
        # one ExtraStart batch for the flush iff ANY rider has a warm start
        # (`batch_starts` returns None otherwise): a hitless flush runs the
        # UNCHANGED cold executable only — the cold==disabled equivalence row
        # holds per flush, not just per service
        extra = batch_starts(
            [r.warm_start for r in filled],
            [r.padded for r in filled],
            k=self.cfg.warmstart.top_k if self.cfg.warmstart is not None else None,
        )
        if extra is not None:
            refine = self._refiner(key, slots, pb, wb, accb, extra)
            extra = self._place_extra(extra)
        pb, wb, accb = self._place(pb, wb, accb)
        t0 = time.perf_counter()
        if extra is None:
            res = jax.block_until_ready(exe(pb, wb, accb))
        else:
            base = exe(pb, wb, accb)
            res = jax.block_until_ready(refine(pb, wb, accb, extra, base))
        solve_s = time.perf_counter() - t0
        self.metrics.observe_batch(n_real, slots, solve_s)
        # score the padded batch through the batched kernel in one fused call
        # (outside solve_s: diagnostics, not solver latency) — under the same
        # per-row fits the rows were SOLVED with, so a `set_accuracy` racing
        # an in-flight flush can never mis-report `Completion.objective`
        objs = (
            np.asarray(self._score(pb, wb, res.alloc, accb))
            if self.cfg.score_objective
            else None
        )

        # convergence traces for the iteration-savings metric (host copy once
        # per flush, only when warm starts are in play on this service)
        traces = (
            np.asarray(res.trace)
            if (self.cfg.warmstart is not None or extra is not None)
            else None
        )
        iters_rtol = (
            self.cfg.warmstart.iters_rtol
            if self.cfg.warmstart is not None
            else WarmStartConfig().iters_rtol
        )

        out = []
        for i, req in enumerate(pending):
            alloc = unpad_alloc(
                tree_index(res.alloc, i), req.params.N, req.params.K
            )
            obj = float(objs[i]) if objs is not None else None
            # record the hardened solution for future requests under this
            # signature (exact shape: one entry serves every covering bucket)
            if self.warm_cache is not None and req.warm_sig is not None:
                self.warm_cache.put(req.warm_sig, entry_from_alloc(alloc, obj))
            if traces is not None:
                self.metrics.observe_warm(
                    hit=req.warm_start is not None,
                    iters=iters_to_converge(traces[i], iters_rtol),
                )
            wait = now - req.arrival_t
            latency = wait + solve_s
            self.metrics.observe_completion(latency, wait)
            out.append(
                Completion(
                    req_id=req.req_id,
                    alloc=alloc,
                    bucket=(key[0], key[1]),
                    latency_s=latency,
                    wait_s=wait,
                    solve_s=solve_s,
                    objective=obj,
                    warm_hit=req.warm_start is not None,
                    warm_start=req.warm_start,
                )
            )
        return out, solve_s

    def _flush_while(self, select, now: float) -> tuple[list[Completion], float]:
        """Flush buckets returned by ``select()`` until none qualify. A queue
        deeper than ``max_batch`` (burst arrivals) flushes in successive
        batches; ``select`` is re-evaluated after every round."""
        completions: list[Completion] = []
        busy = 0.0
        while True:
            keys = select()
            if not keys:
                return completions, busy
            for key in keys:
                # single-server semantics: batches run back-to-back, so
                # requests in a later bucket also wait out earlier solves
                done, solve_s = self._flush_bucket(key, now + busy)
                completions.extend(done)
                busy += solve_s

    def flush_full(self, now: float) -> tuple[list[Completion], float]:
        """Flush buckets that reached ``max_batch``. Returns (completions,
        busy seconds spent solving)."""
        return self._flush_while(self.batcher.full_keys, now)

    def flush_due(self, now: float) -> tuple[list[Completion], float]:
        """Flush buckets that are full or whose oldest request waited out
        ``max_wait_s`` by ``now``."""
        return self._flush_while(lambda: self.batcher.due_keys(now), now)

    def drain(self, now: float) -> tuple[list[Completion], float]:
        """Flush everything (shutdown / end of load run)."""
        return self._flush_while(self.batcher.keys, now)
