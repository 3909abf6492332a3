"""Spans and timing records of the served path, on the CPU.

The served path times itself with `repro.serve.metrics.span`: each span's
duration lands on `PendingRequest`/`Completion` fields and one `FlushTiming`
per flush, and under a `jax.profiler` session the same spans are host events
of the trace, by fixed names a trace reduction matches. These tests check
the records (through the real-clock driver and the virtual-clock replay),
the spans' names, nesting and ids in a CPU trace, and the score program's
stable name. No assertion is made on a duration's size.
"""
import pathlib
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.core import (
    AllocatorConfig,
    Weights,
    pad_params,
    sample_params,
    sample_request_stream,
    stack_params,
    stack_weights,
)
from repro.core.accuracy import default_accuracy, stack_accuracy
from repro.core.pgd import PGDConfig
from repro.core.types import Allocation, ShapeBucket
from repro.serve import (
    AllocService,
    BatchPolicy,
    FlushTiming,
    RealClockDriver,
    ServeConfig,
    WarmStartConfig,
    run_load,
    same_hardened_assignments,
    span,
)

WAIT_S = 120.0
TINY = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=40))
CFG = ServeConfig(
    policy=BatchPolicy(max_batch=2, max_wait_s=0.01),
    buckets=(ShapeBucket(4, 8),),
    allocator=TINY,
)
#: rounding of a difference of perf_counter readings
EPS = 1e-9

#: child span -> the span it nests in, on one thread
CALLER = {"alloc.prepare": "alloc.submit", "alloc.enqueue": "alloc.submit",
          "alloc.pad": "alloc.prepare", "alloc.warm_lookup": "alloc.prepare"}
FLUSH = {f"flush.{k}": "alloc.flush"
         for k in ("stack", "solve", "score", "unpad", "record")}
SOLVER_TOP = ("alloc.idle", "alloc.admit", "alloc.flush", "alloc.resolve")


def _stream(n=6, seed=7):
    return sample_request_stream(jax.random.PRNGKey(seed), n, sizes=((3, 8), (4, 8)))


@pytest.fixture(scope="module")
def executables():
    """One compiled-program cache for the module's services."""
    service = AllocService(CFG)
    service.warmup(_stream())
    return service.executables


def _served(cfg, executables, requests):
    service = AllocService(cfg, executables=executables)
    with RealClockDriver(service) as driver:
        futures = [driver.submit(p) for p in requests]
        done = [f.result(timeout=WAIT_S) for f in futures]
    return done, driver.summary()


def test_driver_completions_carry_timings(executables):
    requests = _stream(7)
    done, summary = _served(CFG, executables, requests)

    by_flush = defaultdict(list)
    for c in done:
        assert c.prepare_s >= 0.0 and c.inbox_s >= 0.0
        assert c.inbox_s <= c.wait_s + EPS          # inbox dwell is part of the wait
        f = c.flush
        assert isinstance(f, FlushTiming)
        parts = f.stack_s + f.score_s + f.unpad_s + f.record_s
        assert min(f.stack_s, c.solve_s, f.score_s, f.unpad_s, f.record_s) >= 0.0
        assert parts <= f.host_s + EPS  # children within the flush's wall
        # the stack and the unpad each lie inside ``alloc.flush``'s wall
        assert max(f.stack_s, f.unpad_s) <= f.host_s + c.solve_s + EPS
        by_flush[f.flush_id].append(c)
    for fid, group in by_flush.items():
        assert len({id(c.flush) for c in group}) == 1   # one record per flush
        assert group[0].flush.n_real == len(group)
        assert group[0].flush.slots == CFG.policy.max_batch
    assert sum(len(g) for g in by_flush.values()) == len(requests)

    assert summary["prepare_p50_s"] >= 0.0 and summary["inbox_p50_s"] >= 0.0
    assert summary["flush_host_mean_s"] > 0.0
    assert summary["solver_idle_s"] > 0.0


def test_virtual_replay_has_no_inbox_and_same_answers(executables):
    requests = _stream()
    virtual = run_load(
        AllocService(CFG, executables=executables), requests, [0.0] * len(requests)
    )
    assert all(c.inbox_s == 0.0 for c in virtual.completions)
    assert all(c.prepare_s >= 0.0 and c.flush is not None for c in virtual.completions)
    assert virtual.summary["inbox_p50_s"] == 0.0

    real, _ = _served(CFG, executables, requests)
    assert same_hardened_assignments(real, virtual.completions)


def _host_lines(trace_dir: pathlib.Path) -> list:
    from jax.profiler import ProfileData

    files = sorted(trace_dir.rglob("*.xplane.pb"))
    assert files, f"no trace written under {trace_dir}"
    data = ProfileData.from_file(str(files[-1]))
    ours = set(CALLER) | set(CALLER.values()) | set(FLUSH) | set(SOLVER_TOP)
    lines = []
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                       for e in line.events if e.name in ours]
                if evs:
                    lines.append(evs)
    return lines


def _within(ev, parents) -> bool:
    return any(s <= ev[1] and ev[2] <= e for _, s, e, _ in parents)


def test_profiler_trace_holds_the_spans_nested(executables, tmp_path):
    cfg = CFG._replace(warmstart=WarmStartConfig())
    requests = _stream(4, seed=11)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        done, _ = _served(cfg, executables, requests)
    lines = _host_lines(tmp_path)

    names = {ev[0] for line in lines for ev in line}
    assert names >= set(CALLER) | set(CALLER.values()) | set(FLUSH) | set(SOLVER_TOP)
    for line in lines:
        by_name = defaultdict(list)
        for ev in line:
            by_name[ev[0]].append(ev)
        for child, parent in {**CALLER, **FLUSH}.items():
            for ev in by_name[child]:
                assert _within(ev, by_name[parent]), (child, parent)
        # the caller's spans and the solver's run on different threads
        assert not (by_name["alloc.submit"] and by_name["alloc.flush"])

    flushes = [ev[3] for line in lines for ev in line if ev[0] == "alloc.flush"]
    by_id = {c.flush.flush_id: c.flush for c in done}
    assert sorted(f["flush_id"] for f in flushes) == sorted(by_id)
    for f in flushes:
        rec = by_id[f["flush_id"]]
        assert (f["n_real"], f["slots"]) == (rec.n_real, rec.slots)
        ids = sorted(int(i) for i in str(f["req_ids"]).split())
        assert ids == sorted(c.req_id for c in done if c.flush.flush_id == f["flush_id"])


def test_span_returns_its_duration_without_a_profiler():
    with span("alloc.test", flush_id=1) as outer:
        with span("alloc.test.inner") as inner:
            pass
    assert 0.0 <= inner.s <= outer.s


def _flush_args(slots=8, bucket=ShapeBucket(16, 64)):
    p = pad_params(sample_params(jax.random.PRNGKey(0), N=10, K=50), bucket)
    N, K = bucket
    alloc = Allocation(
        f=np.zeros((slots, N), np.float32), P=np.zeros((slots, N, K), np.float32),
        X=np.zeros((slots, N, K), np.float32), rho=np.zeros((slots,), np.float32),
    )
    return (stack_params([p] * slots), stack_weights([Weights.ones()] * slots),
            alloc, stack_accuracy([default_accuracy()] * slots))


@pytest.mark.parametrize("sharded", [False, True])
def test_score_program_name_is_stable(sharded):
    """The trace names the score program by its function; the benchmark's
    reduction matches ``jit_batch_objectives``."""
    from repro.core import scenario_mesh, scenario_sharding
    from repro.serve.service import _score_flush, sharded_score_flush

    args = _flush_args()
    if sharded:
        mesh = scenario_mesh()
        args = jax.device_put(args, scenario_sharding(mesh))
        score = sharded_score_flush(mesh)
    else:
        score = _score_flush
    text = score.lower(*args).as_text()
    assert text.split("\n", 1)[0].startswith("module @jit_batch_objectives")
