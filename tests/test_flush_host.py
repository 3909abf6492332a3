"""The flush's host work, on the CPU: rows stacked by one compiled program,
answers sliced on the host from one device->host copy of the batch.

The stack program must give what `stack_params`, `stack_weights` and
`stack_accuracy` give, bit for bit; an answer must equal the per-request
slice of the solved batch (`unpad_alloc(tree_index(res.alloc, i), N, K)`,
kept here as the reference), own its memory, and cost no compilation once
the service is warmed up.
"""
import dataclasses

import jax
import jax.monitoring
import numpy as np
import pytest

from repro.core import (
    AllocatorConfig,
    SystemParams,
    Weights,
    pad_params,
    sample_params,
    sample_request_stream,
    stack_params,
    stack_weights,
    tree_index,
    unpad_alloc,
)
from repro.core.accuracy import AccuracyFn, default_accuracy, stack_accuracy
from repro.core.pgd import PGDConfig
from repro.core.types import ShapeBucket
from repro.serve import AllocService, BatchPolicy, ServeConfig, WarmStartConfig
from repro.serve.service import _stack_rows

TINY = AllocatorConfig(inner="pgd", outer_iters=2, pgd=PGDConfig(steps=40))
BUCKET = ShapeBucket(4, 8)
SIZES = ((3, 8), (4, 8), (4, 6))


def _cfg(max_batch, **kw):
    return ServeConfig(
        policy=BatchPolicy(max_batch=max_batch, max_wait_s=0.01),
        buckets=(BUCKET,),
        allocator=TINY,
        **kw,
    )


def _assert_same_bits(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert (g.dtype, g.shape) == (w.dtype, w.shape)
        assert g.tobytes() == w.tobytes()


def _host_params(p: SystemParams) -> SystemParams:
    """``p`` with every array leaf a numpy array, as exact-shape rows may be."""
    return jax.tree.map(np.asarray, p)


def _rows(kind: str, n: int = 3):
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    if kind == "padded":
        params = [pad_params(sample_params(k, N=3, K=6), BUCKET) for k in keys]
    else:
        params = [_host_params(sample_params(k, N=4, K=8)) for k in keys]
    weights = [Weights.ones(), Weights(np.float32(2.0), np.float32(0.5), np.float32(1.0)),
               Weights.ones()][:n]
    accs = [default_accuracy(), AccuracyFn(np.float32(0.5), np.float32(0.3)),
            default_accuracy()][:n]
    return params, weights, accs


@pytest.mark.parametrize("kind", ["padded", "exact_numpy"])
def test_stack_program_equals_eager_stacks(kind):
    params, weights, accs = _rows(kind)
    got = _stack_rows(params, weights, accs)
    want = (stack_params(params), stack_weights(weights), stack_accuracy(accs))
    _assert_same_bits(got, want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.weak_type == w.weak_type


def test_stack_program_rejects_mixed_meta():
    params, weights, accs = _rows("padded", 2)
    params[1] = dataclasses.replace(params[1], B=params[1].B * 2)
    with pytest.raises(ValueError, match=r"static field\(s\) \['B'\]"):
        _stack_rows(params, weights, accs)


def _eager_reference(service, requests, slots):
    """The answers of one flush of ``requests`` as the per-request eager
    unpad of the solved batch gives them, from the service's own executable."""
    padded = [service._pad(p) for p in requests]
    filled = padded + [padded[-1]] * (slots - len(padded))
    pb = stack_params(filled)
    wb = stack_weights([Weights.ones()] * slots)
    accb = stack_accuracy([default_accuracy()] * slots)
    exe = service._solver(service._bucket_key(padded[0]), slots, pb, wb, accb)
    res = exe(*service._place(pb, wb, accb))
    return [unpad_alloc(tree_index(res.alloc, i), p.N, p.K)
            for i, p in enumerate(requests)]


def _one_flush(shard_batch):
    """Three requests of mixed sizes through one flush of a service with
    four slots (one device, or one slot per device of the 4-device mesh)."""
    cfg = _cfg(1 if shard_batch else 4, shard_batch=shard_batch)
    requests = sample_request_stream(jax.random.PRNGKey(5), 3, sizes=SIZES)
    service = AllocService(cfg)
    for p in requests:
        service.submit(p, now=0.0)
    done, _ = service.drain(now=0.0)
    assert len({c.flush.flush_id for c in done}) == 1
    assert done[0].flush.slots == 4
    return service, requests, done


@pytest.mark.parametrize("shard_batch", [False, True], ids=["one_device", "mesh"])
def test_answers_equal_the_eager_unpad(shard_batch):
    service, requests, done = _one_flush(shard_batch)
    ref = _eager_reference(service, requests, done[0].flush.slots)
    assert [c.req_id for c in done] == [0, 1, 2]
    for c, want, p in zip(done, ref, requests):
        _assert_same_bits(c.alloc, want)
        assert c.alloc.P.shape == (p.N, p.K)


@pytest.mark.parametrize("shard_batch", [False, True], ids=["one_device", "mesh"])
def test_answers_own_their_memory(shard_batch):
    _, _, done = _one_flush(shard_batch)
    for c in done:
        for leaf in jax.tree.leaves(c.alloc):
            assert isinstance(leaf, np.ndarray)
            assert leaf.base is None


def test_warm_cache_entries_own_their_memory():
    service = AllocService(_cfg(2, warmstart=WarmStartConfig()))
    for p in sample_request_stream(jax.random.PRNGKey(6), 2, sizes=SIZES):
        service.submit(p, now=0.0)
    service.drain(now=0.0)
    entries = list(service.warm_cache._entries.values())
    assert len(entries) == 2
    for e in entries:
        for a in (e.f, e.P, e.X):
            assert a.base is None


class _CompileEvents:
    """Counts `jax.monitoring`'s trace, lowering and compile events while on
    (the events `bench/harness.py`'s `Compiles` reads)."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.on = False
        self.seen = []

    def __call__(self, event, duration, **kw):
        if self.on and event in self.EVENTS:
            self.seen.append((event, kw.get("fun_name")))

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


@pytest.mark.parametrize(
    "shard_batch,warm", [(False, False), (False, True), (True, False)],
    ids=["one_device", "one_device_warm_cache", "mesh"],
)
def test_flushes_after_warmup_compile_nothing(shard_batch, warm):
    cfg = _cfg(2, shard_batch=shard_batch,
               warmstart=WarmStartConfig() if warm else None)
    requests = sample_request_stream(jax.random.PRNGKey(8), 6, sizes=SIZES)
    jax.clear_caches()  # what earlier tests compiled must not stand in for warmup
    service = AllocService(cfg)
    service.warmup(requests)
    # a repeat of the stream hits the warm cache: refine flushes too
    stream = requests + requests if warm else requests
    with _CompileEvents() as events:
        done = []
        for i, p in enumerate(stream):
            service.submit(p, now=float(i))
            events.on = True
            done += service.flush_full(now=float(i))[0]
            events.on = False
        events.on = True
        done += service.drain(now=float(len(stream)))[0]
        events.on = False
    assert len(done) == len(stream)
    assert not warm or any(c.warm_hit for c in done)
    assert events.seen == []
