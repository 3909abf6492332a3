"""The readers of the program's span records (`prepare_ms.lat`,
`inbox_wait_ms.lat`, `flush_host_ms.lat`, `flush_host_ms.tput`) on windows
built by hand: what each reads, that a flush counts once however many
answers it holds, and that a reader gives None where there is nothing to
read (no answers, or a program whose `Completion` has no such field).
No cell declares them yet: a traced run's `run.host` holds only the sends
before its profiled stretch (PERF.md, open questions).
"""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace
from typing import NamedTuple

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import serve, spec  # noqa: E402
from repro.serve import Completion, FlushTiming  # noqa: E402

METRICS = ("prepare_ms.lat", "inbox_wait_ms.lat", "flush_host_ms.lat",
           "flush_host_ms.tput")
FLUSH_HOST = ("flush_host_ms.lat", "flush_host_ms.tput")


def _flush(flush_id, host_s, n_real):
    return FlushTiming(flush_id=flush_id, n_real=n_real, slots=8, stack_s=0.0,
                       score_s=0.0, unpad_s=0.0, record_s=0.0, host_s=host_s)


def _done(req_id, flush, prepare_s=0.0, inbox_s=0.0):
    return Completion(req_id=req_id, alloc=None, bucket=(16, 64), latency_s=0.0,
                      wait_s=inbox_s, solve_s=0.05, prepare_s=prepare_s,
                      inbox_s=inbox_s, flush=flush)


def _window(completions) -> serve.Window:
    n = len(completions)
    z = np.zeros(n)
    return serve.Window(0.0, 1.0, np.arange(n), z, z, z, z + 0.5,
                        list(completions), [None] * n)


def _run(host, window=None):
    return SimpleNamespace(host=host, window=window if window is not None else host)


def _read(name, run):
    return spec.reader(name, ROOT)(run)


@pytest.mark.parametrize("name", FLUSH_HOST)
def test_a_flush_counts_once_however_many_answers_it_holds(name):
    big, small = _flush(0, 0.100, 3), _flush(1, 0.300, 1)
    done = [_done(0, big), _done(1, big), _done(2, big), _done(3, small)]
    # per answer the median would be 100 ms; per flush it is 200 ms
    assert _read(name, _run(_window(done))) == pytest.approx(200.0)


def test_prepare_and_inbox_medians_per_answer():
    f = _flush(0, 0.1, 3)
    done = [_done(0, f, 0.001, 0.010), _done(1, f, 0.003, 0.030),
            _done(2, f, 0.002, 0.020)]
    run = _run(_window(done))
    assert _read("prepare_ms.lat", run) == pytest.approx(2.0)
    assert _read("inbox_wait_ms.lat", run) == pytest.approx(20.0)


@pytest.mark.parametrize("name", METRICS)
def test_readers_read_the_sends_outside_the_profiled_stretch(name):
    inside = [_done(0, _flush(0, 9.0, 1), 9.0, 9.0)]
    outside = [_done(1, _flush(1, 0.004, 1), 0.004, 0.004)]
    assert _read(name, _run(_window(outside), _window(inside))) == pytest.approx(4.0)


@pytest.mark.parametrize("name", METRICS)
def test_none_without_completions(name):
    assert _read(name, _run(_window([]))) is None
    failed = _window([None, None])
    assert _read(name, _run(failed)) is None


class _Legacy(NamedTuple):
    """A `Completion` of a program that keeps no span records."""

    req_id: int
    wait_s: float


@pytest.mark.parametrize("name", METRICS)
def test_none_where_the_program_keeps_no_records(name):
    assert _read(name, _run(_window([_Legacy(0, 0.01), _Legacy(1, 0.02)]))) is None
