"""One run of one cell: set-up, the measured window, the check, the metrics.

`bench/run.py` calls `run` after it has found the chips; tests call it with
the chip check left out. Set-up is everything from process start to the
window: imports and device init, drawing the traffic, lowering and compiling
(or loading) the cell's programs, and warm-up flushes through the driver.
"""
from __future__ import annotations

import collections
import json
import shutil
import sys
import threading
import time
from typing import NamedTuple

import numpy as np

from . import check, serve, spec, trace, traffic
from .roofline import fedsem_objective
from .reference import Requests

#: how long after an open loop's last due time, or a closed loop's close, the
#: window's answers are waited for before a request counts as failed
DRAIN_S = 60.0
#: a traced run profiles this long a stretch of its window, starting this far in
TRACE_LEAD_S = 2.0
TRACE_S = 2.0


class Compiles:
    """JAX's compile events, as `jax.monitoring` reports them: each lowering
    and each backend compile or persistent-cache load, with its duration."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "compile",
    }

    def __init__(self):
        self.events = []            # (perf_counter, kind, fun_name, seconds)
        self._lock = threading.Lock()

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)

    def _on(self, event, duration, **kw):
        kind = self.EVENTS.get(event)
        if kind is not None:
            with self._lock:
                self.events.append((time.perf_counter(), kind,
                                    str(kw.get("fun_name", "?")), float(duration)))

    def between(self, t0: float, t1: float) -> list:
        with self._lock:
            return [e for e in self.events if t0 <= e[0] <= t1]


class Run(NamedTuple):
    """What a metric's reader gets: the run's records and the trace."""

    cell: spec.Cell
    slots: int
    devices: list
    setup_s: float
    window: serve.Window
    host: serve.Window          # the sends outside the profiled stretch
    traffic: traffic.Traffic
    trace: trace.Reduced | None


def _programs(events, least_s: float = 0.5) -> dict:
    """Seconds of tracing and lowering, and of compiling or loading, per
    program; programs under ``least_s`` in all are summed as one entry."""
    per = collections.defaultdict(lambda: {"trace_lower_s": 0.0, "compile_s": 0.0})
    for _, kind, name, s in events:
        name = name.removeprefix("jit(").removesuffix(")")
        per[name]["compile_s" if kind == "compile" else "trace_lower_s"] += s
    out = {"small_programs": {"count": 0, "trace_lower_s": 0.0, "compile_s": 0.0}}
    for name, v in per.items():
        if v["trace_lower_s"] + v["compile_s"] >= least_s:
            out[name] = v
        else:
            small = out["small_programs"]
            small["count"] += 1
            small["trace_lower_s"] += v["trace_lower_s"]
            small["compile_s"] += v["compile_s"]
    return out


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool, devices: list,
        t_start: float, executables: dict | None = None) -> dict:
    """Run ``cell`` once; returns the result line's dict, the earlier line's,
    and the window's answers (for the control's readings)."""
    with Compiles() as compiles:
        return _run(cell, seed, seconds, traced, devices, t_start, executables, compiles)


def _run(cell, seed, seconds, traced, devices, t_start, executables, compiles) -> dict:
    import jax

    dep, mix = cell.dep, cell.mix
    split = {"imports_and_device_init_s": time.perf_counter() - t_start}

    t = time.perf_counter()
    n_slots = serve.slots(dep, cell.chips)
    tr = traffic.make(dep, mix, seed, seconds, n_slots)
    warm_params = serve.requests(dep, tr.warm)
    window_params = serve.requests(dep, tr.window)
    w = serve.weights(dep)
    split["traffic_s"] = time.perf_counter() - t

    t = time.perf_counter()
    svc = serve.service(dep, executables)
    svc.warmup(warm_params[:1])
    t_w = time.perf_counter()
    split["programs_s"] = _programs(compiles.between(t, t_w))
    driver = serve.program()[1].RealClockDriver(svc)
    try:
        serve.warm_up(driver, warm_params, w, timeout=600.0)
        t_win = time.perf_counter()
        split["warmup_flushes_s"] = t_win - t_w
        split["warmup_compiles"] = _programs(compiles.between(t_w, t_win))
        setup_s = t_win - t_start

        annotate = None
        reduced = None
        prof = None
        span: list = []
        if traced:
            annotate = jax.profiler.TraceAnnotation
            trace_dir = spec.ROOT / ".bench_trace" / cell.name
            shutil.rmtree(trace_dir, ignore_errors=True)
            lead = min(TRACE_LEAD_S, seconds / 4)
            length = min(TRACE_S, seconds - 2 * lead)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # Python calls would swamp the host's spans
            prof = serve.start_stop(
                lambda: jax.profiler.start_trace(str(trace_dir), profiler_options=opts),
                jax.profiler.stop_trace,
                time.perf_counter() + lead, length, span)
        if tr.closed:
            win = serve.closed_loop(driver, window_params, tr.clients, w, seconds,
                                    DRAIN_S, annotate)
        else:
            win = serve.open_loop(driver, window_params, tr.due, w, seconds,
                                  DRAIN_S, annotate)
        t_end = time.perf_counter()
        if prof is not None:
            prof.join()
    finally:
        driver.close(timeout=600.0)
    in_window = compiles.between(win.t0, t_end)

    used = devices[: cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used)
    if traced:
        shapes = fedsem_objective.Shapes.of(dep, n_slots)
        reduced = trace.reduce_dir(trace_dir, len(used), used[0].device_kind, shapes)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the check, after the window, the peak read and the driver closed
    index, ans, reported = serve.answers(win, dep)
    unanswered = sum(e is not None for e in win.errors)
    req = Requests(tr.window.g[index], tr.window.c[index])
    values = check.readings(dep, req, ans, reported, unanswered)
    correct, shown = check.judge(values, dep["correct"])

    host = win.outside(*span) if len(span) == 2 else win
    run_ = Run(cell, n_slots, used, setup_s, win, host, tr, reduced)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = spec.reader(m["name"])(run_)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    late = win.sent - win.due
    setup_line = {
        "setup_s": setup_s,
        "setup_split": split,
        "compiles_in_window": sum(1 for e in in_window if e[1] == "compile"),
        "compiled_in_window": sorted({e[2] for e in in_window if e[1] == "compile"}),
        "generator_late_ms": {
            "p50": float(np.median(late) * 1e3) if len(late) else None,
            "max": float(np.max(late) * 1e3) if len(late) else None,
        },
        "sent": len(win.index),
    }
    if reduced is not None:
        setup_line.update(reduced.notes)
        setup_line["host_metrics_over"] = len(host.index)
    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": int(peak),
    }
    result = {
        "correct": correct,
        "attempted": len(win.index),
        "failed": unanswered,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown
    result["checks"] = shown
    return {"result": result, "setup": setup_line,
            "answers": (req, ans, reported, unanswered)}


def emit(out: dict) -> None:
    """The earlier line, the checks on standard error, the result line last."""
    print(json.dumps({"setup": out["setup"]}), flush=True)
    for name, c in out["result"]["checks"].items():
        side, lim = next((k, v) for k, v in c.items() if k != "value")
        print(f"check {name}: {c['value']!r} ({side} {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out["result"]), flush=True)
