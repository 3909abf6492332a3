"""Reduction of a profiler trace to the benchmark's device metrics.

Reads the ``.xplane.pb`` that `jax.profiler` writes, with nothing but JAX
(`jax.profiler.ProfileData`), and gives:

* the traced window (the profiler session's host span) and, per chip, the
  device's busy time: the union of the intervals in which an operation ran;
* device time and run count per program, matched by the program names the
  trace shows for the solve, refine and score executables;
* the objective kernel's device time and its roofline share, from the
  operations and bytes `bench/roofline/fedsem_objective.py` counts for each
  call at the problem's logical shapes, and the chip's peaks
  (`bench/peaks.py`);
* the longest idle gaps, each labelled by the harness's host span that
  covers it (``bench.submit``) or "no host span".
"""
from __future__ import annotations

import pathlib
import re
from typing import NamedTuple

import numpy as np

from . import peaks as peaks_mod
from .roofline import fedsem_objective

#: programs by the name the trace gives each run of a compiled program on
#: the "XLA Modules" line: ``jit_<function>(<hash>)``
PROGRAMS = {
    "solve": re.compile(r"^jit__solve_batch_impl\("),
    "refine": re.compile(r"^jit__refine_batch_impl\("),
    "score": re.compile(r"^jit__unknown\(|^jit_batch_objectives\("),
}
#: the objective kernel's runs on the "XLA Ops" line, named by their HLO
#: text: a custom call whose instruction carries the kernel's name
#: (``%objective_batch_pallas.1 = ... custom-call(...)``, or
#: ``%vmap_jit_objective_batch_pallas__.2`` where the call is batched);
#: the programs' other custom calls (gather indices) do not
KERNEL = re.compile(r"^%?[\w.]*objective_batch_pallas[\w.]* = .*? custom-call\(")
HOST_SPAN = "bench.submit"
TOP = 10


class Reduced(NamedTuple):
    window_s: float
    busy_s: float                      # mean over chips
    program_s: dict                    # name -> device seconds, mean over chips
    program_runs: dict                 # name -> runs, mean over chips
    solve_ms_per_flush: float
    kernel_s: float                    # mean over chips
    kernel_roofline_pct: float
    breakdown: dict
    notes: dict


def union_length(intervals: np.ndarray, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals, clipped to [lo, hi)."""
    if not len(intervals):
        return 0.0
    iv = np.clip(np.asarray(intervals, np.float64), lo, hi)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    total, cur_s, cur_e = 0.0, iv[0, 0], iv[0, 1]
    for s, e in iv[1:]:
        if s > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    return float(total + cur_e - cur_s)


def gaps(intervals: np.ndarray, lo: float, hi: float) -> list:
    """Idle stretches [start, end) of [lo, hi) not covered by ``intervals``."""
    out, t = [], lo
    iv = np.asarray(intervals, np.float64).reshape(-1, 2)
    for s, e in iv[np.argsort(iv[:, 0], kind="stable")]:
        if s > t:
            out.append((float(t), float(min(s, hi))))
        t = max(t, e)
    if t < hi:
        out.append((float(t), float(hi)))
    return [(s, e) for s, e in out if e > s]


def _program(name: str) -> str:
    for label, pat in PROGRAMS.items():
        if pat.search(name):
            return label
    return re.sub(r"\(\d+\)$", "", name)


def _device_planes(data, n_devices: int) -> list:
    planes = [p for p in data.planes if re.fullmatch(r"/device:TPU:\d+", p.name)]
    planes.sort(key=lambda p: int(p.name.rsplit(":", 1)[1]))
    if len(planes) < n_devices:
        raise RuntimeError(f"the trace holds {len(planes)} TPU planes, the cell uses {n_devices}")
    return planes[:n_devices]


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def reduce_dir(trace_dir, n_devices: int, device_kind: str,
               shapes: fedsem_objective.Shapes) -> Reduced:
    import jax

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise RuntimeError(f"no trace written under {trace_dir}")
    return reduce(jax.profiler.ProfileData.from_file(str(files[-1])), n_devices,
                  device_kind, shapes)


def _kernel_calls(modules, ops) -> tuple[list, float, float]:
    """The kernel's calls inside whole program runs: [(program, index of the
    call in its run, calls in the run, seconds)], and the span of the ops
    line. A run the ops line does not cover from start to end (the trace
    began or stopped inside it, or the profiler dropped the line's later
    events) is left out."""
    mods = sorted(((ev.start_ns, ev.end_ns, _program(ev.name)) for ev in modules),
                  key=lambda m: m[0])
    calls = sorted((ev.start_ns, ev.duration_ns * 1e-9) for ev in ops
                   if KERNEL.search(ev.name))
    if not mods or not calls:
        return [], 0.0, 0.0
    op_lo = min(ev.start_ns for ev in ops)
    op_hi = max(ev.end_ns for ev in ops)
    starts = np.array([m[0] for m in mods])
    runs: dict = {}
    for t, secs in calls:
        j = int(np.searchsorted(starts, t, side="right")) - 1
        if j < 0 or t >= mods[j][1]:
            continue
        runs.setdefault(j, []).append(secs)
    out = []
    for j, secs in sorted(runs.items()):
        s, e, label = mods[j]
        if s < op_lo or e > op_hi:
            continue
        out.extend((label, i, len(secs), v) for i, v in enumerate(secs))
    return out, op_lo, op_hi


def reduce(data, n_devices: int, device_kind: str,
           shapes: fedsem_objective.Shapes) -> Reduced:
    """The device metrics of a trace of a run on ``n_devices`` chips.

    The kernel's operations and bytes are counted at the problem's logical
    shapes (``shapes``: the flush's slots, the deployment's devices and the
    candidates each call site scores), never at the padded operand shapes of
    the compiled call, so the count does not move when the padding does.
    Raises where the trace shows no run of the solve program or no call of
    the kernel: the names the reduction matches no longer fit the program.
    """
    chip = peaks_mod.peaks(device_kind)
    # the traced window: the profiler session, from the first event on any
    # line (each line is in time order) to the last end on the host's lines
    # and the chips' lines
    lo, hi = np.inf, -np.inf
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                lo = min(lo, ev.start_ns)
                break
    host_spans = []
    per_dev = []
    for plane in data.planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    hi = max(hi, ev.end_ns)
                    if ev.name == HOST_SPAN:
                        host_spans.append((ev.start_ns, ev.end_ns))
    for plane in _device_planes(data, n_devices):
        modules = _line(plane, "XLA Modules")
        modules = list(modules.events) if modules is not None else []
        runs, secs, iv, names = {}, {}, [], []
        for ev in modules:
            label = _program(ev.name)
            runs[label] = runs.get(label, 0) + 1
            secs[label] = secs.get(label, 0.0) + ev.duration_ns * 1e-9
            iv.append((ev.start_ns, ev.end_ns))
            names.append(label)
            hi = max(hi, ev.end_ns)
        ops = _line(plane, "XLA Ops")
        ops = list(ops.events) if ops is not None else []
        calls, _, op_hi = _kernel_calls(modules, ops)
        hi = max(hi, op_hi)
        k_s, k_least = 0.0, 0.0
        bound = {"bytes": 0, "ops": 0}
        per_run: dict = {}
        for label, i, n, v in calls:
            G = shapes.candidates(label, last=i == n - 1)
            n_ops, n_bytes = fedsem_objective.counts(shapes.B, shapes.N, G)
            t_ops, t_bytes = n_ops / chip.flops, n_bytes / chip.hbm_bw
            bound["bytes" if t_bytes >= t_ops else "ops"] += 1
            k_least += max(t_ops, t_bytes)
            k_s += v
            if i == 0:
                per_run.setdefault(label, set()).add(n)
        per_dev.append((runs, secs, np.asarray(iv, np.float64).reshape(-1, 2), names,
                        k_s, k_least, bound, per_run))
    if not np.isfinite(lo) or not hi > lo:
        raise RuntimeError("the trace holds no events")
    if not any(d[0].get("solve") for d in per_dev):
        raise RuntimeError("no run of the solve program in the trace; programs seen: "
                           f"{sorted({k for d in per_dev for k in d[0]})[:20]}")
    if not any(d[4] > 0 for d in per_dev):
        raise RuntimeError("no call of the objective kernel inside a whole program "
                           "run in the trace")
    window_s = (hi - lo) * 1e-9
    busy = [union_length(d[2], lo, hi) * 1e-9 for d in per_dev]
    programs = sorted({k for d in per_dev for k in d[0]})
    program_s = {k: float(np.mean([d[1].get(k, 0.0) for d in per_dev])) for k in programs}
    program_runs = {k: float(np.mean([d[0].get(k, 0) for d in per_dev])) for k in programs}
    flushes = program_runs.get("solve", 0.0)
    solve_ms = (program_s.get("solve", 0.0) + program_s.get("refine", 0.0)) / flushes * 1e3
    kernel_s = float(np.mean([d[4] for d in per_dev]))
    least = float(np.mean([d[5] for d in per_dev]))
    roofline = 100.0 * least / kernel_s
    by_bytes = sum(d[6]["bytes"] for d in per_dev)
    by_ops = sum(d[6]["ops"] for d in per_dev)
    calls_per_run: dict = {}
    for d in per_dev:
        for k, v in d[7].items():
            calls_per_run.setdefault(k, set()).update(v)

    # the longest idle stretches of the first chip, named by the programs on
    # either side and by whether a host span of the harness covers them
    dev0 = per_dev[0]
    order = np.argsort(dev0[2][:, 0], kind="stable") if len(dev0[2]) else []
    spans = np.asarray(host_spans, np.float64).reshape(-1, 2)
    idle = []
    prev = "window start"
    t = lo
    seq = [(dev0[2][i, 0], dev0[2][i, 1], dev0[3][i]) for i in order] + [(hi, hi, "window end")]
    for s, e, name in seq:
        if s > t:
            covered = bool(len(spans)) and bool(np.any((spans[:, 0] < s) & (spans[:, 1] > t)))
            label = f"{prev} -> {name}: " + (HOST_SPAN if covered else "no host span")
            idle.append((label, (s - t) * 1e-9))
        if e >= t:
            t, prev = e, name
    idle.sort(key=lambda x: -x[1])
    ops_top = sorted(program_s.items(), key=lambda kv: -kv[1])
    ops_top = [[k, v] for k, v in ops_top[: TOP - 1]]
    ops_top.append(["fedsem_objective kernel (inside solve, refine, score)", kernel_s])
    ops_top.sort(key=lambda kv: -kv[1])
    breakdown = {"device_ops": ops_top[:TOP], "idle_gaps": [[k, v] for k, v in idle[:TOP]]}
    notes = {"roofline_bound": "bytes" if by_bytes >= by_ops else "ops",
             "kernel_calls_per_run": {k: sorted(v) for k, v in calls_per_run.items()}}
    return Reduced(window_s, float(np.mean(busy)), program_s, program_runs, solve_ms,
                   kernel_s, roofline, breakdown, notes)
