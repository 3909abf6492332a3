"""The benchmark harness on the CPU: trace reduction, kernel counts, cell
resolution, traffic, the chip check, and the comparison that decides
``correct`` against its control and planted faults.

Runs never reach a chip here: `bench.harness.run` is called with the CPU's
devices at a small deployment, which is what `bench/run.py` refuses to do.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import channel, check, faults, harness, spec, trace, traffic  # noqa: E402
from bench.roofline import fedsem_objective  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
BENCH = spec.load(ROOT)


# -- trace reduction -----------------------------------------------------------


def test_union_and_gaps_by_hand():
    iv = np.array([[0.0, 2.0], [1.0, 3.0], [5.0, 6.0], [5.5, 5.8]])
    assert trace.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert trace.union_length(iv, 1.5, 5.5) == pytest.approx(2.0)
    assert trace.gaps(iv, 0.0, 10.0) == [(3.0, 5.0), (6.0, 10.0)]


def _synthetic_trace(operand="f32[8,16,128]", solve="jit__solve_batch_impl(123)",
                     kernel=None):
    """A two-chip trace built by hand: on each chip a solve run over [0, 10)
    ms and a score run over [12, 13) ms, each holding one kernel call of
    2 us; on the host a submit span over [10.5, 11) ms and a thread busy over
    [0, 20) ms."""
    from jax.profiler import ProfileData

    cc = kernel or (f"%objective_batch_pallas.1 = f32[8,1,128]{{2,1,0}} custom-call("
                    f"{operand}{{2,1,0}} %a, {operand}{{2,1,0}} %b), "
                    f"custom_call_target=tpu_custom_call")
    def dev(i):
        return f"""planes {{ id: {i + 1} name: "/device:TPU:{i}"
          lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0
            events {{ metadata_id: 1 offset_ps: 0 duration_ps: 10000000000 }}
            events {{ metadata_id: 2 offset_ps: 12000000000 duration_ps: 1000000000 }} }}
          lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0
            events {{ metadata_id: 4 offset_ps: 0 duration_ps: 1000000 }}
            events {{ metadata_id: 3 offset_ps: 1000000000 duration_ps: 2000000 }}
            events {{ metadata_id: 3 offset_ps: 12000000000 duration_ps: 2000000 }}
            events {{ metadata_id: 4 offset_ps: 12990000000 duration_ps: 10000000 }} }}
          event_metadata {{ key: 1 value {{ id: 1 name: "{solve}" }} }}
          event_metadata {{ key: 2 value {{ id: 2 name: "jit__unknown(456)" }} }}
          event_metadata {{ key: 3 value {{ id: 3 name: "{cc}" }} }}
          event_metadata {{ key: 4 value {{ id: 4 name: "%copy.1 = f32[8]{{0}} copy(f32[8]{{0}} %x)" }} }} }}"""
    host = """planes { id: 9 name: "/host:CPU"
      lines { id: 1 name: "python" timestamp_ns: 0
        events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000000 }
        events { metadata_id: 2 offset_ps: 10500000000 duration_ps: 500000000 } }
      event_metadata { key: 1 value { id: 1 name: "main loop" } }
      event_metadata { key: 2 value { id: 2 name: "bench.submit" } } }"""
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(dev(0) + dev(1) + host))


def _shapes(N=10):
    return fedsem_objective.Shapes(B=8, N=N, select={"solve": 3, "score": 1})


def test_reduction_of_a_trace_built_by_hand():
    from bench import peaks

    r = trace.reduce(_synthetic_trace(), 2, "TPU v5 lite", _shapes())
    assert r.window_s == pytest.approx(20e-3)
    assert r.busy_s == pytest.approx(11e-3)
    assert r.program_s == pytest.approx({"solve": 10e-3, "score": 1e-3})
    assert r.program_runs == {"solve": 1.0, "score": 1.0}
    assert r.solve_ms_per_flush == pytest.approx(10.0)
    assert r.kernel_s == pytest.approx(4e-6)
    assert r.notes["kernel_calls_per_run"] == {"solve": [1], "score": [1]}
    # at the logical shapes: 8 slots, 10 devices; the solve's one call is its
    # multi-start selection over 3 candidates, the score's one candidate
    chip = peaks.peaks("TPU v5 lite")
    least = sum(max(o / chip.flops, b / chip.hbm_bw)
                for o, b in (fedsem_objective.counts(8, 10, 3),
                             fedsem_objective.counts(8, 10, 1)))
    assert r.kernel_roofline_pct == pytest.approx(100 * least / 2e-6 / 2)
    assert r.notes["roofline_bound"] == "bytes"
    assert r.breakdown["idle_gaps"] == [
        ["score -> window end: no host span", pytest.approx(7e-3)],
        ["solve -> score: bench.submit", pytest.approx(2e-3)],
    ]
    with pytest.raises(ValueError):
        trace.reduce(_synthetic_trace(), 1, "TPU v9 imaginary", _shapes())


@pytest.mark.parametrize("operand", ["f32[8,16,128]", "f32[8,10,3]", "f32[16,64,512]"])
def test_kernel_count_does_not_follow_the_padding(operand):
    """The same trace with the kernel's operands padded differently gives
    the same count: only the logical shapes enter it."""
    base = trace.reduce(_synthetic_trace(), 2, "TPU v5 lite", _shapes())
    padded = trace.reduce(_synthetic_trace(operand), 2, "TPU v5 lite", _shapes())
    assert padded.kernel_roofline_pct == base.kernel_roofline_pct


def test_reduction_raises_where_the_names_do_not_match():
    with pytest.raises(RuntimeError, match="program"):
        trace.reduce(_synthetic_trace(solve="jit_renamed_solve(1)"), 2, "TPU v5 lite",
                     _shapes())
    with pytest.raises(RuntimeError, match="objective kernel"):
        trace.reduce(_synthetic_trace(kernel="%custom-call.7 = s32[8,3]{1,0} custom-call("
                                             "%concatenate.112), custom_call_target="
                                             "GatherScatterIndicesBitpacked"), 2,
                     "TPU v5 lite", _shapes())


# -- cells resolve by name -------------------------------------------------------


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_cell_resolves_config_mix_and_metrics(name):
    cell = spec.cell(BENCH, name, ROOT)
    assert cell.dep["chips"] == cell.chips
    assert {"N", "K", "law", "serve", "correct"} <= set(cell.dep)
    assert cell.mix["arrivals"] in ("poisson", "closed")
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.reader(m["name"], ROOT))


# -- traffic -----------------------------------------------------------------------


def _dep(name="table1"):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json")))
def test_traffic_is_deterministic_in_the_seed(mix):
    dep = _dep()
    m = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json").read_text())
    m["rate_rps"] = 50.0
    m["pool"] = 32
    a = traffic.make(dep, m, 2**33 + 5, 2.0, 8)
    b = traffic.make(dep, m, 2**33 + 5, 2.0, 8)
    c = traffic.make(dep, m, 2**33 + 6, 2.0, 8)
    for x, y in ((a.window.g, b.window.g), (a.warm.c, b.warm.c)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.window.g, c.window.g)
    if not a.closed:
        np.testing.assert_array_equal(a.due, b.due)
        assert len(a.due) == len(c.due) == 100 and np.all(np.diff(a.due) >= 0)


def _gain_moments(g):
    db = 10.0 * np.log10(np.asarray(g, np.float64).mean(axis=-1))  # large scale per device
    return db.mean(), db.std(), np.log(np.asarray(g, np.float64)).mean()


def test_channel_laws_match_the_programs_scenarios():
    import jax

    from repro.scenarios import get_family

    dep = _dep()
    rng = np.random.default_rng(11)
    g_iid, c_iid = channel.iid(rng, 4000, 10, 50, dep["law"])
    prog = get_family("iid_rayleigh").sample_batch(jax.random.PRNGKey(3), 1000)
    mine, theirs = _gain_moments(g_iid), _gain_moments(np.asarray(prog.g))
    np.testing.assert_allclose(mine, theirs, atol=0.6)
    np.testing.assert_allclose(c_iid.mean(), float(np.asarray(prog.c).mean()), rtol=0.02)


# -- the chip check ------------------------------------------------------------------


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", str(2**32 + 1), "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"correct"' not in p.stdout
    assert "no TPU" in p.stderr


# -- the comparison, its control and planted faults ---------------------------------


def _small_cell(config: str, mix_name: str) -> spec.Cell:
    """A deployment and its limits, at 4 devices and 8 subcarriers. The gain
    floor is this size's own: sound runs read a median gain of 0.71-0.77 here
    on the CPU, the under-converged solver 0.58-0.61."""
    dep = _dep(config)
    dep.update(N=4, K=8, B_hz=8 * dep["B_hz"] / dep["K"])
    dep["correct"] = dict(dep["correct"], gain=0.65)
    mix = json.loads((ROOT / "bench" / "traffic" / f"{mix_name}.json").read_text())
    mix["pool"] = 64
    return spec.Cell("small", dep["chips"], config, dep, mix,
                     [{"name": "setup_s", "unit": "s"}], [])


@pytest.fixture(scope="module")
def small_run():
    import jax

    exes: dict = {}
    cell = _small_cell("table1", "closed_iid")
    out = harness.run(cell, 2**31 + 7, 3.0, False, jax.devices(), time.perf_counter(),
                      executables=exes)
    return cell, exes, out


def test_sound_small_run_is_correct(small_run):
    _, _, out = small_run
    assert out["result"]["correct"], out["result"]["checks"]
    assert out["result"]["failed"] == 0 and out["result"]["attempted"] > 16
    assert list(out["result"])[-1] == "checks"


def test_control_in_bfloat16_is_not_correct(small_run):
    cell, _, out = small_run
    req, ans, reported, unanswered = out["answers"]
    control = check.readings(cell.dep, req, ans, reported, unanswered, control=True)
    ok, shown = check.judge(control, cell.dep["correct"])
    assert not ok, shown


@pytest.mark.parametrize("fault", faults.NAMES)
def test_planted_fault_is_not_correct(small_run, fault):
    import jax

    cell, exes, _ = small_run
    with faults.planted(fault):
        out = harness.run(cell, 2**31 + 8, 2.0, False, jax.devices(), time.perf_counter(),
                          executables=exes)
    assert not out["result"]["correct"], out["result"]["checks"]
