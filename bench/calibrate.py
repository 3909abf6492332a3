"""Readings from which the check's limits are set, for one cell, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \
        [--faults state_unchanged,half_batch --fault-seeds 4,5,6 --fault-seconds 5]

For each seed it runs the cell's window as `bench/run.py` does and prints one
JSON line with the program's readings and the control's (the same answers
as a bfloat16 path would return them, scored in bfloat16). For each fault
and fault seed it runs a window with the fault planted (`bench/faults.py`)
and prints the readings and whether ``correct`` came out false. The
compiled programs are shared between the runs, so set-up is paid once.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--faults", default="")
    p.add_argument("--fault-seeds", default="")
    p.add_argument("--fault-seconds", type=float, default=None)
    args = p.parse_args(argv)

    from bench.run import cache_dir, chips, setup_jax

    cache_dir()
    from bench import check, faults, harness, spec

    cell = spec.cell(spec.load(), args.workload)
    devices = chips(cell.chips)
    setup_jax()
    exes: dict = {}
    for seed in _seeds(args.seeds):
        out = harness.run(cell, seed, args.seconds, False, devices, time.perf_counter(),
                          executables=exes)
        req, ans, reported, unanswered = out["answers"]
        control = check.readings(cell.dep, req, ans, reported, unanswered, control=True)
        print(json.dumps({"seed": seed, "correct": out["result"]["correct"],
                          "program": {k: v["value"] for k, v in out["result"]["checks"].items()},
                          "control": control, "control_correct": check.judge(control, cell.dep["correct"])[0],
                          "metrics": out["result"]["metrics"],
                          "attempted": out["result"]["attempted"]}), flush=True)
    for name in args.faults.split(","):
        if not name:
            continue
        for seed in _seeds(args.fault_seeds):
            with faults.planted(name):
                out = harness.run(cell, seed, args.fault_seconds or args.seconds, False,
                                  devices, time.perf_counter(), executables=exes)
            print(json.dumps({"fault": name, "seed": seed, "correct": out["result"]["correct"],
                              "readings": {k: v["value"] for k, v in out["result"]["checks"].items()}}),
                  flush=True)


if __name__ == "__main__":
    main()
