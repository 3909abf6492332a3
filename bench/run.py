"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``. The run exits
nonzero, and prints no result, where JAX finds no TPU or fewer chips than
the cell asks for. Its last line on standard output is the result, one JSON
object; the line before it splits the set-up time.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def cache_dir() -> None:
    """Compiled programs stay in one fixed directory inside the checkout; the
    program takes the directory it is given (`launch/compile_cache.py`).
    Called before JAX is imported, which reads it then."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache" / "bench")


def setup_jax() -> None:
    import jax

    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    # every program of the run, however small, is kept for the next run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def chips(n: int) -> list:
    """The TPU devices, or exit nonzero: there is no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX sees {devices[0].platform!r})")
    if len(devices) < n:
        raise SystemExit(f"bench: the cell needs {n} chips, found {len(devices)}")
    return devices


def main(argv=None) -> None:
    args = parse(argv)
    cache_dir()
    from bench import harness, spec

    cell = spec.cell(spec.load(), args.workload)
    devices = chips(cell.chips)
    setup_jax()
    out = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    harness.emit(out)


if __name__ == "__main__":
    main()
