"""Start-up plumbing shared by the entry points: per-chip peaks keyed by
``device_kind``, the persistent compile cache's location, and the chip smoke
run's refusal to run anywhere but on a TPU."""
import importlib.util
import pathlib

import pytest

from repro.launch import compile_cache
from repro.launch.mesh import PEAKS, chip_peaks
from repro.launch.roofline import roofline

REPO = pathlib.Path(__file__).resolve().parents[1]


def test_v5e_peaks_are_the_published_ones():
    v5e = chip_peaks("TPU v5 lite")
    assert v5e.flops_bf16 == 197e12
    assert v5e.hbm_bw == 819e9
    assert v5e.hbm_bytes == 16 * 1024**3
    assert set(PEAKS) == {"TPU v5 lite"}


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks(kind)


def test_roofline_reads_the_kind_peaks():
    """One second of each resource on a v5e: every term is exactly 1 s."""
    rl = roofline({"flops": 197e12, "bytes accessed": 819e9}, {},
                  {"total": 50e9}, "TPU v5 lite")
    assert (rl["compute_s"], rl["memory_s"], rl["collective_s"]) == (1.0, 1.0, 1.0)
    with pytest.raises(ValueError):
        roofline({}, {}, {}, "cpu")


def test_compile_cache_leaves_a_set_directory_to_jax(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert updates == []


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    updates = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: updates.append(a))
    path = compile_cache.setup_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", path)]
    assert compile_cache.setup_compile_cache() == path   # same path every run


def test_chip_smoke_refuses_to_run_without_a_tpu():
    spec = importlib.util.spec_from_file_location("chip_smoke", REPO / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    with pytest.raises(SystemExit, match="no TPU found"):
        chip_smoke.require_tpu(1)
