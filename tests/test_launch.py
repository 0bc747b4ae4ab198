"""Launcher plumbing: the persistent compile-cache location and the
device-keyed roofline peaks."""
import pytest

from repro.launch import compile_cache
from repro.launch.roofline import PEAKS, Roofline, peaks_for


def test_cache_dir_honours_jax_variable(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    assert compile_cache.cache_dir() == tmp_path / "jc"


def test_cache_dir_defaults_to_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.cache_dir()
    assert path.name == ".xla-cache"
    assert (path.parent / "src" / "repro" / "launch" / "compile_cache.py").is_file()


def test_enable_leaves_the_jax_variable_to_jax(monkeypatch, tmp_path):
    import jax

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    assert compile_cache.enable_compilation_cache() == tmp_path / "jc"
    assert jax.config.jax_compilation_cache_dir == before


def test_roofline_uses_the_device_peaks():
    rf = Roofline("a", "s", "m", "TPU v5 lite", 1, 197e12, 819e9, 0.0, 197e12)
    assert rf.t_compute == pytest.approx(1.0)
    assert rf.t_memory == pytest.approx(1.0)
    assert peaks_for("TPU v5 lite") is PEAKS["TPU v5 lite"]


def test_roofline_rejects_an_unknown_device():
    with pytest.raises(ValueError, match="no published peaks"):
        Roofline("a", "s", "m", "cpu", 1, 1.0, 1.0, 0.0, 1.0)
