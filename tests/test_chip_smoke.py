"""chip_smoke.py off the chip: its workload at tiny size on the CPU
(kernels in interpret mode), its refusals, and the compile-cache rule."""
import os
import sys
from pathlib import Path

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.api import LocalBackend  # noqa: E402
from repro.jax_cache import CHECKOUT_CACHE, enable_compile_cache  # noqa: E402


def _tiny_backend():
    return LocalBackend(chip_smoke.smoke_config(400))


def test_workload_agrees_with_oracle_on_cpu():
    lines = []
    counts = chip_smoke.run_workload(_tiny_backend(), n_keys=400, n_ops=400,
                                     n_scans=10, log=lines.append)
    assert any("load: 400 ops" in ln and "0 oracle mismatches" in ln
               for ln in lines)
    assert any("mix: 400 ops" in ln and "0 oracle mismatches" in ln
               for ln in lines)
    assert any(ln.startswith("scan: 10 scans") for ln in lines)
    assert counts["blk_hits"] > 0
    assert counts["items_scanned"] > 0
    assert counts["move_hits"] > 0       # the balancer moved sublists


def test_workload_catches_a_wrong_result():
    """The referee is not vacuous: one flipped completion fails the run."""
    backend = _tiny_backend()
    step = backend.step
    flipped = []

    def bad_step():
        comps = step()
        if comps and not flipped:
            op_id, val, src = comps[0]
            flipped.append(op_id)
            comps[0] = (op_id, 1 - val, src)
        return comps

    backend.step = bad_step
    with pytest.raises(chip_smoke.SmokeFailure, match="load"):
        chip_smoke.run_workload(backend, n_keys=200, n_ops=0, n_scans=0,
                                log=lambda _: None)
    assert flipped


def test_main_refuses_cpu(monkeypatch, capsys):
    monkeypatch.delenv("REPRO_INTERPRET", raising=False)
    assert jax.devices()[0].platform == "cpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "no TPU" in out


def test_main_refuses_interpret_override(monkeypatch, capsys):
    monkeypatch.setenv("REPRO_INTERPRET", "0")
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out
    assert "REPRO_INTERPRET" in out


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_env_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(CHECKOUT_CACHE)
    assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE)
    assert CHECKOUT_CACHE.parent == Path(ROOT)
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
