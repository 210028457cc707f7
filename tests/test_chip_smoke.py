"""``chip_smoke.py`` at a tiny size on the CPU (interpret mode): its refusal
to run without a TPU, and both phases end to end with every check they
make on the chip.  Also the compile-cache helper its entry points call."""
from pathlib import Path

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch.cache import CHECKOUT, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def test_refuses_to_run_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    assert capsys.readouterr().out == ""        # no phase, no result line


def test_store_phase_at_tiny_size(smoke):
    # t: two interior chunks and an 84-row edge chunk (one block of all
    # its rows); z: one auto chunk of 1021 rows, quantised to 1016
    r = smoke.phase_store(seed=3, levels=3, points=2 * 16_384 + 10_800,
                          t_chunks=16_384)
    assert r["requests"] == 8 and r["serve_errors"] == 0
    assert r["chunks"] == {"t": [1, 16_384], "z": [3, 43_568]}
    for check in r["checks"].values():
        assert check["worst_err_over_bound"] <= 1
    assert r["stored_bytes"] < r["raw_bytes"]


def test_ckpt_phase_at_tiny_size(smoke):
    cfg = get_smoke_config("tinyllama-1.1b")
    r = smoke.phase_ckpt(seed=3, cfg=cfg, new_tokens=4, n_requests=2)
    assert r["requests"] == 2 and r["new_tokens"] == 8
    assert 0 < r["field8_tensors"] < r["tensors"]
    assert r["stored_bytes"] < r["raw_bytes"]


@pytest.fixture
def cache_config():
    keys = ("jax_compilation_cache_dir",)
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(CHECKOUT / ".jax_cache") == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path


def test_compile_cache_env_var_wins(monkeypatch, cache_config, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no other directory
    assert jax.config.jax_compilation_cache_dir is None
