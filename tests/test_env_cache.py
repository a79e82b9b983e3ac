"""Compilation-cache wiring decisions (env._enable_compilation_cache):
opt-out env var, user-configured locations respected, CPU-backend skip
(cross-host AOT entries can SIGILL)."""

import jax
import pytest

import quest_tpu as qt
from quest_tpu import env as E


@pytest.fixture(autouse=True)
def _reset_wired(monkeypatch):
    monkeypatch.setattr(E, "_CACHE_WIRED", [False])
    yield


def _configured():
    return jax.config.jax_compilation_cache_dir


def test_opt_out(monkeypatch):
    monkeypatch.setenv("QT_NO_COMPILE_CACHE", "1")
    before = _configured()
    E._enable_compilation_cache()
    assert _configured() == before
    assert E._CACHE_WIRED == [False]  # may re-wire later without opt-out


def test_respects_user_jax_env_var(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/tmp/userspot")
    before = _configured()
    E._enable_compilation_cache()
    assert _configured() == before  # never overridden


def test_cpu_backend_skipped_by_default(monkeypatch):
    monkeypatch.delenv("QT_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("QT_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("QT_COMPILE_CACHE_DIR", raising=False)
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("cache already configured in this session")
    assert jax.default_backend() == "cpu"  # test harness forces CPU
    E._enable_compilation_cache()
    assert _configured() is None


def test_explicit_dir_forces_on_cpu(monkeypatch, tmp_path):
    monkeypatch.delenv("QT_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("QT_COMPILE_CACHE", raising=False)
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("cache already configured in this session")
    monkeypatch.setenv("QT_COMPILE_CACHE_DIR", str(tmp_path / "qc"))
    try:
        E._enable_compilation_cache()
        assert _configured() == str(tmp_path / "qc")
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_qt_compile_cache_var_wires_and_reports(monkeypatch, tmp_path):
    """QT_COMPILE_CACHE=<dir> (the canonical spelling; *_DIR kept as an
    alias) wires the persistent cache anywhere — including CPU — and the
    hit/miss counters surface through getEnvironmentString."""
    monkeypatch.delenv("QT_NO_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("QT_COMPILE_CACHE_DIR", raising=False)
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("cache already configured in this session")
    cache_dir = str(tmp_path / "qc2")
    monkeypatch.setenv("QT_COMPILE_CACHE", cache_dir)
    try:
        E._enable_compilation_cache()
        assert _configured() == cache_dir
        stats = E.compile_cache_stats()
        assert stats["dir"] == cache_dir
        env = qt.createQuESTEnv()
        s = qt.getEnvironmentString(env)
        assert f"CompileCache={cache_dir}" in s
        assert "hits=" in s and "misses=" in s
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
        E._CACHE_STATS["dir"] = None


def test_environment_string_reports_exchange_config(monkeypatch):
    env = qt.createQuESTEnv()
    monkeypatch.delenv("QT_EXCHANGE_CHUNKS", raising=False)
    assert "ExchangeChunks=auto" in qt.getEnvironmentString(env)
    monkeypatch.setenv("QT_EXCHANGE_CHUNKS", "4")
    assert "ExchangeChunks=4" in qt.getEnvironmentString(env)


def test_accelerator_default_is_the_checkout_dir(monkeypatch):
    """Off the CPU, with no cache named, the cache lives at the fixed
    <checkout>/.jax_cache (the directory .gitignore lists)."""
    import os

    for var in ("QT_NO_COMPILE_CACHE", "JAX_COMPILATION_CACHE_DIR",
                "QT_COMPILE_CACHE", "QT_COMPILE_CACHE_DIR"):
        monkeypatch.delenv(var, raising=False)
    if jax.config.jax_compilation_cache_dir:
        pytest.skip("cache already configured in this session")
    monkeypatch.setattr(E.jax, "default_backend", lambda: "tpu")
    made = []
    monkeypatch.setattr(E.os, "makedirs", lambda p, exist_ok: made.append(p))
    try:
        E._enable_compilation_cache()
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(E.__file__))), ".jax_cache")
        assert _configured() == want and made == [want]
        assert os.path.basename(want) in open(os.path.join(
            os.path.dirname(want), ".gitignore")).read()
    finally:
        jax.config.update("jax_compilation_cache_dir", None)
