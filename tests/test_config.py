"""Env-var configuration tier (reference: src/init_global_grid.jl:51-68)."""

import os

import pytest

import implicitglobalgrid_tpu as igg
from implicitglobalgrid_tpu.utils.config import env_config


@pytest.fixture
def clean_env():
    saved = {k: os.environ.pop(k) for k in list(os.environ) if k.startswith("IGG_")}
    yield
    for k in list(os.environ):
        if k.startswith("IGG_"):
            del os.environ[k]
    os.environ.update(saved)


def test_env_defaults_empty(clean_env):
    assert env_config() == {}


def test_env_values(clean_env):
    os.environ["IGG_QUIET"] = "1"
    os.environ["IGG_OVERLAP"] = "3"
    os.environ["IGG_REORDER"] = "0"
    os.environ["IGG_DEVICE_TYPE"] = "cpu"
    cfg = env_config()
    assert cfg == {"quiet": True, "overlap": 3, "reorder": 0, "device_type": "cpu"}


def test_env_invalid_int(clean_env):
    os.environ["IGG_OVERLAP"] = "two"
    with pytest.raises(ValueError, match="IGG_OVERLAP"):
        env_config()


def test_env_applied_at_init(clean_env):
    os.environ["IGG_OVERLAP"] = "3"
    os.environ["IGG_QUIET"] = "1"
    igg.init_global_grid(8, 8, 8)
    gg = igg.get_global_grid()
    assert gg.overlaps == (3, 3, 3)
    assert gg.quiet is True
    igg.finalize_global_grid()


def test_kwargs_override_env(clean_env):
    os.environ["IGG_OVERLAP"] = "3"
    igg.init_global_grid(8, 8, 8, overlapy=4, quiet=True)
    gg = igg.get_global_grid()
    assert gg.overlaps == (3, 4, 3)
    igg.finalize_global_grid()


def test_profile_trace(tmp_path):
    igg.init_global_grid(8, 8, 8, quiet=True)
    T = igg.zeros((8, 8, 8))
    with igg.profile_trace(tmp_path / "trace"):
        T = igg.update_halo(T)
    assert any((tmp_path / "trace").rglob("*"))  # trace files written
    igg.finalize_global_grid()


@pytest.mark.parametrize("env", [None, "", "/elsewhere/jax-cache"])
def test_compile_cache_dir_env_wins_else_fixed_checkout_path(monkeypatch, env):
    """``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache sits
    at one fixed path inside the checkout (never a temp, pid or time name),
    so the next run in the same checkout finds it.  Path choice only: the
    cache is not switched on here."""
    from implicitglobalgrid_tpu.utils import config

    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env)
    got = config.compile_cache_dir()
    if env:
        assert got == env
    else:
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert got == os.path.join(repo, ".jax_cache")
        assert got == config.compile_cache_dir()  # stable across calls


def test_tune_cache_default_is_inside_the_checkout(monkeypatch):
    from implicitglobalgrid_tpu.tuning.cache import default_cache_dir
    from implicitglobalgrid_tpu.utils.config import CHECKOUT_ROOT

    monkeypatch.delenv("IGG_TUNE_CACHE", raising=False)
    assert default_cache_dir() == os.path.join(CHECKOUT_ROOT, ".igg_tune")
    monkeypatch.setenv("IGG_TUNE_CACHE", "/x/tune")
    assert default_cache_dir() == "/x/tune"
