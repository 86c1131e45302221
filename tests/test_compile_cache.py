"""The one rule for where the persistent compile cache lives
(``utils/compile_cache.py``): env var set -> the code sets nothing; unset ->
one fixed path inside the checkout, the same on every call."""
import os

import jax
import pytest

from deepspeed_tpu.utils import compile_cache as cc


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_var_set_means_code_sets_nothing(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(cc.ENV_VAR, "/somewhere/outside")
    assert cc.configure_compile_cache() == "/somewhere/outside"
    # JAX reads the variable itself at start-up; the helper must not touch
    # the config on top of it
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_means_one_fixed_path_inside_the_checkout(
        monkeypatch, restore_cache_dir):
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    first = cc.configure_compile_cache()
    second = cc.configure_compile_cache()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(cc.__file__)))
    repo = os.path.dirname(repo)
    assert first == second == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_entry_points_call_the_helper():
    import inspect

    import deepspeed_tpu
    from deepspeed_tpu.inference.engine_v2 import InferenceEngineV2

    assert "configure_compile_cache()" in inspect.getsource(
        deepspeed_tpu.initialize)
    assert "configure_compile_cache()" in inspect.getsource(
        InferenceEngineV2.__init__)
    root = os.path.dirname(os.path.dirname(os.path.abspath(
        deepspeed_tpu.__file__)))
    with open(os.path.join(root, "chip_smoke.py")) as f:
        assert "configure_compile_cache()" in f.read()
