"""Which directory the persistent compilation cache uses."""
import pathlib

from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR, ENV_VAR,
                                        compile_cache_dir)


def test_environment_wins():
    assert compile_cache_dir({ENV_VAR: "/cache/jax"}) == "/cache/jax"


def test_checkout_default_is_fixed():
    root = pathlib.Path(__file__).resolve().parents[1]
    assert compile_cache_dir({}) == str(root / ".jax_cache")
    assert compile_cache_dir({ENV_VAR: ""}) == str(CHECKOUT_CACHE_DIR)
    assert compile_cache_dir({}) == compile_cache_dir({})
