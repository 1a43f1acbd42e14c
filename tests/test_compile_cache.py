"""The persistent compilation cache has one fixed home (launch/compile_cache)."""
import jax

from repro.launch import compile_cache


def _restoring(fn):
    old = jax.config.jax_compilation_cache_dir
    try:
        return fn(), jax.config.jax_compilation_cache_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_env_dir_wins_and_code_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    got, after = _restoring(compile_cache.setup_compile_cache)
    assert got == str(tmp_path)
    assert after == before


def test_default_is_fixed_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got, after = _restoring(compile_cache.setup_compile_cache)
    want = compile_cache.CHECKOUT_CACHE_DIR
    assert got == after == str(want)
    assert want.name == ".jax_cache" and (want.parent / "src").is_dir()
