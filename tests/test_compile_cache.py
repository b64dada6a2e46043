"""The entry points' persistent compilation cache directory."""
import os

from repro.launch import compile_cache as cc


def test_env_var_wins():
    assert cc.compile_cache_dir({cc.ENV: "/srv/jax-cache"}) == \
        "/srv/jax-cache"


def test_default_is_fixed_path_in_checkout():
    path = cc.compile_cache_dir({})
    assert path == os.path.join(cc.REPO_ROOT, ".jax_cache")
    # the cache key includes the path: it must not move between runs
    assert cc.compile_cache_dir({}) == path
    assert str(os.getpid()) not in path
    assert os.path.isfile(os.path.join(cc.REPO_ROOT, "chip_smoke.py"))


def test_enable_sets_dir_only_without_env(monkeypatch):
    calls = {}
    import jax
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.__setitem__(k, v))
    monkeypatch.delenv(cc.ENV, raising=False)
    monkeypatch.delenv(cc.SWITCH, raising=False)
    assert cc.enable_compile_cache() == cc.DEFAULT_DIR
    assert calls == {"jax_compilation_cache_dir": cc.DEFAULT_DIR,
                     "jax_enable_compilation_cache": True}
    calls.clear()
    monkeypatch.setenv(cc.ENV, "/srv/jax-cache")
    assert cc.enable_compile_cache() == "/srv/jax-cache"
    assert "jax_compilation_cache_dir" not in calls


def test_gitignore_lists_cache_dir():
    with open(os.path.join(cc.REPO_ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_tests_keep_cache_off():
    """The suite sets the switch off, so an entry point called from a
    test leaves JAX's cache disabled and writes no cache files."""
    import jax
    assert os.environ[cc.SWITCH] == "false"
    assert cc.enable_compile_cache() is None
    assert not jax.config.jax_enable_compilation_cache
