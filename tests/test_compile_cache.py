"""The one rule for where the persistent compile cache lives
(sav_tpu/utils/compile_cache.py): the JAX_COMPILATION_CACHE_DIR variable
wins; unset, an override, else the fixed in-checkout directory on a TPU
and no cache on the CPU; no code path sets a third directory."""

import os
import re

import jax
import pytest

from sav_tpu.utils import compile_cache as cc

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def config_updates(monkeypatch):
    """Record jax.config updates instead of applying them (no test here
    may leave a live persistent cache behind for the rest of the suite)."""
    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    monkeypatch.setattr(cc, "_reset_cache_singleton", lambda: None)
    return updates


def test_variable_set_wins_and_nothing_sets_another_directory(
    tmp_path, monkeypatch, config_updates
):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv(cc.CACHE_DIR_ENV, env_dir)
    assert cc.resolve_cache_dir() == env_dir
    assert cc.resolve_cache_dir(str(tmp_path / "override")) == env_dir
    assert cc.enable_persistent_cache(str(tmp_path / "override")) == env_dir
    # jax read the variable itself at import; the code sets no directory.
    assert not [u for u in config_updates if u[0] == "jax_compilation_cache_dir"]
    # ...not even to switch it off.
    cc.disable_persistent_cache()
    assert not [u for u in config_updates if u[0] == "jax_compilation_cache_dir"]


def test_unset_on_a_tpu_gives_the_fixed_in_checkout_path(
    monkeypatch, config_updates
):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(os, "makedirs", lambda *a, **k: None)
    assert cc.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    assert cc.resolve_cache_dir() == cc.DEFAULT_CACHE_DIR
    assert cc.enable_persistent_cache() == cc.DEFAULT_CACHE_DIR
    assert ("jax_compilation_cache_dir", cc.DEFAULT_CACHE_DIR) in config_updates


def test_unset_on_the_cpu_keeps_the_cache_off(monkeypatch, config_updates):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    assert jax.default_backend() == "cpu"
    assert cc.resolve_cache_dir() is None
    assert cc.enable_persistent_cache() is None
    assert config_updates == []


def test_override_is_used_only_without_the_variable(
    tmp_path, monkeypatch, config_updates
):
    monkeypatch.delenv(cc.CACHE_DIR_ENV, raising=False)
    override = str(tmp_path / "override")
    assert cc.enable_persistent_cache(
        override, min_compile_time_secs=0.0
    ) == override
    assert os.path.isdir(override)
    assert ("jax_compilation_cache_dir", override) in config_updates
    assert ("jax_persistent_cache_min_compile_time_secs", 0.0) in config_updates


def test_default_path_is_fixed_and_ignored_by_git():
    # Never derived from a temporary name, a process id or the time.
    assert cc.DEFAULT_CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_place_sets_the_cache_directory():
    """No third path: across the program's code, exactly one statement
    sets ``jax_compilation_cache_dir``, in compile_cache.py, under the
    variable's guard."""
    pattern = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir["']"""
        r"""|compilation_cache\.set_cache_dir\("""
    )
    hits = []
    for base in ("sav_tpu", "tools"):
        for dirpath, _, files in os.walk(os.path.join(ROOT, base)):
            hits += [
                os.path.join(dirpath, f) for f in files if f.endswith(".py")
            ]
    hits += [
        os.path.join(ROOT, f)
        for f in ("train.py", "bench.py", "chip_smoke.py", "__graft_entry__.py")
    ]
    setters = {}
    for path in hits:
        with open(path) as f:
            n = len(pattern.findall(f.read()))
        if n:
            setters[os.path.relpath(path, ROOT)] = n
    assert setters == {os.path.join("sav_tpu", "utils", "compile_cache.py"): 1}
    with open(os.path.join(ROOT, "sav_tpu", "utils", "compile_cache.py")) as f:
        source = f.read()
    guard = source.index("if not os.environ.get(CACHE_DIR_ENV):")
    setter = source.index('jax.config.update("jax_compilation_cache_dir"')
    assert guard < setter < guard + 120
