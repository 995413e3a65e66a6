"""paddle_tpu.utils.compile_cache: the one place that points JAX's persistent
compilation cache somewhere. Set from outside, the directory is left to JAX;
unset, it is a fixed path in the checkout — never a per-run temp dir."""
import os

import jax

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _updates(monkeypatch):
    calls = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: calls.__setitem__(key, value))
    return calls


def test_variable_set_means_no_directory_set_in_code(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    calls = _updates(monkeypatch)
    assert compile_cache.enable() == str(tmp_path)
    assert not [k for k in calls if k.endswith("cache_dir")]
    assert calls   # the cache is still switched on for every compile


def test_variable_unset_means_the_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _updates(monkeypatch)
    want = os.path.join(REPO, ".jax_compile_cache")
    assert compile_cache.enable() == want == compile_cache.CHECKOUT_CACHE_DIR
    assert [v for k, v in calls.items() if k.endswith("cache_dir")] == [want]


def test_nothing_else_in_the_repo_places_the_cache():
    """The acceptance grep: the config key appears in the helper only."""
    key = "jax_compilation_" + "cache_dir"
    paths = [os.path.join(REPO, f) for f in os.listdir(REPO)
             if f.endswith(".py")]
    for top in ("paddle_tpu", "tests", "tools"):
        for root, _, files in os.walk(os.path.join(REPO, top)):
            paths += [os.path.join(root, f) for f in files
                      if f.endswith(".py")]
    hits = [os.path.relpath(p, REPO) for p in paths
            if key in open(p, errors="replace").read()]
    assert hits == [os.path.join("paddle_tpu", "utils", "compile_cache.py")]
