"""The launchers' configuration and compile-cache surface: published widths
unless ``--reduced`` is asked for, and JAX's persistent compilation cache
where ``JAX_COMPILATION_CACHE_DIR`` says or else at one fixed place in the
checkout."""

import pathlib
import sys

import jax
import pytest

from repro.launch import compile_cache, serve, train


class _Stop(Exception):
    pass


def _run_main(module, monkeypatch, entry, argv):
    """Run ``module.main()`` with ``argv`` until it reaches ``entry`` (the
    function that would build the model), and return the config it got."""
    seen = {}

    def capture(cfg, *a, **kw):
        seen["cfg"] = cfg
        raise _Stop

    monkeypatch.setattr(module, entry, capture)
    monkeypatch.setattr(module, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", [module.__name__] + argv)
    with pytest.raises(_Stop):
        module.main()
    return seen["cfg"]


@pytest.mark.parametrize("module,entry", [(serve, "make_engine"),
                                          (train, "train")])
@pytest.mark.parametrize("reduced", [False, True])
def test_launcher_keeps_published_widths_unless_reduced(module, entry,
                                                        reduced,
                                                        monkeypatch):
    from repro.configs import get
    argv = ["--arch", "llama3.2-3b"] + (["--reduced"] if reduced else [])
    cfg = _run_main(module, monkeypatch, entry, argv)
    want = get("llama3.2-3b")
    if reduced:
        want = want.reduced()
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab) == \
        (want.n_layers, want.d_model, want.d_ff, want.vocab)
    if not reduced:
        assert (cfg.n_layers, cfg.d_model) == (28, 3072)


def test_compile_cache_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.compile_cache_dir() == str(tmp_path)
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # the variable is JAX's own: nothing here sets another directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    root = pathlib.Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    before = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
