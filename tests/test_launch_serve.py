"""The serving entry point: config selection, seeded inputs, the compile
cache rule, and one reduced end-to-end run of ``python -m
repro.launch.serve``."""
import dataclasses
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.launch import serve
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


def _args(*argv):
    return serve.parser().parse_args(list(argv))


def test_published_widths_cut_only_depth():
    full = serve.get_config("moonshot-v1-16b-a3b")
    cfg = serve.serving_config(
        _args("--arch", "moonshot-v1-16b-a3b", "--published", "--layers", "4")
    )
    assert cfg == dataclasses.replace(full, num_layers=4)


def test_reduced_by_default():
    cfg = serve.serving_config(_args("--arch", "moonshot-v1-16b-a3b"))
    assert cfg == serve.get_config("moonshot-v1-16b-a3b").reduced()


def test_bad_layers_refused():
    with pytest.raises(SystemExit):
        serve.build_model(_args("--layers", "0"))


def test_seed_fixes_weights_and_prompts():
    argv = ("--layers", "1", "--requests", "2", "--prompt-len", "8")
    a = serve.build_model(_args(*argv, "--seed", "5"))
    b = serve.build_model(_args(*argv, "--seed", "5"))
    c = serve.build_model(_args(*argv, "--seed", "6"))
    assert [len(p) for p in a[2]] == [8, 8]
    assert all(np.array_equal(x, y) for x, y in zip(a[2], b[2]))
    assert not all(np.array_equal(x, y) for x, y in zip(a[2], c[2]))
    assert np.array_equal(a[1]["embed"], b[1]["embed"])
    assert not np.array_equal(a[1]["embed"], c[1]["embed"])


def test_compile_cache_uses_env_and_sets_nothing(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_gitignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_lower_decode_then_serve():
    """``lower_decode`` lowers the megastep at the engine's shapes without
    running or donating anything; serving afterwards is unaffected."""
    args = _args("--layers", "1", "--requests", "2", "--prompt-len", "8",
                 "--max-new", "3", "--slots", "2", "--pmq")
    cfg, params, prompts = serve.build_model(args)
    engine = serve.build_engine(args, cfg, params)
    text = engine.lower_decode().as_text()
    assert "func.func public @main" in text
    out = engine.serve([
        serve.PagedRequest(rid=i, prompt=p, max_new=3)
        for i, p in enumerate(prompts)
    ])
    assert [len(out[i]) for i in range(2)] == [3, 3] and not engine.errors


def test_main_serves_reduced(capsys, monkeypatch, tmp_path):
    # the variable wins, so main() leaves this process's cache settings be
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    serve.main(["--requests", "2", "--max-new", "2", "--slots", "2",
                "--layers", "1", "--prompt-len", "8"])
    assert "served 2 requests" in capsys.readouterr().out
