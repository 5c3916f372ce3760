"""Where the entry points put JAX's persistent compilation cache."""

from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_to_jax(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_inside_the_checkout_and_ignored(
    monkeypatch, cache_dir_restored
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    want = str(REPO / ".jax_cache")
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()


def test_importing_the_program_sets_no_cache():
    env = {k: v for k, v in os.environ.items() if k != compile_cache.ENV_VAR}
    env["PYTHONPATH"] = str(REPO / "src")
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax, chip_smoke, repro.launch.serve, repro.launch.train\n"
        "print(jax.config.jax_compilation_cache_dir)" % str(REPO)
    )
    out = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True,
        check=True, timeout=300,
    )
    assert out.stdout.strip().splitlines()[-1] == "None"
