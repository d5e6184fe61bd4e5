"""Where the persistent compilation cache goes (``repro.compile_cache``).

Each case runs in a fresh interpreter: the cache directory is process-global
JAX state, and this worker's own JAX must stay as the other tests found it.
"""
import json
import os
import pathlib
import subprocess
import sys

import pytest

from repro.compile_cache import ENV_CACHE_DIR, REPO_CACHE_DIR

REPO = pathlib.Path(__file__).resolve().parents[1]

_PROBE = """
import json, sys
import jax, jax.numpy as jnp
import repro, repro.core, repro.serve
from repro import compile_cache
before = jax.config.jax_compilation_cache_dir
used = compile_cache.enable_compile_cache() if sys.argv[1] == "on" else None
if sys.argv[2] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({"before": before, "used": used,
                  "after": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, *args):
    env = {k: v for k, v in os.environ.items() if k != ENV_CACHE_DIR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_dir is not None:
        env[ENV_CACHE_DIR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _PROBE, *args], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_importing_repro_leaves_the_cache_off():
    got = _probe(None, "off", "none")
    assert got["before"] is None and got["after"] is None


def test_env_dir_is_used_and_written_alone(tmp_path):
    cache = tmp_path / "cache"
    repo_entries = (set(REPO_CACHE_DIR.iterdir()) if REPO_CACHE_DIR.exists()
                    else set())
    got = _probe(cache, "on", "compile")
    assert got["used"] == got["after"] == str(cache)
    assert any(cache.iterdir()), "no cache entry was written"
    if REPO_CACHE_DIR.exists():
        assert set(REPO_CACHE_DIR.iterdir()) == repo_entries


@pytest.mark.parametrize("env_dir", [None, ""])
def test_without_env_dir_the_repo_cache_is_used(env_dir):
    got = _probe(env_dir, "on", "none")
    assert got["used"] == got["after"] == str(REPO / ".jax_cache")
    assert REPO_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored, ".jax_cache/ is not gitignored"
