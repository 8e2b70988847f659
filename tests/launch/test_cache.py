"""The persistent compilation cache lands where ``use_compile_cache`` says.

Each case runs in a fresh interpreter: JAX reads ``JAX_COMPILATION_CACHE_DIR``
once, at import.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))

_PROBE = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.cache import use_compile_cache
    path = use_compile_cache()
    if os.environ.get("COMPILE_ONE"):
        jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(8)).block_until_ready()
    print(json.dumps({"path": path, "config": jax.config.jax_compilation_cache_dir}))
""")


def _probe(env_dir, compile_one=False):
    env = {
        k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.path.join(ROOT, "src"))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    if compile_one:
        env.update(COMPILE_ONE="1", JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, capture_output=True,
        text=True, timeout=300, check=True,
    )
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_not_overridden(tmp_path):
    got = _probe(tmp_path, compile_one=True)
    assert got["path"] == str(tmp_path)
    assert got["config"] == str(tmp_path)
    assert os.listdir(tmp_path), "the compile was not cached in the env directory"


@pytest.mark.parametrize("env_dir", [None, ""])
def test_checkout_dir_without_env(env_dir):
    got = _probe(env_dir)
    expected = os.path.join(ROOT, ".jax_cache")
    assert got["path"] == expected
    assert got["config"] == expected
