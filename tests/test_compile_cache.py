"""The persistent compilation cache helper (``repro.launch.compile_cache``).

Each case runs in a fresh interpreter: the cache latches its configuration
at the process's first compile.
"""

import json
import os
import subprocess
import sys

from repro.launch.compile_cache import REPO_CACHE

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")

PROBE = r"""
import json, sys
import jax, jax.numpy as jnp
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
from repro.launch.compile_cache import enable_compile_cache
used = enable_compile_cache()
if sys.argv[1] == "compile":
    jax.jit(lambda x: jnp.cos(x) * 2.5)(jnp.ones(11)).block_until_ready()
print(json.dumps({"used": used,
                  "config": jax.config.jax_compilation_cache_dir}))
"""


def _probe(env_dir, mode):
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", PROBE, mode], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    cache = str(tmp_path / "cache")
    got = _probe(cache, "compile")
    assert got == {"used": cache, "config": cache}
    assert os.listdir(cache)


def test_unset_env_uses_the_fixed_repo_dir():
    got = _probe(None, "configure-only")
    assert got == {"used": REPO_CACHE, "config": REPO_CACHE}
    assert os.path.basename(REPO_CACHE) == ".jax_cache"
    assert os.path.isfile(os.path.join(os.path.dirname(REPO_CACHE),
                                       "pyproject.toml"))
