"""The launchers' persistent compilation cache location
(`repro.launch.compile_cache`). Each case runs in a fresh interpreter: the
cache directory is process-wide JAX configuration."""
import os
import subprocess
import sys

from conftest import REPO, SRC

CODE = """
import json, os, jax, jax.numpy as jnp
from repro.launch.compile_cache import DEFAULT_DIR, enable_compile_cache
used = enable_compile_cache()
if os.environ.get("COMPILE"):
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
print(json.dumps({"used": used, "config": jax.config.jax_compilation_cache_dir,
                  "default": str(DEFAULT_DIR)}))
"""


def _run(env_extra: dict, drop=()):
    import json

    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(env_extra, JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", CODE], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_dir_is_used_and_written(tmp_path):
    cache = tmp_path / "jaxcache"
    out = _run({"JAX_COMPILATION_CACHE_DIR": str(cache), "COMPILE": "1"})
    assert out["used"] == out["config"] == str(cache)
    assert any(cache.iterdir()), "the compile was not cached there"


def test_default_is_fixed_path_in_checkout():
    out = _run({}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert out["used"] == out["config"] == out["default"]
    assert out["default"] == os.path.join(REPO, ".jax_cache")
