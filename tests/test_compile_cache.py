"""Persistent compilation cache placement and the chip smoke's refusal to
run without a TPU (each in a fresh interpreter: both read process state
that a test process must not change)."""
import os
import subprocess
import sys

from tests.util_subproc import REPO

_CODE = r"""
import os, sys
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import CACHE_DIR, enable_compile_cache
got = enable_compile_cache()
want = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
assert got == want, (got, want)
assert jax.config.jax_compilation_cache_dir == want, jax.config.jax_compilation_cache_dir
if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
print(got)
"""


def _run(args, env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable] + args, capture_output=True, text=True,
                          timeout=300, env=env, cwd=REPO)


def test_compile_cache_honours_env_dir(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache lands there, nothing is
    overridden in code."""
    d = str(tmp_path / "xla_cache")
    proc = _run(["-c", _CODE], {"JAX_COMPILATION_CACHE_DIR": d,
                                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"})
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().splitlines()[-1] == d
    assert os.listdir(d), "nothing was written to the cache directory"


def test_compile_cache_defaults_to_fixed_in_tree_dir():
    """Unset: one fixed directory inside the checkout, listed in .gitignore."""
    proc = _run(["-c", _CODE], {}, drop=("JAX_COMPILATION_CACHE_DIR",))
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.strip().splitlines()[-1] == os.path.join(REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_refuses_cpu():
    """Without a TPU the smoke exits non-zero and prints no result line."""
    proc = _run([os.path.join(REPO, "chip_smoke.py")], {})
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
