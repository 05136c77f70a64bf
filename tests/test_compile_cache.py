"""The persistent compilation cache lands where it is placed: in
``JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise in the one
fixed, git-ignored directory of the checkout — and nowhere else."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.utils import compile_cache

REPO = Path(__file__).resolve().parents[1]

# compile once in a fresh process, with the checkout's default directory
# redirected so the test writes nothing into the checkout itself
_SCRIPT = """
import sys
from pathlib import Path
import jax, jax.numpy as jnp
from repro.utils import compile_cache
compile_cache.DEFAULT_DIR = Path(sys.argv[1])
print(compile_cache.enable_compile_cache())
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""


def test_default_dir_is_fixed_and_ignored():
    assert compile_cache.DEFAULT_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_entries_land_only_where_placed(tmp_path, from_env):
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    if from_env:
        env[compile_cache.ENV_VAR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _SCRIPT, str(default_dir)],
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True)
    want, other = (env_dir, default_dir) if from_env else (default_dir,
                                                           env_dir)
    assert out.stdout.split()[-1] == str(want)
    assert any(want.iterdir())
    assert not other.exists()
