"""Where JAX's persistent compilation cache lives (exec/qcache.py):
at JAX_COMPILATION_CACHE_DIR when the caller sets it, else at the fixed
<checkout>/.jax_cache. Each case runs in its own process, because the
test process itself runs with the cache off (tests/conftest.py)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROG = (
    "import presto_tpu, jax, jax.numpy as jnp;"
    "from presto_tpu.exec.qcache import enable_persistent_compile_cache as e;"
    "jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready();"
    "print(e()); print(jax.config.jax_compilation_cache_dir)"
)


@pytest.mark.parametrize("given", [True, False], ids=["env-dir", "default-dir"])
def test_compile_cache_directory(tmp_path, given):
    env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR", "JAX_ENABLE_COMPILATION_CACHE")
    }
    env["JAX_PLATFORMS"] = "cpu"
    want = os.path.join(REPO, ".jax_cache")
    if given:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "given")
    out = subprocess.run(
        [sys.executable, "-c", _PROG], cwd=REPO, env=env, text=True,
        capture_output=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [want, want]
    assert any(f.startswith("jit__lambda") for f in os.listdir(want))
