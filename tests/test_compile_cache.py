"""Where the entry points put JAX's persistent compilation cache.

Each case runs in a fresh interpreter: the cache is process-wide state,
and the test process itself must not turn it on."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

PROG = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    print("DIR", enable_compile_cache())
    print("CONFIG", jax.config.jax_compilation_cache_dir)
    jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
""")


def _run(env_dir):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
               # cache every executable, however fast it compiled
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    r = subprocess.run([sys.executable, "-c", PROG], capture_output=True,
                       text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_cache_goes_where_the_environment_says(tmp_path):
    out = _run(tmp_path)
    assert out["DIR"] == str(tmp_path)
    assert out["CONFIG"] == str(tmp_path)         # set by JAX, not by us
    assert any(tmp_path.iterdir()), "nothing was cached"


def test_cache_defaults_to_the_checkout():
    out = _run(None)
    assert out["DIR"] == str(ROOT / ".jax_cache")
    assert out["CONFIG"] == str(ROOT / ".jax_cache")
