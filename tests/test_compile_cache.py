"""The entry points' persistent compilation cache (launch/compile_cache.py).

Each case runs in a child interpreter: the tests themselves never turn the
cache on.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from repro.launch.compile_cache import DEFAULT_DIR

ROOT = Path(__file__).resolve().parent.parent

_CHILD = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == path, path
    if {compile}:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.jit(lambda x: x * 2.0 + 1.0)(jnp.ones(8)).block_until_ready()
    print("CACHE_DIR", path)
""")


def _run(compile_, extra_env):
    env = {"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
           "HOME": os.path.expanduser("~"), **extra_env}
    r = subprocess.run([sys.executable, "-c",
                        _CHILD.format(compile=compile_)],
                       capture_output=True, text=True, timeout=300, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    return r.stdout.split("CACHE_DIR", 1)[1].strip()


def _listing(path):
    return sorted(p.name for p in path.iterdir()) if path.exists() else None


def test_cache_written_only_where_env_says(tmp_path):
    before = _listing(DEFAULT_DIR)
    got = _run(True, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "c")})
    assert got == str(tmp_path / "c")
    assert any((tmp_path / "c").iterdir())
    assert _listing(DEFAULT_DIR) == before


def test_cache_defaults_to_fixed_repo_dir():
    got = _run(False, {})
    assert got == str(ROOT / ".jax_cache") == str(DEFAULT_DIR)
