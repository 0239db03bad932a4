"""The entry points' persistent compilation cache (repro/launch/cache.py).

Each case runs in a fresh interpreter, so the test process itself never
turns the cache on, and nothing is written under the repository.
"""
import os
import subprocess
import sys
import textwrap

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run(code: str, **env_over) -> str:
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
               **env_over)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_writes_only_to_env_dir(tmp_path):
    cache = tmp_path / "jax-cache"
    before = os.path.exists(os.path.join(REPO, ".jax_cache"))
    got = _run("""
        import jax, jax.numpy as jnp
        from repro.launch.cache import enable_compile_cache
        d = enable_compile_cache()
        jax.jit(lambda x: jnp.sin(x) * 2).lower(jnp.ones(8)).compile()
        print(d)
    """, JAX_COMPILATION_CACHE_DIR=str(cache),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert got == str(cache)
    assert cache.is_dir() and any(cache.iterdir())
    assert os.path.exists(os.path.join(REPO, ".jax_cache")) == before


def test_compile_cache_defaults_to_repo_dir():
    got = _run("""
        import jax
        from repro.launch.cache import enable_compile_cache
        d = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == d
        print(d)
    """)
    assert got == os.path.join(REPO, ".jax_cache")
