"""Where the persistent compilation cache lives: ``JAX_COMPILATION_CACHE_DIR``
when it is set, and nothing else; otherwise the fixed, git-ignored
``<checkout>/.jax_cache``."""
import os
import subprocess
import sys
import textwrap

from repro.compile_cache import DEFAULT_DIR, ENV_VAR, compile_cache_dir

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_var_names_the_directory():
    assert compile_cache_dir({ENV_VAR: "/some/cache"}) == "/some/cache"


def test_fallback_is_fixed_and_git_ignored():
    assert compile_cache_dir({}) == os.path.join(REPO, ".jax_cache")
    assert compile_cache_dir({ENV_VAR: ""}) == str(DEFAULT_DIR)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_cache_is_written_only_where_the_env_var_says(tmp_path):
    """A fresh process with the variable set writes its entries there and
    creates no in-checkout cache."""
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.compile_cache import enable_compile_cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        print(enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) * 3.0)(jnp.ones(7)).block_until_ready()
    """)
    cache = tmp_path / "cache"
    before = os.path.exists(DEFAULT_DIR) and sorted(os.listdir(DEFAULT_DIR))
    env = dict(os.environ, JAX_PLATFORMS="cpu", **{ENV_VAR: str(cache)},
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == str(cache)
    assert cache.is_dir() and any(cache.iterdir())
    after = os.path.exists(DEFAULT_DIR) and sorted(os.listdir(DEFAULT_DIR))
    assert after == before
