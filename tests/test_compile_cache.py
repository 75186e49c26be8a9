"""Where the persistent compilation cache goes (``repro.compile_cache``).

Each case runs in a fresh interpreter: the cache directory is
process-wide JAX configuration.
"""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROG = textwrap.dedent("""
    import json, sys
    import jax, jax.numpy as jnp
    from repro import compile_cache
    path = compile_cache.enable()
    if len(sys.argv) > 1:       # compile something small into the cache
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
    print(json.dumps({"path": path,
                      "config": jax.config.jax_compilation_cache_dir}))
""")


def _run(cache_dir, *argv):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", PROG, *argv],
                         capture_output=True, text=True, timeout=300,
                         env=env, cwd=ROOT)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_env_var_is_honoured(tmp_path):
    got = _run(tmp_path, "compile")
    assert got["path"] == str(tmp_path)
    assert got["config"] == str(tmp_path)
    assert any(tmp_path.iterdir()), "no compiled program in the cache dir"


def test_default_dir_is_fixed_inside_the_checkout():
    first, second = _run(None), _run(None)
    expected = os.path.join(ROOT, ".jax_cache")
    assert first == second == {"path": expected, "config": expected}
