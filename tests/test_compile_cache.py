"""Where the persistent compilation cache goes, and what importing the
package initialises.  Each case runs in a fresh interpreter: the cache
directory and the backends are process-wide state."""

import os
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROBE = textwrap.dedent("""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    used = enable_compile_cache()
    print("USED", used)
    print("CONFIG", jax.config.jax_compilation_cache_dir)
""")


def _run(code, **env_over):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_over)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-3000:]
    return dict(line.split(" ", 1) for line in r.stdout.splitlines())


def test_env_dir_is_left_to_jax_and_receives_entries(tmp_path):
    d = str(tmp_path / "cc")
    out = _run(PROBE + textwrap.dedent("""
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.jit(lambda x: x * 2 + 1)(jax.numpy.ones(8)).block_until_ready()
    """), JAX_COMPILATION_CACHE_DIR=d)
    assert out["USED"] == d and out["CONFIG"] == d
    assert os.listdir(d), "no cache entry was written"


def test_default_dir_is_fixed_inside_the_checkout_and_ignored():
    out = _run(PROBE)
    want = os.path.join(ROOT, ".jax_cache")
    assert out["USED"] == want and out["CONFIG"] == want
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_importing_repro_initialises_no_backend():
    out = _run(textwrap.dedent("""
        import importlib, pkgutil
        import repro
        from jax._src import xla_bridge
        for m in pkgutil.walk_packages(repro.__path__, "repro."):
            importlib.import_module(m.name)
        print("INIT", xla_bridge.backends_are_initialized())
    """))
    assert out["INIT"] == "False"
