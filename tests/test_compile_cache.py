"""repro.compile_cache: JAX's persistent cache goes where
JAX_COMPILATION_CACHE_DIR says, else to the checkout's .jax_cache.  Each
case runs in a fresh interpreter, since the setting is process-wide."""
import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro import compile_cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print("DIR=" + compile_cache.enable())
    print("CFG=" + str(jax.config.jax_compilation_cache_dir))
    if {compile}:
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(7)).block_until_ready()
""")


def _run(env_dir, compile_):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": str(ROOT / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run([sys.executable, "-c",
                           SCRIPT.format(compile=compile_)],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return dict(line.split("=", 1) for line in proc.stdout.splitlines()
                if line[:4] in ("DIR=", "CFG="))


def test_env_dir_is_left_to_jax_and_receives_the_entries(tmp_path):
    cache = tmp_path / "cache"
    out = _run(cache, True)
    assert out["DIR"] == str(cache) == out["CFG"]
    assert any(cache.iterdir())


def test_default_dir_is_the_checkouts_ignored_jax_cache():
    out = _run(None, False)
    assert out["DIR"] == str(ROOT / ".jax_cache") == out["CFG"]
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
