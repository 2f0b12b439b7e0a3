"""Where the launchers keep JAX's persistent compilation cache, and that
``chip_smoke.py`` refuses to run without a TPU.

Each case runs in a child process: JAX reads ``JAX_COMPILATION_CACHE_DIR``
once, at import, and the cache is process-global state.
"""

import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent

CHILD = textwrap.dedent(
    """
    import json, sys
    from pathlib import Path
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache

    compile_cache.CHECKOUT_CACHE_DIR = Path(sys.argv[1])  # keep the checkout clean
    if sys.argv[2] == "tpu":
        jax.default_backend = lambda: "tpu"  # take the TPU branch on the CPU
    used = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.jit(lambda x: jnp.sin(x) * 2.0)(jnp.ones(8)).block_until_ready()
    print("RESULT " + json.dumps({
        "used": None if used is None else str(used),
        "config": jax.config.jax_compilation_cache_dir,
    }))
    """
)


def run_child(tmp_path, backend, env_dir):
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu")
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(tmp_path / "checkout_cache"), backend],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=120,
    )
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, proc.stderr[-2000:]
    return json.loads(line[0][len("RESULT "):])


def entries(d: Path) -> list:
    return sorted(p.name for p in d.iterdir()) if d.exists() else []


@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_env_dir_is_used_and_nothing_else(tmp_path, backend):
    env_dir = tmp_path / "env_cache"
    out = run_child(tmp_path, backend, env_dir)
    assert out["used"] == out["config"] == str(env_dir)
    assert entries(env_dir)
    assert not entries(tmp_path / "checkout_cache")


def test_tpu_without_env_uses_checkout_dir(tmp_path):
    out = run_child(tmp_path, "tpu", None)
    assert out["used"] == out["config"] == str(tmp_path / "checkout_cache")
    assert entries(tmp_path / "checkout_cache")


def test_cpu_without_env_caches_nothing(tmp_path):
    out = run_child(tmp_path, "cpu", None)
    assert out["used"] is None and out["config"] is None
    assert not entries(tmp_path / "checkout_cache")


def test_checkout_dir_is_fixed_and_ignored():
    from repro.launch.compile_cache import CHECKOUT_CACHE_DIR

    assert CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(tmp_path, where):
    """On the CPU, and in a directory holding nothing of the repo but the
    script, it exits non-zero and prints no ``ok`` line."""
    script = ROOT / "chip_smoke.py"
    if where == "alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        cwd=script.parent, env=env, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
