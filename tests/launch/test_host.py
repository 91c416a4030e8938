"""repro.launch.host: the compile-cache rule and the one-process-per-chip
guard."""
import os
import subprocess
import sys

import jax
import pytest

from repro.launch import host

SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src")


def _fresh(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_is_fixed_inside_the_checkout():
    code = ("from repro.launch.host import enable_compile_cache; "
            "import jax; d = enable_compile_cache(); "
            "print(d == jax.config.jax_compilation_cache_dir, d)")
    first, second = _fresh(code), _fresh(code)
    assert first == second and first.startswith("True ")
    root = os.path.abspath(os.path.join(SRC, ".."))
    assert first.split(" ", 1)[1] == os.path.join(root, ".jax_cache")


def test_cache_dir_left_to_jax_when_env_set(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert host.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_guard_leaves_an_unstarted_backend_unstarted():
    code = ("from repro.launch.host import forbid_jax_children; "
            "from jax._src import xla_bridge; "
            "forbid_jax_children('child'); "
            "print(xla_bridge.backends_are_initialized())")
    assert _fresh(code) == "False"


def test_guard_refuses_children_of_a_tpu_process(monkeypatch):
    jax.devices()                       # this process has a backend
    host.forbid_jax_children("child")   # the CPU: children may run
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="holds the TPU"):
        host.forbid_jax_children("child")
