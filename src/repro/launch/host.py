"""What a process may do on its host: where JAX keeps its compiled code,
and whether it may start children that need the accelerator.

``enable_compile_cache``
    JAX's persistent compilation cache.  Where ``JAX_COMPILATION_CACHE_DIR``
    is set, JAX reads it itself and nothing is set here.  Otherwise the
    cache lives in ``.jax_cache/`` at the root of the checkout, a path
    derived from this file's own location: the path is part of the
    cache's key, so it must not move between runs (no tempdir, pid or
    time in it).

``forbid_jax_children``
    A TPU belongs to one process at a time.  A parent whose backend is the
    TPU holds the chip, and a child that needs it fails or hangs — so the
    spawners refuse up front instead of starting children that cannot run.
    A parent that stays off JAX may still run its children one at a time.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def forbid_jax_children(what: str) -> None:
    """Raise when this process has started a JAX backend and it is a TPU
    (it then holds the chip, and ``what`` — a child JAX process — could
    never get it).  A process that has not started JAX is left so: asking
    for the backend would take the chip itself."""
    import jax
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what}: this process holds the TPU, and a chip belongs to one "
            "process at a time, so a child JAX process would fail or hang. "
            "Run the work in this process over jax.devices() instead.")
