"""Shared kernel infrastructure: backend resolution, autotune cache,
the kernel-op registry, and the ``KernelPolicy`` selector.

PR 2 built this machinery for conv2d only (``conv2d/tune.py``); every
other Pallas kernel hardcoded ``interpret=True`` and had no way to be
selected by the model layer.  This module hoists the shared parts so all
four kernel families (conv2d, flash_attention, rglru, rwkv6) resolve
their execution mode, tune their block sizes, and register themselves
the same way:

``resolve_interpret``
    Tri-state ``interpret`` flag: ``None`` means "compile when the
    backend can" (TPU), falling back to the Pallas interpreter on
    CPU/GPU hosts.  ``REPRO_PALLAS_INTERPRET=0|1`` overrides for every
    kernel (the env var used to reach only conv2d).

``autotune`` / ``clear_cache`` / ``cache_info``
    The process-level shape-keyed winner cache.  Keys are namespaced per
    kernel (``("matmul", ...)``, ``("flash", ...)``, ...); a measured
    sweep runs once per shape on compiled backends and the winner is
    memoised for the rest of the process.  ``REPRO_PALLAS_AUTOTUNE=0``
    disables measurement globally (``REPRO_CONV_AUTOTUNE`` is still
    honoured by the conv tuners for back-compat).

``KernelOp`` / ``register`` / ``get_op`` / ``ops``
    The registration pattern: each kernel package registers its public
    pallas entry point, the XLA reference it must match, its block-size
    tuner, and its fp32 parity tolerance.  Tests iterate the registry
    (``tests/kernels/test_grad_parity.py``) so a new kernel gets parity
    and gradient coverage by registering, not by copying a test file.

``KernelPolicy``
    The single per-run selector threaded ``configs/base.py`` →
    ``models/*`` → ``launch/train.py --kernel-backend``.  One global
    ``backend`` default (``xla | pallas | auto``) plus per-op overrides
    (which may also name a concrete impl, e.g. ``attention="qloop"``)
    and interpret/autotune overrides.  ``auto`` resolves to the Pallas
    path exactly when it would compile (i.e. not interpret mode), so
    CPU development keeps the fast XLA paths while a TPU host trains the
    whole zoo on the Pallas kernels with no flag changes.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax

# shape-keyed winner cache: key -> blocks tuple
_CACHE: Dict[tuple, tuple] = {}
# how many measured autotune sweeps ran, and cache hits (introspection /
# tests)
_STATS = {"measured": 0, "hits": 0}
# (key, candidate, error) for every refused candidate, oldest first
_REFUSALS: list = []

SUBLANE = 8           # TPU fp32 sublane count — block floor
LANE = 128            # TPU lane count — preferred alignment


# ------------------------------------------------------------------ mode ----

def resolve_interpret(interpret=None) -> bool:
    """Resolve the tri-state ``interpret`` flag for ANY Pallas kernel.

    None  -> auto: compile on TPU, interpret elsewhere (the pinned
             kernels are Mosaic/TPU programs; CPU runs them through the
             Pallas interpreter for correctness work).
    bool  -> honoured as given (tests force both modes).
    Env   -> REPRO_PALLAS_INTERPRET=0|1 overrides auto-detection only.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env not in ("0", "false", "False")
    return jax.default_backend() != "tpu"


def pow2_clip(dim: int, cap: int) -> int:
    """Smallest power of two >= dim, clipped to [SUBLANE, cap]."""
    p = 1 << max(dim - 1, 0).bit_length()
    return max(min(p, cap), SUBLANE)


def autotune_enabled(interpret: bool, override: Optional[bool] = None,
                     env: str = "REPRO_PALLAS_AUTOTUNE") -> bool:
    """Whether a tuner should measure candidates (vs heuristic default).

    Interpreter timings reflect Python overhead, not the MXU — never
    measure in interpret mode.  ``override`` (a ``KernelPolicy.autotune``
    value) wins over the env switch.
    """
    if interpret:
        return False
    if override is not None:
        return bool(override)
    return os.environ.get(env, "1") != "0"


# ----------------------------------------------------------------- cache ----

def clear_cache() -> None:
    _CACHE.clear()
    _REFUSALS.clear()
    _STATS["measured"] = _STATS["hits"] = 0


def cache_info() -> dict:
    return {"entries": len(_CACHE), **_STATS, "refused": len(_REFUSALS),
            "errors": list(_REFUSALS)}


def cache_state() -> dict:
    """JSON-able snapshot of the winner cache (key repr -> block list).

    Checkpoint manifests stash this so a resumed run reuses the SAME
    measured block sizes: autotune winners depend on timing noise, and a
    different blocking changes fp reduction order — which would silently
    break the bit-exact-resume guarantee the session layer makes.
    """
    return {repr(k): list(v) for k, v in _CACHE.items()}


def load_cache_state(state: dict) -> int:
    """Seed the winner cache from a ``cache_state()`` snapshot; existing
    entries win (the live process may already have measured).  Returns
    the number of entries loaded."""
    import ast
    n = 0
    for key_repr, blocks in (state or {}).items():
        try:
            key = ast.literal_eval(key_repr)
        except (ValueError, SyntaxError):
            continue
        if key not in _CACHE:
            _CACHE[key] = tuple(blocks)
            n += 1
    return n


def autotune(key: tuple, candidates: Sequence[tuple],
             measure: Optional[Callable[[tuple], float]]) -> tuple:
    """Return the cached winner for ``key``, measuring once on a miss.

    ``measure(candidate) -> seconds``; an exception disqualifies a
    candidate (e.g. a blocking the compiler refuses): it is counted in
    ``cache_info()["refused"]`` and its error kept in
    ``cache_info()["errors"]``.  When every candidate is refused the tune
    raises with the compiler's messages — nothing is cached.  A single
    candidate is cached without measuring (``measure`` may be None then).
    """
    if key in _CACHE:
        _STATS["hits"] += 1
        return _CACHE[key]
    best, best_t = candidates[0], float("inf")
    if len(candidates) > 1:
        _STATS["measured"] += 1
        errors = []
        for cand in candidates:
            try:
                t = measure(cand)
            except Exception as e:      # a refused blocking: kept below
                err = f"{type(e).__name__}: {e}"
                _REFUSALS.append((key, cand, err))
                errors.append(f"{cand}: {err}")
                continue
            if t < best_t:
                best, best_t = cand, t
        if best_t == float("inf"):
            raise RuntimeError(f"autotune {key}: every candidate was "
                               "refused:\n" + "\n".join(errors))
    _CACHE[key] = best
    return best


def time_call(fn, *args, iters: int = 3) -> float:
    """Mean wall-time per ``fn(*args)`` call in seconds (compile and
    warm-up excluded).

    Tuners run while the caller's step is being traced, where a plain call
    would be staged into that program instead of running, and the clock
    would time tracing.  So ``fn`` is compiled ahead of time and its
    executable called directly — that always runs — with ``args`` put on
    the device once (by a compiled identity, for the same reason)."""
    staged = jax.jit(lambda *a: a).lower(*args).compile()(*args)
    run = jax.jit(fn).lower(*staged).compile()
    jax.block_until_ready(run(*staged))       # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = run(*staged)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


# -------------------------------------------------------------- registry ----

@dataclasses.dataclass(frozen=True)
class KernelOp:
    """One registered kernel family.

    ``pallas`` and ``ref`` share a signature over ``example(seed)``'s
    args and return the same pytree, so the registry-driven parity and
    gradient tests need no per-kernel glue.  ``differentiable`` ops must
    support ``jax.grad`` through the pallas path (custom_vjp).
    """
    name: str
    pallas: Callable                      # pallas entry point
    ref: Callable                         # XLA reference, same signature
    example: Callable                     # seed -> tuple of example args
    tuner: Optional[Callable] = None      # shape -> block sizes
    tol: float = 2e-4                     # fp32 parity tolerance
    differentiable: bool = True
    grad_argnums: Tuple[int, ...] = (0,)  # args to diff in parity tests


_REGISTRY: Dict[str, KernelOp] = {}
_OP_PACKAGES = ("conv2d", "decode_attention", "flash_attention", "lrn",
                "rglru", "rwkv6")


def register(op: KernelOp) -> KernelOp:
    _REGISTRY[op.name] = op
    return op


def _ensure_registered() -> None:
    # registration happens at kernel-package import; do it lazily so that
    # importing KernelPolicy (e.g. from repro.configs) stays light — no
    # consumer pays the full pallas import chain until it asks for ops
    import importlib
    for name in _OP_PACKAGES:
        importlib.import_module(f"repro.kernels.{name}")


def get_op(name: str) -> KernelOp:
    _ensure_registered()
    return _REGISTRY[name]


def ops() -> Dict[str, KernelOp]:
    _ensure_registered()
    return dict(_REGISTRY)


# ---------------------------------------------------------------- policy ----

BACKENDS = ("auto", "xla", "pallas")
# ops a global ``backend=pallas`` switches over, and the impl name the
# model layer maps it to
_PALLAS_IMPL = {"attention": "flash", "rglru": "pallas", "rwkv6": "pallas",
                "conv2d": "pallas", "decode_attention": "pallas",
                "lrn": "pallas"}


@dataclasses.dataclass(frozen=True)
class KernelPolicy:
    """Per-run kernel selection, carried on the model config.

    ``backend`` is the global default; per-op fields override it and may
    name a concrete impl (``attention="qloop"`` for the dry-run's
    static-slice lowering, ``conv2d="pallas_im2col_ref"`` for parity).
    ``interpret`` / ``autotune`` override the env/backend resolution for
    every kernel the policy reaches.
    """
    backend: str = "auto"                 # xla | pallas | auto
    attention: Optional[str] = None       # auto|xla|chunked|qloop|flash
    # single-token flash-decode over the ring cache (serving hot path)
    decode_attention: Optional[str] = None  # auto|xla|pallas
    rglru: Optional[str] = None           # auto|xla|pallas
    rwkv6: Optional[str] = None           # auto|sequential|chunked|pallas
    conv2d: Optional[str] = None          # auto|xla|pallas|pallas_im2col_ref
    lrn: Optional[str] = None             # auto|xla|pallas
    # explicit opt-in ONLY (the global backend does not flip it): route
    # dense/MoE projection GEMMs through kernels.conv2d.matmul_bias —
    # XLA's einsum is already near-roofline there, so this is for A/B
    # benchmarking the Pallas GEMM, not a default
    matmul: Optional[str] = None          # None|pallas
    interpret: Optional[bool] = None
    autotune: Optional[bool] = None

    def __post_init__(self):
        if self.backend not in BACKENDS:
            raise ValueError(f"backend must be one of {BACKENDS}, "
                             f"got {self.backend!r}")

    def wants_pallas(self, op: str) -> bool:
        """True when the policy resolves ``op`` to its Pallas impl:
        explicitly, or via ``auto``/the global backend on a host where
        Pallas compiles.  Ops outside ``_PALLAS_IMPL`` (``matmul``) are
        explicit-opt-in: the global backend never flips them."""
        sel = getattr(self, op, None)
        if sel is None:
            if op not in _PALLAS_IMPL:
                return False
            sel = self.backend
        if sel in ("pallas", _PALLAS_IMPL.get(op)):
            return True
        if sel == "auto":
            return not resolve_interpret(self.interpret)
        return False

    def describe(self) -> dict:
        """Stable summary for logging / checkpoint manifests."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)
                if getattr(self, f.name) is not None}


def policy_of(cfg) -> KernelPolicy:
    """The config's policy, defaulting for configs predating the field."""
    pol = getattr(cfg, "kernels", None)
    return pol if pol is not None else KernelPolicy()
