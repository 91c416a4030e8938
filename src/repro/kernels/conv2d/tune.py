"""Block-size autotuning for the conv2d kernels.

The shared machinery (``resolve_interpret``, the process-level autotune
cache, measurement helpers) lives in ``repro.kernels.common`` — this
module was its first client and now only contributes the conv-specific
tuners.  ``resolve_interpret`` / ``autotune`` / ``clear_cache`` /
``cache_info`` are re-exported for back-compat with PR-2-era callers.

``matmul_blocks`` / ``conv_blocks``: ``(bm, bk, bn)`` were fixed at 128³
regardless of problem shape.  Block sizes come from the shared
shape-keyed autotune cache: for each distinct problem shape the
candidate blockings are measured once (compiled backends only — timing
the interpreter is meaningless) and the winner is memoised for the rest
of the process.  Tiny problems get clipped blocks instead of padding
everything up to 128.  ``REPRO_CONV_AUTOTUNE=0`` (or the global
``REPRO_PALLAS_AUTOTUNE=0``) disables measurement and always returns the
heuristic default.
"""
from __future__ import annotations

import os
from typing import Tuple

from repro.kernels import common
from repro.kernels.common import (LANE, autotune_enabled, cache_info,
                                  clear_cache, pow2_clip, resolve_interpret,
                                  time_call)

# re-export (PR-2 name): the cache entry point — NOT the ``autotune=``
# override parameter the block tuners take below
autotune = common.autotune

__all__ = ["resolve_interpret", "autotune", "clear_cache", "cache_info",
           "matmul_blocks", "conv_blocks"]

_pow2_clip = pow2_clip           # back-compat aliases (PR-2 private names)
_time_call = time_call
_LANE = LANE


def _autotune_enabled(interpret: bool, override: bool = None) -> bool:
    # a policy override beats the env switches; legacy env wins otherwise
    if override is None and os.environ.get("REPRO_CONV_AUTOTUNE") == "0":
        return False
    return autotune_enabled(interpret, override)


def matmul_blocks(m: int, k: int, n: int, dtype, *, interpret: bool,
                  autotune: bool = None,
                  w_dtype=None) -> Tuple[int, int, int]:
    """(bm, bk, bn) for the blocked matmul; autotuned on compiled backends.

    ``w_dtype`` widens the cache key to the (x, w) dtype tuple when the
    operands differ (a NumericsPolicy mixing precisions): winners are
    measured per byte-traffic profile, so mixed-dtype problems must not
    share entries with same-dtype ones."""
    default = (pow2_clip(m, LANE), pow2_clip(k, LANE), pow2_clip(n, LANE))
    dt_key = str(dtype) if w_dtype is None else (str(dtype), str(w_dtype))
    key = ("matmul", m, k, n, dt_key)
    if not _autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)

    cands = {default}
    for bm in (128, 256, 512):
        for bn in (128, 256):
            if bm <= 2 * m and bn <= 2 * n:
                cands.add((bm, pow2_clip(min(k, LANE), LANE), bn))
    import numpy as np
    from repro.kernels.conv2d import conv2d as _k
    x = np.random.default_rng(0).normal(size=(m, k)).astype(dtype)
    w = np.random.default_rng(1).normal(size=(k, n)).astype(w_dtype or dtype)
    b = np.zeros((n,), w_dtype or dtype)

    def measure(c):
        bm, bk, bn = c
        return time_call(
            lambda x, w, b: _k.matmul_bias(x, w, b, bm=bm, bk=bk, bn=bn,
                                           interpret=False), x, w, b)
    return common.autotune(key, sorted(cands), measure)


# fused conv tiles M = OH*OW by whole output rows; bm is the budget a
# tile's rows fill (conv2d._row_tiling).  A larger cap than the matmul's
# 128 means fewer, taller tiles over the VMEM-resident image.
_CONV_BM_CAP = 512


def conv_blocks(b: int, oh: int, ow: int, kernel: int, cin: int, cout: int,
                stride: int, dtype, *, groups: int = 1, interpret: bool,
                autotune: bool = None, w_dtype=None) -> Tuple[int, int]:
    """(bm, bn) for the fused implicit-GEMM conv (reduction is unrolled
    in-kernel, so there is no bk).  ``groups`` is part of the cache key —
    a grouped layer tiles N per diagonal block (Cout/G wide), so its
    winning blocking is NOT the ungrouped layer's — and bn defaults to
    the per-group output width, never a whole-Cout tile.  A compiled
    grouped conv takes a whole lane (128) at least: its bn tile is one
    of several along the output's lane axis, which Mosaic only splits at
    lane multiples.  ``w_dtype`` widens the key to the (x, w) dtype tuple
    for mixed-precision calls (see ``matmul_blocks``)."""
    m = oh * ow
    bn = pow2_clip(cout // groups, LANE)
    if groups > 1 and not interpret:
        bn = LANE
    default = (pow2_clip(m, _CONV_BM_CAP), bn)
    dt_key = str(dtype) if w_dtype is None else (str(dtype), str(w_dtype))
    key = ("conv", b, oh, ow, kernel, cin, cout, stride, groups, dt_key)
    if not _autotune_enabled(interpret, autotune):
        return common.autotune(key, [default], None)

    cands = {default}
    for bm in (128, 256, 512):
        if bm <= 2 * m:
            cands.add((bm, default[1]))
    import numpy as np
    from repro.kernels.conv2d import ops as _ops
    h = (oh - 1) * stride + kernel
    w_sz = (ow - 1) * stride + kernel
    x = np.random.default_rng(0).normal(size=(b, h, w_sz, cin)).astype(dtype)
    wt = np.random.default_rng(1).normal(
        size=(kernel, kernel, cin // groups, cout)).astype(w_dtype or dtype)

    def measure(c):
        bm, bn = c
        return time_call(
            lambda x, wt: _ops.conv2d_fused(x, wt, stride=stride, padding=0,
                                            groups=groups, bm=bm, bn=bn,
                                            interpret=False), x, wt)
    return common.autotune(key, sorted(cands), measure)
