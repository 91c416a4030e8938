"""The comparison that decides ``correct``.

Set-up drives the program's compiled step from the seed through its first
three steps, on three distinct batches, through the window's own call and
feed, and reads it after step 1 and after step 3.  After the window the
plain reference (``reference.py``) trains from the same weights on the
same batches, and three numbers are compared with the cell's limits:

``loss_gap``    max over the three steps of |loss - ref| / |ref|.
``grad_gap``    the first gradient as the optimizer got it, worked out
                from its momentum after step 1 (``v1 - wd * p0``): per
                leaf, |norm - ref norm| / max(ref norm of that leaf, median
                ref leaf norm), worst leaf and worst replica.
``change_gap``  the same gap for the norm of each leaf's change after
                three steps (``p3 - p0``).

Leaves whose reference gradient is under a thousandth of the median
leaf's move by round-off alone and are left out of ``change_gap``.
Feeds that transform batches add their own exact numbers
(``batch_gap``).  Norms are taken on the device; only (leaves x
replicas) numbers come to the host.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference

NEGLIGIBLE_GRAD = 1e-3      # of the median leaf's reference gradient norm


def leaf_names(tree) -> list:
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _norms(leaves, lead: bool):
    """(n_leaves, R) norms; ``lead``: leaves carry a leading replica axis."""
    out = []
    for x in leaves:
        x = x.astype(jnp.float32)
        axes = tuple(range(1, x.ndim)) if lead else None
        n = jnp.sqrt(jnp.sum(x * x, axis=axes))
        out.append(n if lead else n[None])
    return jnp.stack(out)


@jax.jit
def _grad_norms(velocity, p0, wd):
    return _norms([v - wd * p[None] for v, p in
                   zip(jax.tree.leaves(velocity), jax.tree.leaves(p0))],
                  lead=True)


@jax.jit
def _change_norms(params, p0):
    return _norms([p - q[None] for p, q in
                   zip(jax.tree.leaves(params), jax.tree.leaves(p0))],
                  lead=True)


@jax.jit
def _ref_norms(grad, params, p0):
    g = _norms(jax.tree.leaves(grad), lead=False)[:, 0]
    d = _norms([p - q for p, q in zip(jax.tree.leaves(params),
                                      jax.tree.leaves(p0))], lead=False)
    return g, d[:, 0]


def program_grad_norms(state, p0, weight_decay: float) -> np.ndarray:
    """After step 1: norm of ``v1 - wd * p0`` per leaf and replica."""
    return np.asarray(_grad_norms(state.opt_state["velocity"], p0,
                                  jnp.float32(weight_decay)))


def program_change_norms(state, p0) -> np.ndarray:
    """After step 3: norm of ``p3 - p0`` per leaf and replica."""
    return np.asarray(_change_norms(state.params, p0))


def reference_readings(cfg: dict, p0, batches, operands=None) -> dict:
    """The reference's losses and per-leaf norms over ``batches``."""
    losses, grad, p3 = reference.train(cfg, p0, batches, operands)
    g, d = _ref_norms(grad, p3, p0)
    return {"losses": losses, "grad_norms": np.asarray(g)[:, None],
            "change_norms": np.asarray(d)[:, None]}


def _worst_gap(got: np.ndarray, ref: np.ndarray, keep: np.ndarray):
    """Worst leaf/replica of |got - ref| / max(ref, median ref)."""
    ref = ref[:, 0]
    den = np.maximum(ref, np.median(ref))[:, None]
    gap = np.abs(got - ref[:, None]) / den
    gap[~keep] = 0.0
    i, _ = np.unravel_index(int(np.argmax(gap)), gap.shape)
    return float(gap.max()), int(i)


def numbers(prog: dict, ref: dict, names=None) -> dict:
    """The compared numbers: ``prog`` and ``ref`` are readings dicts
    (``losses``, ``grad_norms`` and ``change_norms`` as (leaves, R))."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr))) \
        if np.all(np.isfinite(lp)) else float("inf")
    gref = ref["grad_norms"][:, 0]
    keep = gref >= NEGLIGIBLE_GRAD * np.median(gref)
    grad_gap, gi = _worst_gap(prog["grad_norms"], ref["grad_norms"],
                              np.ones_like(keep))
    change_gap, ci = _worst_gap(prog["change_norms"], ref["change_norms"],
                                keep)
    out = {"loss_gap": loss_gap, "grad_gap": grad_gap,
           "change_gap": change_gap}
    for k in ("grad_gap", "change_gap"):
        if not np.isfinite(out[k]):
            out[k] = float("inf")
    if names is not None:
        out["_worst"] = {"grad_gap": names[gi], "change_gap": names[ci],
                         "left_out": [n for n, k in zip(names, keep)
                                      if not k]}
    return out


def verdict(nums: dict, limits: dict):
    """-> (correct, {name: {"value", "limit"}}) over the cell's limits.
    A number that is missing, or not finite, fails."""
    check, ok = {}, True
    for name, limit in limits.items():
        v = nums.get(name, float("nan"))
        good = bool(np.isfinite(v) and v <= limit)
        ok = ok and good
        check[name] = {"value": v, "limit": limit}
    return ok, check
