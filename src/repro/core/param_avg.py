"""Replica exchange-and-average — the paper's §2.2 / Fig. 2, generalized.

Two execution engines share one ``Exchanger`` API:

* **mesh engine** (``axis=<mesh axis name(s)>``): each replica is one shard
  of a ``jax.shard_map`` program over the ('pod','data') mesh axes, and the
  strategies are per-shard collectives (``jax.lax.pmean`` /
  ``jax.lax.ppermute``) — every strategy lowers to the real collective its
  name promises.  This is the production path (launch/train.py,
  examples/train_dataparallel.py).
* **reference engine** (``axis=None``): replicated state carries an explicit
  leading axis R and each strategy is an ordinary jnp program over axis 0.
  It is the interpretable spec the mesh engine is tested against, and the
  path the GSPMD dry-run (launch/dryrun.py) compiles when replicas combine
  with tensor parallelism.

Strategy -> collective:

  ``all_reduce``  mean across replicas                       -> all-reduce
                  (1 fused reduction per step)
  ``ring``        R-1 neighbour shifts, accumulate           -> collective-
                  permute chain (the closest analogue of the paper's
                  sequential P2P copies around a ring)
  ``pairwise``    log2(R) hypercube exchange+average rounds  -> collective-
                  permute pairs (R=2 reproduces the paper's Fig. 2 EXACTLY:
                  one exchange, then average on both replicas)
  ``none``        no synchronization (local SGD / sync-every-k)

All strategies are exact means (for power-of-two R), so they are numerically
interchangeable; they differ only in the communication schedule — which is
precisely the axis the paper's Table 1 explores with its hardware.  The same
function is applied to params AND optimizer state (momentum), per the
paper's footnote 3.

Compression (beyond-paper, the Theano-MPI direction): an ``Exchanger`` can
carry an ``ExchangeCompression`` policy lowering the exchanged volume:

  ``none``  full-precision dense exchange (the paper's path; default)
  ``bf16``  wire dtype bf16 — halves the physically-moved bytes
  ``topk``  top-k-magnitude sparsification of the *delta from the shared
            consensus base* with error-feedback residuals (what top-k
            drops this step is carried into the next step's delta, so
            nothing is lost, only delayed).  Stateful: needs the
            base+residual buffers that ride on ``TrainState.exchange``
            under the delay=1 overlapped exchange (core/steps.py), and an
            all-gather schedule (k values + k indices per replica), so it
            composes with ``all_reduce`` only.

``average`` is the stateless whole-value exchange (none/bf16);
``average_delta`` is the stateful compressed-delta exchange the delayed
path uses (none/bf16/topk, with residual threading).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from jax import shard_map  # noqa: F401 — re-exported to core.steps

STRATEGIES = ("all_reduce", "ring", "pairwise", "none")
COMPRESSIONS = ("none", "bf16", "topk")

# HLO op each strategy's mesh-engine lowering must contain (None: no
# communication).  tests/core/test_exchange_mesh.py asserts this.
EXPECTED_COLLECTIVE = {"all_reduce": "all-reduce",
                       "ring": "collective-permute",
                       "pairwise": "collective-permute",
                       "none": None}

AxisName = Union[str, Tuple[str, ...]]


# ------------------------------------------------ reference (axis-0) engine --

def _avg_all_reduce(x):
    return jnp.broadcast_to(jnp.mean(x, axis=0, keepdims=True), x.shape)


def _avg_ring(x):
    r = x.shape[0]
    acc = x
    cur = x
    for _ in range(r - 1):
        cur = jnp.roll(cur, shift=1, axis=0)     # neighbour pass
        acc = acc + cur
    return acc / r


def _avg_pairwise(x):
    r = x.shape[0]
    assert r & (r - 1) == 0, f"pairwise needs power-of-two replicas, got {r}"
    idx = jnp.arange(r)
    dim = 1
    while dim < r:
        partner = idx ^ dim                      # hypercube neighbour
        x = 0.5 * (x + jnp.take(x, partner, axis=0))
        dim <<= 1
    return x


_FNS = {"all_reduce": _avg_all_reduce, "ring": _avg_ring,
        "pairwise": _avg_pairwise}


# ------------------------------------------------- mesh (per-shard) engine --
# These run INSIDE shard_map: x is one replica's slice (no leading R axis)
# and the replica index lives on the named mesh axis.

def _shard_all_reduce(x, axis):
    return jax.lax.pmean(x, axis)


def _shard_ring(x, axis):
    r = jax.lax.psum(1, axis)
    perm = [(i, (i + 1) % r) for i in range(r)]  # same direction as the
    acc = x                                      # reference's roll(+1)
    cur = x
    for _ in range(r - 1):
        cur = jax.lax.ppermute(cur, axis, perm)
        acc = acc + cur
    return acc / r


def _shard_pairwise(x, axis):
    r = jax.lax.psum(1, axis)
    assert r & (r - 1) == 0, f"pairwise needs power-of-two replicas, got {r}"
    dim = 1
    while dim < r:
        perm = [(i, i ^ dim) for i in range(r)]
        x = 0.5 * (x + jax.lax.ppermute(x, axis, perm))
        dim <<= 1
    return x


_SHARD_FNS = {"all_reduce": _shard_all_reduce, "ring": _shard_ring,
              "pairwise": _shard_pairwise}


# ------------------------------------------------------------ Exchanger API --

@dataclasses.dataclass(frozen=True)
class Exchanger:
    """One exchange schedule bound to an execution engine.

    ``axis=None``: reference engine — ``average`` expects every leaf to
    carry a leading replica axis R and averages over it in plain jnp.

    ``axis=<name or tuple of names>``: mesh engine — ``average`` must be
    called inside ``jax.shard_map`` with ``axis`` manual; leaves are single
    replica slices and the average is a real collective over the mesh axis.

    ``compression`` lowers the exchanged volume (module docstring).
    ``topk_frac`` is the kept fraction per leaf for ``topk`` (1.0 keeps
    everything — identity compression, bit-equal to ``none`` by
    construction: it routes through the same dense path).
    """
    strategy: str = "all_reduce"
    axis: Optional[AxisName] = None
    compression: str = "none"
    topk_frac: float = 0.01

    def __post_init__(self):
        if self.strategy not in STRATEGIES:
            raise ValueError(f"unknown strategy {self.strategy!r}; "
                             f"one of {STRATEGIES}")
        if self.compression not in COMPRESSIONS:
            raise ValueError(f"unknown compression {self.compression!r}; "
                             f"one of {COMPRESSIONS}")
        if not 0.0 < self.topk_frac <= 1.0:
            raise ValueError(f"topk_frac must be in (0, 1], "
                             f"got {self.topk_frac}")
        if self.compression == "topk" and self.strategy not in (
                "all_reduce", "none"):
            raise ValueError(
                "topk compression is an all-gather schedule (k values + k "
                "indices per replica); ring/pairwise permute dense buffers "
                f"— use bf16 with strategy {self.strategy!r}")

    @property
    def is_mesh(self) -> bool:
        return self.axis is not None

    @property
    def expected_collective(self) -> Optional[str]:
        """HLO op the mesh engine lowers this strategy to."""
        if self.strategy != "none" and self.compression == "topk" \
                and self.topk_frac < 1.0:
            return "all-gather"
        return EXPECTED_COLLECTIVE[self.strategy]

    @property
    def is_stateful(self) -> bool:
        """True when the exchange needs base+residual buffers on the train
        state (the delayed compressed-delta path)."""
        return self.compression != "none"

    def _wire_cast(self, x):
        """Cast to the wire dtype (what the collective physically moves)."""
        if self.compression == "bf16":
            return x.astype(jnp.bfloat16)
        return x.astype(jnp.float32)

    def average(self, tree):
        """Stateless exchange+average of whole values (params or optimizer
        state).  Supports ``none``/``bf16``; ``topk`` is delta-based and
        needs ``average_delta`` (whole params are dense — sparsifying them
        directly would discard most of the model)."""
        if self.strategy == "none":
            return tree
        if self.compression == "topk":
            raise ValueError(
                "topk compression is stateful (delta from a shared base + "
                "error-feedback residual); use average_delta via the "
                "delay=1 overlapped exchange (core/steps.py)")
        if self.is_mesh:
            fn = _SHARD_FNS[self.strategy]

            def avg(x):
                if x.ndim == 0:      # scalars (e.g. adam count) stay equal
                    return x
                xf = self._wire_cast(x)
                return fn(xf, self.axis).astype(jnp.float32).astype(x.dtype)
        else:
            fn = _FNS[self.strategy]

            def avg(x):
                if x.ndim == 0:
                    return x
                xf = self._wire_cast(x)
                return fn(xf).astype(jnp.float32).astype(x.dtype)

        return jax.tree.map(avg, tree)

    # ------------------------------------------------ compressed deltas --
    def _n_replicas(self, x):
        if self.is_mesh:
            return jax.lax.psum(1, self.axis)
        return x.shape[0]

    def _mean(self, x):
        """Dense collective mean in x's dtype (engine-dispatched)."""
        return (_SHARD_FNS[self.strategy](x, self.axis) if self.is_mesh
                else _FNS[self.strategy](x))

    def _topk_mean(self, d, k: int):
        """Mean of per-replica top-k-sparsified deltas + what was dropped.

        Returns ``(mean, kept)`` where ``kept`` is this replica's dense
        top-k selection (for the error-feedback residual ``d - kept``).
        Mesh engine: all-gather k values + k indices per replica and
        scatter-add locally — the collective moves 2k entries instead of n.
        Reference engine: the same flattened scatter in the same order, so
        the two engines agree bitwise.
        """
        if self.is_mesh:
            n = d.size
            flat = d.reshape(-1)
            mag, idx = jax.lax.top_k(jnp.abs(flat), k)
            vals = jnp.take(flat, idx)
            allv = jax.lax.all_gather(vals, self.axis)       # (R, k)
            alli = jax.lax.all_gather(idx, self.axis)        # (R, k)
            r = jax.lax.psum(1, self.axis)
            total = jnp.zeros((n,), d.dtype).at[alli.reshape(-1)].add(
                allv.reshape(-1))
            kept = jnp.zeros((n,), d.dtype).at[idx].set(vals)
            return (total / r).reshape(d.shape), kept.reshape(d.shape)
        r, n = d.shape[0], d[0].size
        flat = d.reshape(r, n)
        mag, idx = jax.lax.top_k(jnp.abs(flat), k)           # (R, k)
        vals = jnp.take_along_axis(flat, idx, axis=1)
        total = jnp.zeros((n,), d.dtype).at[idx.reshape(-1)].add(
            vals.reshape(-1))
        mean = jnp.broadcast_to(total / r, (r, n)).reshape(d.shape)
        rowed = idx + n * jnp.arange(r, dtype=idx.dtype)[:, None]
        kept = jnp.zeros((r * n,), d.dtype).at[rowed.reshape(-1)].set(
            vals.reshape(-1)).reshape(d.shape)
        return mean, kept

    def average_delta(self, tree, base, residual):
        """Stateful compressed exchange of deltas with error feedback.

        ``base`` must be replica-identical (the previous exchange's output —
        every replica computed the same average).  Per leaf::

            d    = (x - base) + residual        # what we owe the consensus
            c    = compress(d)                  # what actually moves
            out  = base + collective_mean(c)    # new consensus(+own kept)
            res' = d - c                        # dropped -> next step

        Returns ``(averaged_tree, new_residual)``.  With ``none`` or
        ``topk_frac=1.0`` the compressor is the identity, so ``out`` equals
        the dense delta exchange bit-for-bit and ``res'`` stays zero.
        """
        if self.strategy == "none":
            return tree, residual

        def one(x, b, r):
            if x.ndim == 0:             # scalars never exchanged
                return x, r
            d = x.astype(jnp.float32) - b.astype(jnp.float32) + r
            if self.compression == "topk":
                per_rep = d.size if self.is_mesh else d[0].size
                k = max(1, int(round(self.topk_frac * per_rep)))
                if k < per_rep:
                    avg_c, kept = self._topk_mean(d, k)
                    return ((b.astype(jnp.float32) + avg_c).astype(x.dtype),
                            d - kept)
                # k == n: identity compression.  The residual is zero by
                # induction (it starts zero and stays zero here), so
                # base + mean(x - base) == mean(x) exactly — take the SAME
                # dense whole-value arithmetic as compression "none" so the
                # result is bit-equal to it, not just close.
                avg_c = self._mean(x.astype(jnp.float32))
                return avg_c.astype(jnp.float32).astype(x.dtype), \
                    jnp.zeros_like(r)
            if self.compression == "bf16":
                c = d.astype(jnp.bfloat16)
                avg_c = self._mean(c).astype(jnp.float32)
                return (b.astype(jnp.float32) + avg_c).astype(x.dtype), \
                    d - c.astype(jnp.float32)
            avg_c = self._mean(d)
            return (b.astype(jnp.float32) + avg_c).astype(x.dtype), \
                jnp.zeros_like(r)

        flat = jax.tree.map(one, tree, base, residual)
        avg = jax.tree.map(lambda _, p: p[0], tree, flat)
        new_res = jax.tree.map(lambda _, p: p[1], tree, flat)
        return avg, new_res

    def logical_bytes(self, tree, n_replicas: int) -> int:
        """Bytes one replica logically transmits per exchange under this
        policy (the low-bandwidth axis the benchmark reports).  ``none``:
        full fp32 leaves; ``bf16``: half; ``topk``: k values + k int32
        indices per leaf."""
        total = 0
        for x in jax.tree.leaves(tree):
            if x.ndim == 0:
                continue
            n = int(x.size) // (n_replicas if not self.is_mesh else 1)
            if self.strategy == "none":
                continue
            if self.compression == "bf16":
                total += 2 * n
            elif self.compression == "topk":
                k = max(1, int(round(self.topk_frac * n)))
                total += (4 + 4) * k if k < n else 4 * n
            else:
                total += 4 * n
        return total


def as_exchanger(strategy: Union[str, Exchanger, "ExchangeConfig"],
                 axis: Optional[AxisName] = None) -> Exchanger:
    """Accept a strategy name, an ExchangeConfig, or a ready Exchanger
    (axis overrides engine)."""
    if isinstance(strategy, ExchangeConfig):
        return strategy.exchanger(axis=axis)
    if isinstance(strategy, Exchanger):
        if axis is not None and strategy.axis != axis:
            return dataclasses.replace(strategy, axis=axis)
        return strategy
    return Exchanger(strategy, axis=axis)


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """The exchange policy that rides on configs and the CLI — everything
    about how replicas synchronize, in one frozen value:

    ``strategy``     communication schedule (STRATEGIES)
    ``compression``  wire compression (COMPRESSIONS)
    ``topk_frac``    kept fraction for topk
    ``delay``        0 = synchronous exchange after the update (the paper's
                     path, bit-equal to the pre-policy engines); 1 = one-
                     step-stale overlapped exchange (core/steps.py): the
                     collective for step t's parameters runs inside step
                     t+1's program, concurrent with its forward/backward
    ``sync_every``   local SGD: exchange every k-th step only
    """
    strategy: str = "all_reduce"
    compression: str = "none"
    topk_frac: float = 0.01
    delay: int = 0
    sync_every: int = 1

    def __post_init__(self):
        if self.delay not in (0, 1):
            raise ValueError(f"delay must be 0 or 1, got {self.delay}")
        if self.sync_every < 1:
            raise ValueError(f"sync_every must be >= 1, "
                             f"got {self.sync_every}")
        if self.compression == "topk" and self.delay == 0:
            raise ValueError(
                "topk compression needs the delay=1 overlapped exchange "
                "(its error-feedback residual and consensus base live in "
                "TrainState.exchange, which only the delayed path carries)")
        # strategy/compression cross-validation happens in Exchanger
        self.exchanger()

    def exchanger(self, axis: Optional[AxisName] = None) -> Exchanger:
        return Exchanger(self.strategy, axis=axis,
                         compression=self.compression,
                         topk_frac=self.topk_frac)

    def describe(self) -> str:
        out = f"{self.strategy}/delay{self.delay}/{self.compression}"
        if self.compression == "topk":
            out += f"@{self.topk_frac:g}"
        if self.sync_every != 1:
            out += f"/every{self.sync_every}"
        return out


def exchange_average(tree, strategy: Union[str, Exchanger] = "all_reduce"):
    """Average every leaf of a replicated pytree over its leading R axis
    (reference engine; kept as the stable public entrypoint)."""
    ex = as_exchanger(strategy)
    if ex.is_mesh:
        raise ValueError("exchange_average is the axis-0 reference; call "
                         "Exchanger.average inside shard_map for the mesh "
                         "engine")
    return ex.average(tree)


def replicate(tree, n_replicas: int):
    """Give every leaf a leading replica axis (identical initial copies —
    the paper initializes both GPUs' models identically)."""
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_replicas,) + x.shape), tree)


def unreplicate(tree):
    """Take replica 0 (after averaging all replicas are identical)."""
    return jax.tree.map(lambda x: x[0], tree)


def replica_spread(tree) -> jnp.ndarray:
    """Max abs deviation across replicas — 0 right after a sync step; a
    diagnostic for local-SGD drift."""
    def spread(x):
        if x.ndim == 0:
            return jnp.zeros((), jnp.float32)
        xf = x.astype(jnp.float32)
        return jnp.max(jnp.abs(xf - jnp.mean(xf, axis=0, keepdims=True)))
    return jax.tree.reduce(jnp.maximum, jax.tree.map(spread, tree),
                           jnp.zeros((), jnp.float32))
