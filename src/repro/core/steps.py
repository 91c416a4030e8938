"""Train/serve step builders.

``make_param_avg_step`` is the paper's algorithm (Fig. 2): per-replica
independent forward/backward/update (NO gradient communication), then
exchange+average of params and optimizer state.  It runs on the reference
engine (leading replica axis R + vmap, GSPMD sharding) and is what the
multi-pod dry-run compiles.  ``make_mesh_param_avg_step`` is the mesh-native
engine: the SAME algorithm as a ``jax.shard_map`` program over the replica
mesh axes, where the exchange lowers to real collectives (see
core/param_avg.py).  ``make_grad_avg_step`` is the modern baseline: single
param copy, gradients mean-reduced across the batch by XLA.  ``sync_every``
turns the paper's every-step averaging into local SGD (beyond-paper
extension — expressible only in the param-avg formulation).

State layout (both param-avg engines): every leaf has leading axis
R = #replicas; batches are (R, per_replica_batch, ...).  The reference
engine vmaps over axis 0; the mesh engine shards axis 0 one-replica-per-
shard so each program instance owns exactly one replica — the paper's
one-model-per-GPU memory layout, literally.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.param_avg import (AxisName, ExchangeConfig, Exchanger,
                                  as_exchanger, replicate, shard_map)
from repro.numerics import (NumericsPolicy, all_finite, cast_floats,
                            init_loss_scale_state, next_loss_scale_state)
from repro.optim.optimizers import Optimizer, apply_updates


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainState:
    """``exchange`` is the overlapped-exchange auxiliary state (None for
    the synchronous delay=0 path and for uncompressed delay=1): the
    replica-identical consensus ``base`` the compressed deltas are taken
    against, and the per-replica error-feedback ``residual``.  It rides on
    the donated TrainState so the in-flight buffers update in place.

    ``numerics`` is the loss-scaling state (None unless the
    ``NumericsPolicy`` enables static/dynamic scaling): the current
    scale, the clean-step counter, and the skipped-step count — scalars,
    replica-identical bookkeeping like the optimizer's ``count``."""
    params: Any
    opt_state: Any
    step: jnp.ndarray
    exchange: Any = None
    numerics: Any = None


def init_exchange_state(params_r, opt_r, exchanger: Exchanger,
                        delay: int = 0):
    """Auxiliary state for the delayed compressed exchange (None when the
    exchange is stateless).  ``base`` starts as a copy of the initial
    replicated state (all replicas identical at init, so it IS the
    consensus); ``residual`` starts at zero (nothing dropped yet).  Copies,
    not aliases: the TrainState is donated, and a leaf donated twice
    would force a silent defensive copy every step."""
    if delay == 0 or not exchanger.is_stateful \
            or exchanger.strategy == "none":
        return None
    tree = (params_r, opt_r)
    base = jax.tree.map(jnp.copy, tree)
    residual = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32) if x.ndim else
        jnp.zeros((), jnp.float32), tree)
    return {"base": base, "residual": residual}


def init_param_avg_state(rng, init_fn, optimizer: Optimizer,
                         n_replicas: int, *,
                         exchange: Union[ExchangeConfig, None] = None,
                         numerics: Union[NumericsPolicy, None] = None
                         ) -> TrainState:
    params = init_fn(rng)
    params_r = replicate(params, n_replicas)
    opt_r = jax.vmap(optimizer.init)(params_r)
    aux = None
    if exchange is not None:
        aux = init_exchange_state(params_r, opt_r, exchange.exchanger(),
                                  exchange.delay)
    return TrainState(params_r, opt_r, jnp.zeros((), jnp.int32), aux,
                      init_loss_scale_state(numerics))


def init_grad_avg_state(rng, init_fn, optimizer: Optimizer, *,
                        numerics: Union[NumericsPolicy, None] = None
                        ) -> TrainState:
    params = init_fn(rng)
    return TrainState(params, optimizer.init(params),
                      jnp.zeros((), jnp.int32), None,
                      init_loss_scale_state(numerics))


def _make_loss_and_grad(loss_fn: Callable, microbatch: int,
                        compute_dtype=None):
    """Shared by both engines.  loss_fn(params, batch) -> scalar.

    ``loss_fn`` must be differentiable END TO END for whatever kernel
    backend its config's ``KernelPolicy`` selects — every Pallas kernel
    (conv2d, flash_attention, rglru, rwkv6) carries a ``jax.custom_vjp``
    precisely so ``jax.value_and_grad`` here works identically on the
    ``xla`` and ``pallas`` policies (no kernel kwargs reach this layer).

    ``microbatch`` > 1 accumulates gradients over that many slices of the
    per-replica batch (fp32 accumulator) — bounds activation memory at the
    cost of re-reading params per slice.

    ``compute_dtype`` (NumericsPolicy.compute_dtype, when it differs from
    the params' own dtype) casts float params at the loss boundary; the
    returned ``loss_and_grad(params, batch, scale=None)`` multiplies the
    loss by ``scale`` inside the differentiated function, so the grads
    come out scaled — callers unscale in fp32 (loss-scaling contract).
    """
    cdt = None if compute_dtype is None else jnp.dtype(compute_dtype)

    def run_loss(params, batch, scale):
        if cdt is not None:
            # float inputs (images, frames, patch embeds) follow the
            # params to the compute dtype — mixed-dtype conv/matmul
            # operands are a lax type error, not an implicit upcast
            params = cast_floats(params, cdt)
            batch = cast_floats(batch, cdt)
        loss = loss_fn(params, batch)
        if scale is not None:
            loss = loss * scale.astype(loss.dtype)
        return loss

    def loss_and_grad(params, batch, scale=None):
        if microbatch == 1:
            if cdt is None and scale is None:
                # the pre-policy trace, bit-equal
                return jax.value_and_grad(loss_fn)(params, batch)
            return jax.value_and_grad(run_loss)(params, batch, scale)
        from repro.models._unroll import scan_or_unroll
        # split as (b/m, m) then move m to the front: microbatch i takes the
        # i-th row of each contiguous group, so a batch dim sharded over
        # 'data' stays cleanly sharded after the reshape (a plain (m, b/m)
        # reshape interleaves shards and GSPMD replicates — 4x compute).
        mb = jax.tree.map(
            lambda x: jnp.moveaxis(
                x.reshape((x.shape[0] // microbatch, microbatch)
                          + x.shape[1:]), 1, 0), batch)
        acc0 = (jnp.zeros((), jnp.float32),
                jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params))

        def mstep(carry, mbatch):
            lsum, gsum = carry
            li, g = jax.value_and_grad(run_loss)(params, mbatch, scale)
            gsum = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                gsum, g)
            return (lsum + li, gsum), None

        (lsum, gsum), _ = scan_or_unroll(mstep, acc0, mb)
        inv = 1.0 / microbatch
        return lsum * inv, jax.tree.map(lambda g: g * inv, gsum)

    return loss_and_grad


def _select_step(finite, new_tree, old_tree):
    """Per-leaf ``where``: keep the candidate update only on finite steps
    (the loss-scaling skip — both branches are already computed, so the
    select is free next to a cond)."""
    return jax.tree.map(lambda n, o: jnp.where(finite, n, o),
                        new_tree, old_tree)


def _synced(exchanger: Exchanger, params, opt_state, step, sync_every: int):
    """Apply the exchange, every step or gated every ``sync_every`` steps."""
    if sync_every == 1:
        return exchanger.average(params), exchanger.average(opt_state)
    do_sync = (step + 1) % sync_every == 0
    if exchanger.is_mesh:
        # cond, not where: do_sync is replicated across shards (every shard
        # holds the same step counter), so all shards branch together and
        # the skipped steps really skip the collectives — with where, local
        # SGD would pay full exchange traffic every step
        return jax.lax.cond(
            do_sync,
            lambda t: (exchanger.average(t[0]), exchanger.average(t[1])),
            lambda t: t, (params, opt_state))
    params = jax.tree.map(lambda a, b: jnp.where(do_sync, a, b),
                          exchanger.average(params), params)
    opt_state = jax.tree.map(lambda a, b: jnp.where(do_sync, a, b),
                             exchanger.average(opt_state), opt_state)
    return params, opt_state


def _delayed_synced(exchanger: Exchanger, prev_params, prev_opt,
                    new_params, new_opt, aux, step, sync_every: int):
    """One-step-stale overlapped exchange (delay=1).

    The collective runs on the *incoming* (pre-update) parameters, which
    have no data dependency on this step's forward/backward — XLA is free
    to schedule the communication concurrently with the compute instead of
    serializing it after the update.  The local progress is then grafted
    onto the fresh consensus::

        w_{t+1} = avg(w_t) + (new_t - w_t)

    (exact for the paper's every-step averaging up to one step of
    staleness; scalars — optimizer step counts — take the local value).
    With a stateful (compressed) exchanger the consensus comes from
    ``average_delta`` against ``aux["base"]`` with error-feedback
    residuals, and ``aux`` is rolled forward.  Returns
    ``(params, opt_state, aux)``."""
    if exchanger.strategy == "none":
        return new_params, new_opt, aux

    def exchange(operand):
        pp, po, np_, no_, ax = operand
        if exchanger.is_stateful:
            (avg_p, avg_o), new_res = exchanger.average_delta(
                (pp, po), ax["base"], ax["residual"])
            ax = {"base": (avg_p, avg_o), "residual": new_res}
        else:
            avg_p = exchanger.average(pp)
            avg_o = exchanger.average(po)

        def graft(a, n, w):
            return a + (n - w) if a.ndim else n

        return (jax.tree.map(graft, avg_p, np_, pp),
                jax.tree.map(graft, avg_o, no_, po), ax)

    operand = (prev_params, prev_opt, new_params, new_opt, aux)
    if sync_every == 1:
        return exchange(operand)
    # cond (not where) for the same reason as _synced: the predicate is
    # replica-identical, so gated-off steps really skip the collectives.
    do_sync = (step + 1) % sync_every == 0
    return jax.lax.cond(do_sync, exchange,
                        lambda t: (t[2], t[3], t[4]), operand)


def make_param_avg_step(loss_fn: Callable, optimizer: Optimizer,
                        schedule: Callable, *,
                        strategy: Union[str, Exchanger,
                                        ExchangeConfig] = "all_reduce",
                        sync_every: int = 1, microbatch: int = 1,
                        delay: int = 0, replica_exec: str = "vmap",
                        numerics: Union[NumericsPolicy, None] = None):
    """Reference engine.  loss_fn(params, batch) -> scalar; returns
    step(state, batch).  batch leaves have leading axis R matching
    state.params.  ``strategy`` is a name, an axis-less ``Exchanger``, or
    an ``ExchangeConfig`` (which then supplies ``delay``/``sync_every``).

    ``delay=1`` selects the one-step-stale overlapped exchange
    (``_delayed_synced``); ``delay=0`` is the synchronous path, bit-equal
    to the pre-policy engine.  ``replica_exec`` picks how the R
    independent replicas execute: ``"vmap"`` (batched, the default) or
    ``"scan"`` (sequential replicas, unrolled in the traced program; at
    fixed global batch each replica's smaller microbatch is more
    cache-resident, which is where replica scaling pays on hosts
    without R-way parallel compute).

    ``numerics`` (NumericsPolicy) engages mixed precision: a
    ``compute_dtype`` casts params at the loss boundary, and loss scaling
    multiplies the loss before the backward pass, unscales the grads in
    fp32, and SKIPS the whole update (params, optimizer state, scale
    growth) when any replica's grads go non-finite — the finite check is
    ANDed across all replicas so they stay in lockstep.  Pair with
    ``optim.optimizers.for_numerics`` so fp32 masters ride the optimizer
    state.  A default/fp32 policy leaves the pre-policy trace bit-equal.
    """
    if isinstance(strategy, ExchangeConfig):
        sync_every = strategy.sync_every
        delay = strategy.delay
    exchanger = as_exchanger(strategy)
    if exchanger.is_mesh:
        raise ValueError("make_param_avg_step is the axis-0 reference "
                         "engine; use make_mesh_param_avg_step for a "
                         "mesh-bound Exchanger")
    if delay not in (0, 1):
        raise ValueError(f"delay must be 0 or 1, got {delay}")
    if replica_exec not in ("vmap", "scan"):
        raise ValueError(f"replica_exec must be 'vmap' or 'scan', "
                         f"got {replica_exec!r}")
    if exchanger.is_stateful and delay == 0 \
            and exchanger.compression == "topk":
        raise ValueError("topk compression requires delay=1 (its "
                         "base+residual state rides the delayed exchange)")
    active = numerics is not None and not numerics.is_training_default
    scaling = active and numerics.loss_scale != "none"
    loss_and_grad = _make_loss_and_grad(
        loss_fn, microbatch,
        compute_dtype=(numerics.compute_dtype or numerics.param_dtype)
        if active else None)

    def _unscaled(loss, grads, scale):
        """Undo the loss scale in fp32 + the cross-replica finite check."""
        if scale is None:
            return loss, grads, jnp.asarray(True)
        inv = 1.0 / scale
        grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv, grads)
        # one scalar over EVERY replica's grads: the skip decision must be
        # replica-identical or the replicas fall out of lockstep
        return loss * inv, grads, all_finite(grads)

    def _single_replica_update(state, batch, lr, scale=None):
        # degenerate single-replica case: skip vmap entirely — the
        # size-1 batched axis confuses GSPMD sharding propagation
        # (observed as "involuntary full rematerialization" resharding)
        p0 = jax.tree.map(lambda x: x[0], state.params)
        o0 = jax.tree.map(lambda x: x[0] if x.ndim > 0 else x,
                          state.opt_state)
        b0 = jax.tree.map(lambda x: x[0], batch)
        loss, grads = loss_and_grad(p0, b0, scale)
        loss, grads, finite = _unscaled(loss, grads, scale)
        updates, o0 = optimizer.update(grads, o0, p0, lr)
        p0 = apply_updates(p0, updates)
        params = jax.tree.map(lambda x: x[None], p0)
        opt_state = jax.tree.map(
            lambda x: x[None] if x.ndim > 0 else x, o0)
        # re-attach scalar leaves' replica axis bookkeeping
        opt_state = jax.tree.map(
            lambda new, old: new if new.ndim == old.ndim else
            jnp.broadcast_to(new, old.shape),
            opt_state, state.opt_state)
        return params, opt_state, loss, finite

    def _replica_update(state, batch, lr, scale=None):
        """Independent per-replica update -> (params_r, opt_r, mean loss,
        all-replica finite flag)."""
        if replica_exec == "scan":
            # sequential replicas, unrolled: replica i's op sequence is
            # emitted after replica i-1's, so each forward/backward runs
            # at per-replica batch size with a cache-resident working
            # set.  lax.map would also sequence, but its while-loop
            # lowering defeats XLA:CPU fusion (measured ~7x slower than
            # this unroll); vmap fuses replicas back into global-batch
            # ops and loses the locality entirely.
            n_rep = jax.tree.leaves(batch)[0].shape[0]
            outs = []
            for ri in range(n_rep):
                p = jax.tree.map(lambda x: x[ri], state.params)
                o = jax.tree.map(lambda x: x[ri] if x.ndim else x,
                                 state.opt_state)
                b = jax.tree.map(lambda x: x[ri], batch)
                loss, grads = loss_and_grad(p, b, scale)
                loss, grads, finite = _unscaled(loss, grads, scale)
                updates, o = optimizer.update(grads, o, p, lr)
                outs.append((apply_updates(p, updates), o, loss, finite))
            params = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[t[0] for t in outs])
            # scalar opt leaves are replica-identical bookkeeping; keep
            # them unstacked so the state layout matches the vmap path
            opt_state = jax.tree.map(
                lambda old, *xs: jnp.stack(xs) if old.ndim else xs[0],
                state.opt_state, *[t[1] for t in outs])
            return (params, opt_state,
                    jnp.mean(jnp.stack([t[2] for t in outs])),
                    jnp.stack([t[3] for t in outs]).all())
        # 1) independent per-replica grads — no cross-replica communication
        losses, grads = jax.vmap(
            lambda p, b: loss_and_grad(p, b, scale),
            in_axes=(0, 0))(state.params, batch)
        loss = jnp.mean(losses)
        finite = jnp.asarray(True)
        if scale is not None:
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv,
                                 grads)
            # the (R, ...) grads reduce to ONE flag — the AND over replicas
            loss, finite = loss * inv, all_finite(grads)
        # 2) independent per-replica optimizer updates
        updates, opt_state = jax.vmap(
            lambda g, s, p: optimizer.update(g, s, p, lr))(
                grads, state.opt_state, state.params)
        params = jax.vmap(apply_updates)(state.params, updates)
        return params, opt_state, loss, finite

    def step(state: TrainState, batch) -> tuple:
        lr = schedule(state.step)
        n_rep = jax.tree.leaves(batch)[0].shape[0]
        scale = state.numerics["scale"] if scaling else None

        if delay == 0 and replica_exec == "vmap" and not active:
            # the pre-policy synchronous path, unchanged
            if n_rep == 1:
                params, opt_state, loss, _ = _single_replica_update(
                    state, batch, lr)
                return TrainState(params, opt_state, state.step + 1), loss
            params, opt_state, loss, _ = _replica_update(state, batch, lr)
            # 3) exchange & average params AND optimizer state (paper fn. 3)
            params, opt_state = _synced(exchanger, params, opt_state,
                                        state.step, sync_every)
            return TrainState(params, opt_state, state.step + 1), loss

        if n_rep == 1 and replica_exec == "vmap":
            params, opt_state, loss, finite = _single_replica_update(
                state, batch, lr, scale)
        else:
            params, opt_state, loss, finite = _replica_update(
                state, batch, lr, scale)

        ns = state.numerics
        if scaling:
            # poisoned step: keep the incoming state, halve the scale
            params = _select_step(finite, params, state.params)
            opt_state = _select_step(finite, opt_state, state.opt_state)
            ns = next_loss_scale_state(numerics, ns, finite)

        if delay == 0:
            params, opt_state = _synced(exchanger, params, opt_state,
                                        state.step, sync_every)
            return TrainState(params, opt_state, state.step + 1,
                              state.exchange, ns), loss

        params, opt_state, aux = _delayed_synced(
            exchanger, state.params, state.opt_state, params, opt_state,
            state.exchange, state.step, sync_every)
        return TrainState(params, opt_state, state.step + 1, aux, ns), loss

    return step


def replica_specs(tree, axis: AxisName):
    """shard_map PartitionSpecs for a param-avg pytree: leading replica dim
    over ``axis``, scalars replicated."""
    return jax.tree.map(
        lambda x: P(axis, *([None] * (x.ndim - 1))) if x.ndim else P(), tree)


def make_mesh_param_avg_step(loss_fn: Callable, optimizer: Optimizer,
                             schedule: Callable, *, mesh,
                             strategy: Union[str, Exchanger,
                                             ExchangeConfig] = "all_reduce",
                             replica_axes=("pod", "data"),
                             sync_every: int = 1, microbatch: int = 1,
                             delay: int = 0,
                             numerics: Union[NumericsPolicy, None] = None):
    """Mesh-native engine: the whole train step is one ``shard_map``
    program over ``replica_axes`` of ``mesh``; each shard owns exactly one
    replica and the exchange is a real collective (all-reduce /
    collective-permute — see core/param_avg.py).

    Requires one replica per mesh slice (R == prod of replica axis sizes)
    and all remaining mesh axes of size 1: shard_map's partial-auto mode is
    not implemented in the pinned jax, so tensor-parallel inner axes cannot
    yet be delegated to GSPMD inside the manual region — combine replicas
    with TP via the reference engine instead (launch/dryrun.py does).

    ``delay=1``: one-step-stale overlapped exchange.  The collective's
    input is the shard's *incoming* parameters — independent of this
    step's forward/backward — so XLA's latency-hiding scheduler can run
    the all-reduce / permute chain concurrently with the compute instead
    of after it (see ``_delayed_synced``).  ``delay=0`` is unchanged.

    ``numerics`` mirrors the reference engine: compute-dtype cast at the
    loss boundary, loss scaling with the skip decision ANDed across the
    replica axes via ``jax.lax.pmin`` — every shard must agree or the
    replicas fall out of lockstep.  Default/fp32 policy: pre-policy
    trace, bit-equal.
    """
    if isinstance(strategy, ExchangeConfig):
        sync_every = strategy.sync_every
        delay = strategy.delay
    if delay not in (0, 1):
        raise ValueError(f"delay must be 0 or 1, got {delay}")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = tuple(a for a in replica_axes if a in mesh.axis_names)
    if not axes:
        raise ValueError(f"none of {replica_axes} in mesh axes "
                         f"{mesh.axis_names}")
    for name, size in sizes.items():
        if name not in axes and size != 1:
            raise ValueError(
                f"mesh engine needs non-replica axis {name!r} of size 1 "
                f"(got {size}); shard_map auto-mode is unavailable — use "
                "make_param_avg_step (reference engine) for replica x TP")
    axis = axes if len(axes) > 1 else axes[0]
    n_rep = math.prod(sizes[a] for a in axes)
    exchanger = as_exchanger(strategy, axis=axis)
    if exchanger.is_stateful and delay == 0 \
            and exchanger.compression == "topk":
        raise ValueError("topk compression requires delay=1 (its "
                         "base+residual state rides the delayed exchange)")
    active = numerics is not None and not numerics.is_training_default
    scaling = active and numerics.loss_scale != "none"
    loss_and_grad = _make_loss_and_grad(
        loss_fn, microbatch,
        compute_dtype=(numerics.compute_dtype or numerics.param_dtype)
        if active else None)

    def shard_step(state: TrainState, batch) -> tuple:
        # per-shard leaves keep a leading local-replica axis of size 1
        lr = schedule(state.step)
        p_prev = jax.tree.map(lambda x: x[0], state.params)
        o_prev = jax.tree.map(lambda x: x[0] if x.ndim > 0 else x,
                              state.opt_state)
        b0 = jax.tree.map(lambda x: x[0], batch)
        scale = state.numerics["scale"] if scaling else None
        loss, grads = loss_and_grad(p_prev, b0, scale)
        ns = state.numerics
        finite = None
        if scaling:
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv,
                                 grads)
            loss = loss * inv
            # the skip decision must be identical on every shard: AND the
            # local flags across the replica axes (min over 0/1 ints)
            finite = jax.lax.pmin(
                all_finite(grads).astype(jnp.int32), axis) > 0
        updates, o0 = optimizer.update(grads, o_prev, p_prev, lr)
        p0 = apply_updates(p_prev, updates)
        if scaling:
            # poisoned step: keep the incoming state, halve the scale
            p0 = _select_step(finite, p0, p_prev)
            o0 = _select_step(finite, o0, o_prev)
            ns = next_loss_scale_state(numerics, ns, finite)
        aux = state.exchange
        if delay == 0:
            p0, o0 = _synced(exchanger, p0, o0, state.step, sync_every)
        else:
            if aux is not None:
                aux = jax.tree.map(lambda x: x[0] if x.ndim > 0 else x,
                                   aux)
            p0, o0, aux = _delayed_synced(exchanger, p_prev, o_prev,
                                          p0, o0, aux, state.step,
                                          sync_every)
            if aux is not None:
                aux = jax.tree.map(
                    lambda x: x[None] if x.ndim > 0 else x, aux)
        params = jax.tree.map(lambda x: x[None], p0)
        opt_state = jax.tree.map(
            lambda new, old: new[None] if old.ndim > new.ndim else new,
            o0, state.opt_state)
        loss = jax.lax.pmean(loss, axis)
        return TrainState(params, opt_state, state.step + 1, aux, ns), loss

    def step(state: TrainState, batch) -> tuple:
        r = jax.tree.leaves(batch)[0].shape[0]
        if r != n_rep:
            raise ValueError(
                f"mesh engine needs one replica per mesh slice: batch has "
                f"R={r} but {axes} span {n_rep} devices")
        sspec = replica_specs(state, axis)
        bspec = replica_specs(batch, axis)
        fn = shard_map(shard_step, mesh=mesh, in_specs=(sspec, bspec),
                       out_specs=(sspec, P()), check_vma=False)
        return fn(state, batch)

    return step


def make_eval_step(metric_fn: Callable, *, replica_axis: bool = True):
    """Jitted validation step: ``metric_fn(params, batch) -> dict`` of
    scalar metrics (loss + top-1 error for AlexNet, loss + perplexity for
    the LM zoo — see ``repro.train_loop.eval``).

    ``replica_axis=True`` (param-avg engines) evaluates the AVERAGED model
    — the mean over the leading replica axis, i.e. the ensemble the paper
    reports validation error for.  With ``sync_every=1`` replicas are
    already identical and the mean is a no-op numerically; under local SGD
    it is the natural "current consensus" model.  Batches carry NO replica
    axis: eval is a property of the model, not of the parallel layout.
    """

    def eval_step(params, batch):
        if replica_axis:
            params = jax.tree.map(
                lambda x: jnp.mean(x.astype(jnp.float32), axis=0).astype(
                    x.dtype), params)
        return metric_fn(params, batch)

    return eval_step


def make_grad_avg_step(loss_fn: Callable, optimizer: Optimizer,
                       schedule: Callable, *,
                       numerics: Union[NumericsPolicy, None] = None):
    """Modern baseline: loss is a mean over the global batch, so XLA
    all-reduces gradients inside the backward pass.  ``numerics`` engages
    the same mixed-precision contract as the param-avg engines (single
    param copy, so the finite check needs no cross-replica reduction)."""
    active = numerics is not None and not numerics.is_training_default
    scaling = active and numerics.loss_scale != "none"
    loss_and_grad = _make_loss_and_grad(
        loss_fn, 1, compute_dtype=(numerics.compute_dtype or numerics.param_dtype)
        if active else None)

    def step(state: TrainState, batch) -> tuple:
        lr = schedule(state.step)
        scale = state.numerics["scale"] if scaling else None
        loss, grads = loss_and_grad(state.params, batch, scale)
        ns = state.numerics
        finite = None
        if scaling:
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g.astype(jnp.float32) * inv,
                                 grads)
            loss = loss * inv
            finite = all_finite(grads)
        updates, opt_state = optimizer.update(grads, state.opt_state,
                                              state.params, lr)
        params = apply_updates(state.params, updates)
        if scaling:
            params = _select_step(finite, params, state.params)
            opt_state = _select_step(finite, opt_state, state.opt_state)
            ns = next_loss_scale_state(numerics, ns, finite)
        return TrainState(params, opt_state, state.step + 1,
                          state.exchange, ns), loss

    return step


def make_serve_step(decode_fn: Callable):
    """decode_fn(params, cache, tokens, pos) -> (logits, cache).

    Greedy argmax serving step: feeds back the sampled token.
    """

    def step(params, cache, tokens, pos):
        logits, cache = decode_fn(params, cache, tokens, pos)
        next_tok = jnp.argmax(logits[:, -1:], axis=-1).astype(tokens.dtype)
        return next_tok, cache

    return step


def reshape_for_replicas(batch, n_replicas: int):
    """(B, ...) host batch -> (R, B/R, ...)."""
    def f(x):
        b = x.shape[0]
        assert b % n_replicas == 0, (b, n_replicas)
        return x.reshape((n_replicas, b // n_replicas) + x.shape[1:])
    return jax.tree.map(f, batch)
