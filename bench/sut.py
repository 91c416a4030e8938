"""The system under test: the trainer's own train step for one cell.

``repro.launch.train.main`` builds its step and trains in one call, and
returns the step only after training (``Run.session.step_fn``), so the
harness builds the step through the same calls ``main`` makes for the
cell's device count, from the trainer's own parser defaults:

* one chip — the reference engine (``make_param_avg_step``) with one
  replica, jitted with the TrainState donated;
* four chips — the ``shard_map`` mesh engine
  (``make_mesh_param_avg_step``) over ``make_replica_mesh``, one replica
  per chip, the state and every batch laid out by ``replica_sharding``.

The weights come from the benchmark (``seeding.init_params``); the
program's ``init_param_avg_state`` turns them into its TrainState.

``fault`` plants one of the faults the correctness check must catch,
underneath the timed path; it exists for the benchmark's own tests and
calibration and no cell sets it.
"""
from __future__ import annotations

import dataclasses
import time

import jax

FAULTS = ("frozen", "half_batch", "no_exchange", "loss_altered")


def program_config(cfg: dict):
    """The program's ``AlexNetConfig`` for a configuration file."""
    from repro.configs.alexnet import AlexNetConfig, ConvSpec
    return AlexNetConfig(
        name=cfg["name"], image_size=cfg["image_size"],
        in_channels=cfg["in_channels"], n_classes=cfg["n_classes"],
        convs=tuple(ConvSpec(**c) for c in cfg["convs"]),
        fc_dim=cfg["fc_dim"], dropout=cfg["dropout"],
        faithful=cfg["faithful"], lrn_n=cfg["lrn_n"],
        lrn_alpha=cfg["lrn_alpha"], lrn_beta=cfg["lrn_beta"],
        lrn_k=cfg["lrn_k"], dtype=cfg["dtype"])


@dataclasses.dataclass
class System:
    """The built step and what it needs around it."""
    step: object            # compiled step(state, batch) -> (state, loss)
    state: object           # the TrainState the step owns (donated)
    n_rep: int
    layout: object          # tree in step layout -> its shardings
    put_replicated: object  # tree -> every chip of the cell
    kernels: dict           # op -> backend it resolved to
    parts: dict             # set-up seconds: trace_autotune_s, compile_s
    make_state: object      # weights -> a fresh TrainState, laid out


def batch_shapes(cfg: dict) -> dict:
    """The timed global batch's shapes and dtypes."""
    b, s = cfg["global_batch"], cfg["crop"]
    return {"images": ((b, s, s, cfg["in_channels"]), "float32"),
            "labels": ((b,), "int32")}


def build(cfg: dict, chips: int, params, *, trainer_argv=(),
          fault=None) -> System:
    """Build, trace and compile the trainer's step for ``chips`` chips,
    at the timed batch shape.  ``params`` are the weights, with no
    replica axis."""
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                                SingleDeviceSharding)
    from repro.core import (ExchangeConfig, init_param_avg_state,
                            make_mesh_param_avg_step, make_param_avg_step)
    from repro.launch import train
    from repro.launch.mesh import make_replica_mesh
    from repro.models import alexnet
    from repro.numerics import get_policy
    from repro.optim.optimizers import for_numerics, get_optimizer
    from repro.sharding.specs import replica_sharding

    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    args = train.build_parser().parse_args(
        ["--arch", "alexnet", "--batch", str(cfg["global_batch"]),
         "--lr", str(cfg["lr"]), "--optimizer", cfg["optimizer"],
         *trainer_argv])
    npol = get_policy(args.numerics)
    exch = ExchangeConfig(
        strategy="none" if fault == "no_exchange" else args.strategy,
        compression=args.exchange_compression, topk_frac=args.topk_frac,
        delay=args.exchange_delay, sync_every=args.sync_every)
    pcfg = dataclasses.replace(program_config(cfg),
                               kernels=train.make_policy(args),
                               numerics=npol, exchange=exch)
    if pcfg.n_params() != cfg["n_params"]:
        raise ValueError(f"{cfg['name']}: the program's net has "
                         f"{pcfg.n_params()} params, the file says "
                         f"{cfg['n_params']}")
    opt = for_numerics(get_optimizer(args.optimizer), npol)
    if fault == "frozen":
        opt = dataclasses.replace(
            opt, update=lambda g, s, p, lr: (
                jax.tree.map(lambda x: 0.0 * x, g), s))
    sched = train.make_controller(args).schedule()

    def loss(params, batch):
        images, labels = batch["images"], batch["labels"]
        if fault == "half_batch":
            images, labels = images[: len(images) // 2], \
                labels[: len(labels) // 2]
        return alexnet.loss_fn(params, pcfg, images, labels)

    n_rep = chips
    if chips > 1:
        mesh = make_replica_mesh(n_rep)
        step_fn = make_mesh_param_avg_step(
            loss, opt, sched, mesh=mesh, strategy=exch,
            replica_axes=("data",), numerics=npol)
        lay = lambda tree: replica_sharding(  # noqa: E731
            tree, mesh, replica_axes=("data",))
        replicated = NamedSharding(mesh, P())
    else:
        step_fn = make_param_avg_step(loss, opt, sched, strategy=exch,
                                      replica_exec=args.replica_exec,
                                      numerics=npol)
        replicated = SingleDeviceSharding(jax.devices()[0])
        lay = lambda tree: jax.tree.map(  # noqa: E731
            lambda _: replicated, tree)
    if fault == "loss_altered":
        inner = step_fn

        def step_fn(state, batch):
            state, loss_v = inner(state, batch)
            return state, loss_v * 1.01

    def put_replicated(tree):
        return jax.device_put(tree, replicated)

    init_state = jax.jit(lambda p: init_param_avg_state(
        None, lambda _: p, opt, n_rep, exchange=exch, numerics=npol))

    def make_state(params):
        state = init_state(put_replicated(params))
        return jax.device_put(state, lay(state))

    state = make_state(params)
    shapes = {k: jax.ShapeDtypeStruct(
        (n_rep, shape[0] // n_rep) + shape[1:], dtype)
        for k, (shape, dtype) in batch_shapes(cfg).items()}
    batch = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, lay(shapes))

    from repro.kernels import common
    t0 = time.perf_counter()
    lowered = jax.jit(step_fn, donate_argnums=0).lower(state, batch)
    t1 = time.perf_counter()
    step = lowered.compile()
    t2 = time.perf_counter()
    info = common.cache_info()
    kernels = {"conv2d": alexnet.resolve_conv_backend(pcfg),
               "lrn": alexnet.resolve_lrn_backend(pcfg)}
    return System(step, state, n_rep, lay, put_replicated, kernels,
                  {"trace_autotune_s": t1 - t0, "compile_s": t2 - t1,
                   "autotune_measured": info["measured"],
                   "autotune_hits": info["hits"]}, make_state)
