"""Training driver: resumable sessions on this host's devices.

Builds the model / loss / data streams for an arch, picks the exchange
engine, and hands the loop to ``repro.train_loop.TrainSession`` — which
owns checkpoint/resume, the validation + plateau-LR loop, and Table-1
throughput metrics (docs/training.md).

Runs the paper's parameter-averaging data parallelism end-to-end on this
host's devices (set REPRO_DEVICES=N to fan out over N host devices — this
driver appends the device count to XLA_FLAGS when the variable is present,
BEFORE importing jax, so it must stay the first import in the process).

NOTE: without --smoke, --arch alexnet now means the FULL 227px net (the
seed silently swapped in the smoke config whenever --image-size < 128);
pass --smoke for CPU-sized runs, --image-size to override either config.

Examples:
    REPRO_DEVICES=8 PYTHONPATH=src python -m repro.launch.train \
        --arch olmo-1b --smoke --steps 50 --replicas 4
    PYTHONPATH=src python -m repro.launch.train --arch alexnet --smoke \
        --steps 100
    # checkpoint every 10 steps, then pick up where a killed run stopped:
    PYTHONPATH=src python -m repro.launch.train --arch alexnet --smoke \
        --steps 100 --ckpt-dir /tmp/ck --ckpt-every 10 --resume
    # the paper's LR rule: eval every 20 steps, /10 when error plateaus:
    PYTHONPATH=src python -m repro.launch.train --arch alexnet --smoke \
        --steps 200 --schedule plateau --eval-every 20
"""
import os

if os.environ.get("REPRO_DEVICES"):
    os.environ["XLA_FLAGS"] = " ".join(filter(None, (
        os.environ.get("XLA_FLAGS"),
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DEVICES"])))

# ruff: noqa: E402
import argparse
import dataclasses

import jax
import numpy as np

from repro import models
from repro.configs import (ALEXNET, ALEXNET_FAITHFUL, ALEXNET_FAITHFUL_SMOKE,
                           ALEXNET_SMOKE, get_config, reduced)
from repro.core import (ExchangeConfig, init_param_avg_state, make_eval_step,
                        make_mesh_param_avg_step, make_param_avg_step,
                        replica_spread, reshape_for_replicas)
from repro.kernels.common import KernelPolicy
from repro.launch.host import enable_compile_cache
from repro.launch.mesh import make_replica_mesh
from repro.numerics import get_policy
from repro.sharding.specs import replica_sharding, state_sharding
from repro.data import synthetic
from repro.models import alexnet as alexnet_mod
from repro.optim import schedules
from repro.optim.optimizers import for_numerics, get_optimizer
from repro.train_loop import (EVAL_SEED_OFFSET, TrainSession, alexnet_metrics,
                              lm_metrics)


@dataclasses.dataclass
class Build:
    """Everything arch-specific the session needs."""
    cfg: object
    init: callable
    loss: callable                    # loss(params, batch) -> scalar
    make_stream: callable             # (replicas) -> fresh (R, B/R, ...)
                                      # batch iterator
    make_eval_batches: callable       # () -> fresh held-out iterator
    eval_metric_fn: callable          # (params, batch) -> {name: scalar}
    plateau_metric: str               # the metric the LR controller tracks
    kernels: dict                     # op -> the backend it resolved to


def make_policy(args) -> KernelPolicy:
    """One KernelPolicy from the CLI: ``--kernel-backend`` is the global
    default, ``--attn-impl`` / ``--conv-backend`` stay as per-op
    overrides.  The policy rides on the config — nothing downstream
    takes kernel kwargs anymore."""
    return KernelPolicy(backend=args.kernel_backend,
                        attention=args.attn_impl,
                        conv2d=args.conv_backend)


def build_lm(args, numerics) -> Build:
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg, n_layers=args.layers or 2,
                      d_model=args.d_model or 256)
    # numerics must land on cfg HERE, before the init/loss closures below
    # capture it — a later dataclasses.replace on build.cfg rebinds the
    # field but leaves the closures training fp32 params
    cfg = dataclasses.replace(cfg, kernels=make_policy(args),
                              numerics=numerics)

    def add_extras(b):
        out = {"tokens": b["tokens"], "labels": b["labels"]}
        bsz, s = b["tokens"].shape
        if cfg.family == "encdec":
            out["frames"] = np.random.default_rng(0).normal(
                size=(bsz, max(s // 4, 8), cfg.d_model)).astype(np.float32)
        if cfg.family == "vlm":
            n = cfg.n_image_tokens
            out["image_embeds"] = np.zeros((bsz, n, cfg.d_model), np.float32)
            mask = np.zeros((bsz, s), bool)
            mask[:, :n] = True
            out["image_mask"] = mask
        return out

    def make_stream(replicas):
        return map(lambda b: reshape_for_replicas(add_extras(b), replicas),
                   synthetic.markov_lm(cfg.vocab_size, args.batch,
                                       args.seq_len, seed=args.seed))

    def make_eval_batches():
        # same Markov chain (table from args.seed), held-out sample path
        return map(add_extras, synthetic.markov_lm(
            cfg.vocab_size, args.batch, args.seq_len, seed=args.seed,
            sample_seed=args.seed + EVAL_SEED_OFFSET))

    def loss(params, batch):
        return models.loss_fn(params, cfg, batch)

    from repro.models import attention, rglru, rwkv
    kernels = {} if cfg.attention_free else {
        "attention": attention.resolve_impl(cfg, sq=args.seq_len,
                                            sk=args.seq_len)}
    if cfg.family == "ssm":
        kernels["rwkv6"] = rwkv.resolve_wkv_impl(cfg)
    if cfg.family == "hybrid":
        kernels["rglru"] = rglru.resolve_rglru_impl(cfg)
    return Build(cfg, lambda r: models.init(r, cfg), loss, make_stream,
                 make_eval_batches, lm_metrics(cfg),
                 plateau_metric="loss", kernels=kernels)


def build_alexnet(args, error, numerics) -> Build:
    if args.faithful:
        cfg = ALEXNET_FAITHFUL_SMOKE if args.smoke else ALEXNET_FAITHFUL
    else:
        cfg = ALEXNET_SMOKE if args.smoke else ALEXNET
    # see build_lm: the closures capture cfg now, so numerics goes on now
    cfg = dataclasses.replace(cfg, kernels=make_policy(args),
                              numerics=numerics)
    if args.image_size is not None:
        try:
            cfg.feature_hw(args.image_size)   # conv/pool windows must fit
        except ValueError as e:
            error(str(e))
        cfg = dataclasses.replace(cfg, image_size=args.image_size)
    from repro.data.preprocess import make_image_preprocess
    mean = synthetic.mean_image(
        synthetic.blob_images(cfg.n_classes, args.batch, cfg.image_size + 8,
                              seed=args.seed + 1), 2)

    def make_stream(replicas):
        # fresh preprocess per stream: its RNG advances once per batch, so
        # resume's fast-forward replays crops/flips exactly.  The replica
        # split is a free view of the host batch, and the device program
        # writes the split layout the step reads (no device reshape).
        prep = make_image_preprocess(mean, cfg.image_size, seed=args.seed)
        return map(prep, map(
            lambda b: reshape_for_replicas(b, replicas), synthetic.blob_images(
                cfg.n_classes, args.batch, cfg.image_size + 8,
                seed=args.seed)))

    def make_eval_batches():
        # unsplit (B, ...) batches; the session caches them, and the cached
        # images stay on the default device
        es = args.seed + EVAL_SEED_OFFSET
        prep = make_image_preprocess(mean, cfg.image_size, seed=es)
        return map(prep, synthetic.blob_images(
            cfg.n_classes, args.batch, cfg.image_size + 8, seed=es))

    def loss(params, batch):
        return alexnet_mod.loss_fn(params, cfg, batch["images"],
                                   batch["labels"])

    kernels = {"conv2d": alexnet_mod.resolve_conv_backend(cfg),
               "lrn": alexnet_mod.resolve_lrn_backend(cfg)}
    return Build(cfg, lambda r: alexnet_mod.init(r, cfg), loss, make_stream,
                 make_eval_batches, alexnet_metrics(cfg),
                 plateau_metric="top1_err", kernels=kernels)


def make_controller(args):
    if args.schedule == "constant":
        return schedules.StaticController(schedules.constant(args.lr))
    if args.schedule == "wsd":
        return schedules.StaticController(
            schedules.wsd(args.lr, args.steps // 10,
                          int(args.steps * 0.7), args.steps // 5))
    if args.schedule == "cosine":
        return schedules.StaticController(
            schedules.cosine(args.lr, args.steps // 10, args.steps))
    return schedules.plateau_decay(
        args.lr, factor=args.plateau_factor, patience=args.plateau_patience,
        threshold=args.plateau_threshold)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="alexnet")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--image-size", type=int, default=None,
                    help="override the AlexNet config's image size (errors "
                    "if the conv stack cannot consume it; default: the "
                    "config's own size — 227 full, 64 smoke)")
    ap.add_argument("--replicas", type=int, default=None)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="size of the mesh's 'model' axis: shards grouped "
                    "conv / FC output channels (and the LM zoo's tensor-"
                    "parallel dims) across devices — the paper's intra-"
                    "layer 2-GPU split is --faithful --model-parallel 2. "
                    "Uses the reference engine (replicas x model mesh); "
                    "requires replicas * model-parallel <= devices")
    ap.add_argument("--faithful", action="store_true",
                    help="paper-faithful AlexNet: 2-group conv2/4/5 + LRN "
                    "after pool1/pool2 (the Caffe reference topology); "
                    "without it the legacy PR-2 net is trained")
    ap.add_argument("--strategy", default="all_reduce")
    ap.add_argument("--engine", default="auto",
                    choices=["auto", "mesh", "reference"],
                    help="mesh: shard_map + real collectives (one replica "
                    "per device); reference: leading-axis-R vmap + GSPMD "
                    "(supports replicas < devices via tensor parallelism); "
                    "auto: mesh when replicas == devices > 1")
    ap.add_argument("--sync-every", type=int, default=1)
    ap.add_argument("--exchange-delay", type=int, default=0, choices=[0, 1],
                    help="0: synchronous exchange after the update (the "
                    "paper's path); 1: one-step-stale overlapped exchange "
                    "— the collective for step t's params runs inside step "
                    "t+1's program, concurrent with its forward/backward")
    ap.add_argument("--exchange-compression", default="none",
                    choices=["none", "bf16", "topk"],
                    help="wire compression for the exchange: bf16 halves "
                    "the moved bytes; topk sends the top-k-magnitude "
                    "entries of the delta-from-consensus with error-"
                    "feedback residuals (needs --exchange-delay 1)")
    ap.add_argument("--topk-frac", type=float, default=0.01,
                    help="kept fraction per leaf for --exchange-"
                    "compression topk (1.0 = identity, bit-equal to none)")
    ap.add_argument("--replica-exec", default="vmap",
                    choices=["vmap", "scan"],
                    help="reference engine only: how the R independent "
                    "replicas execute — vmap (batched) or scan "
                    "(sequential lax.map; each replica's smaller batch is "
                    "more cache-resident on CPU hosts)")
    ap.add_argument("--staging", default="queue",
                    choices=["queue", "pinned"],
                    help="batch staging: queue = prefetch handoff queue; "
                    "pinned = double-buffered preallocated host buffers "
                    "with fence-gated reuse (data/pipeline.py)")
    ap.add_argument("--optimizer", default="sgd_momentum")
    ap.add_argument("--schedule", default="constant",
                    choices=["constant", "wsd", "cosine", "plateau"],
                    help="plateau = the paper's rule: divide LR by 10 when "
                    "the validation metric plateaus (needs --eval-every)")
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--plateau-factor", type=float, default=0.1)
    ap.add_argument("--plateau-patience", type=int, default=2)
    ap.add_argument("--plateau-threshold", type=float, default=1e-3)
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="global kernel policy for EVERY op family "
                    "(attention/rglru/rwkv6/conv2d): pallas = the "
                    "compiled kernels everywhere (interpreter on CPU "
                    "hosts — correctness-equivalent), xla = library "
                    "paths, auto = pallas exactly where it compiles")
    ap.add_argument("--attn-impl", default=None,
                    choices=["auto", "xla", "chunked", "qloop", "flash"],
                    help="per-op override of --kernel-backend for "
                    "attention")
    ap.add_argument("--conv-backend", default=None,
                    choices=["xla", "pallas", "pallas_im2col_ref"],
                    help="per-op override of --kernel-backend for conv: "
                    "pallas = fused implicit-GEMM kernel; "
                    "pallas_im2col_ref = two-stage XLA-im2col + Pallas "
                    "GEMM parity path")
    ap.add_argument("--numerics", default="fp32",
                    choices=["fp32", "bf16"],
                    help="NumericsPolicy preset: fp32 = the pre-policy "
                    "default (bit-equal); bf16 = bf16 params/compute with "
                    "fp32 master weights in the optimizer state and "
                    "dynamic loss scaling (docs/numerics.md)")
    ap.add_argument("--kv-cache-dtype", default="auto",
                    choices=["auto", "fp32", "bf16", "int8"],
                    help="decode KV-cache storage dtype (serving only "
                    "matters for --arch LMs; auto follows the model "
                    "dtype, int8 quantizes per head/slot — 2x slots per "
                    "byte)")
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest complete checkpoint in "
                    "--ckpt-dir and continue bit-exactly (fresh start if "
                    "the directory has none)")
    ap.add_argument("--eval-every", type=int, default=0,
                    help="validation pass every N steps (0 = off)")
    ap.add_argument("--eval-batches", type=int, default=2)
    ap.add_argument("--metrics-out", default=None,
                    help="JSONL trace path (train/eval/summary records, "
                    "docs/training.md); implies per-step host sync")
    ap.add_argument("--log-every", type=int, default=10)
    return ap


@dataclasses.dataclass
class Run:
    """What ``main`` trained: the session (``session.step_fn`` is the
    jitted step it ran), its result, the backend each kernel op resolved
    to, and the final replica spread."""
    session: TrainSession
    result: object
    kernels: dict
    spread: float


def main(argv=None) -> Run:
    """Parse ``argv`` (default: the command line) and train."""
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.schedule == "plateau" and args.eval_every <= 0:
        ap.error("--schedule plateau needs --eval-every > 0 (the plateau "
                 "rule is driven by validation metrics)")
    if args.resume and not args.ckpt_dir:
        ap.error("--resume needs --ckpt-dir")

    enable_compile_cache()
    n_dev = jax.device_count()
    mp = args.model_parallel
    n_rep = args.replicas or (n_dev // mp if mp > 1 else n_dev)
    assert args.batch % n_rep == 0, (args.batch, n_rep)
    if mp > 1:
        if args.engine == "mesh":
            ap.error("--model-parallel needs the reference engine (the "
                     "mesh engine's shard_map owns every non-replica axis)")
        if n_rep * mp > n_dev:
            ap.error(f"--replicas {n_rep} x --model-parallel {mp} needs "
                     f"{n_rep * mp} devices, have {n_dev} "
                     "(set REPRO_DEVICES)")

    try:
        exch = ExchangeConfig(strategy=args.strategy,
                              compression=args.exchange_compression,
                              topk_frac=args.topk_frac,
                              delay=args.exchange_delay,
                              sync_every=args.sync_every)
    except ValueError as e:
        ap.error(str(e))

    npol = get_policy(args.numerics)
    if args.kv_cache_dtype != "auto":
        npol = dataclasses.replace(npol, kv_cache_dtype=args.kv_cache_dtype)

    if args.arch == "alexnet":
        build = build_alexnet(args, ap.error, npol)
    else:
        build = build_lm(args, npol)
    build.cfg = dataclasses.replace(build.cfg, exchange=exch)

    opt = for_numerics(get_optimizer(args.optimizer), npol)
    controller = make_controller(args)

    engine = args.engine
    if engine == "auto":
        engine = "mesh" if (n_dev > 1 and n_rep == n_dev and mp == 1
                            and args.replica_exec == "vmap") \
            else "reference"
    if engine == "mesh" and args.replica_exec == "scan":
        ap.error("--replica-exec scan is a reference-engine execution mode "
                 "(the mesh engine runs one replica per device); use "
                 "--engine reference")

    rng = jax.random.PRNGKey(args.seed)
    state = init_param_avg_state(rng, build.init, opt, n_rep, exchange=exch,
                                 numerics=npol)

    sharding = None
    if engine == "mesh":
        # mesh-native engine: shard_map over ('data',), one replica per
        # device, exchange lowers to real collectives (docs/architecture.md)
        mesh = make_replica_mesh(n_rep)
        sharding = replica_sharding(state, mesh, replica_axes=("data",))
        state = jax.device_put(state, sharding)
        put = lambda b: jax.device_put(  # noqa: E731
            b, replica_sharding(b, mesh, replica_axes=("data",)))

        def build_step(sched):
            # donate the TrainState: params/opt-state update in place
            # instead of allocating a fresh copy of the state every step
            return jax.jit(make_mesh_param_avg_step(
                build.loss, opt, sched, mesh=mesh, strategy=exch,
                replica_axes=("data",), numerics=npol),
                donate_argnums=0)
    else:
        out_shardings = None
        if n_dev > 1:
            from jax.sharding import NamedSharding, PartitionSpec as P
            mesh = jax.make_mesh((n_rep, mp if mp > 1 else n_dev // n_rep),
                                 ("data", "model"))
            if mp > 1:
                # replica x model layout: replicas over 'data', grouped
                # conv / FC output channels (and the LM zoo's tensor-
                # parallel dims) over 'model' via sharding/specs.py — the
                # paper's intra-layer split, run by GSPMD
                sharding = state_sharding(state, build.cfg, mesh,
                                          replica_axes=("data",))
            else:
                sharding = replica_sharding(state, mesh,
                                            replica_axes=("data",))
            state = jax.device_put(state, sharding)
            put = lambda b: jax.device_put(  # noqa: E731
                b, replica_sharding(b, mesh, replica_axes=("data",)))
            # pin the state's layout as a loop invariant: left to GSPMD,
            # the output sharding of a step can drift from the layout a
            # fresh device_put (or a sharding-aware restore) produces,
            # compiling a second executable whose reduction order differs
            # in the last float bits — which would break bit-exact resume
            out_shardings = (sharding, NamedSharding(mesh, P()))
        else:
            put = jax.device_put

        def build_step(sched):
            kw = {} if out_shardings is None else \
                {"out_shardings": out_shardings}
            return jax.jit(make_param_avg_step(
                build.loss, opt, sched, strategy=exch,
                replica_exec=args.replica_exec, numerics=npol),
                donate_argnums=0, **kw)

    session = TrainSession(
        state=state, build_step=build_step,
        make_stream=lambda: build.make_stream(n_rep),
        controller=controller, steps=args.steps, device_put=put,
        sharding=sharding,
        eval_step=make_eval_step(build.eval_metric_fn)
        if args.eval_every else None,
        make_eval_batches=build.make_eval_batches,
        eval_every=args.eval_every, eval_batches=args.eval_batches,
        plateau_metric=build.plateau_metric,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume, prefetch=args.prefetch, staging=args.staging,
        log_every=args.log_every, images_per_step=args.batch,
        metrics_path=args.metrics_out,
        run_meta={"kernels": make_policy(args).describe(),
                  "numerics": npol.describe(),
                  "engine": engine, "strategy": args.strategy,
                  "exchange": exch.describe(),
                  "replica_exec": args.replica_exec,
                  "staging": args.staging,
                  "model_parallel": mp})

    print(f"arch={getattr(build.cfg, 'name', args.arch)} replicas={n_rep} "
          f"devices={n_dev} model_parallel={mp} "
          f"engine={engine} exchange={exch.describe()} "
          f"replica_exec={args.replica_exec} staging={args.staging} "
          f"kernels={build.kernels} "
          f"numerics={npol.describe()}"
          + (f" resume_from={args.ckpt_dir}" if args.resume else ""))
    result = session.run()
    spread = float(replica_spread(result.state.params))
    summ = result.summary
    through = (f"; images/sec {summ['images_per_sec']} "
               f"p50 {summ.get('step_ms_p50')}ms "
               f"p99 {summ.get('step_ms_p99')}ms"
               if "images_per_sec" in summ else "")
    print(f"done: steps {result.start_step} -> {result.final_step}; "
          f"final loss "
          f"{result.losses[-1][1] if result.losses else float('nan'):.4f}; "
          f"replica spread {spread:.2e}" + through
          + (f"; lr drops at {result.lr_drops}" if result.lr_drops else ""))
    return Run(session, result, build.kernels, spread)


if __name__ == "__main__":
    main()
