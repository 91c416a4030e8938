#!/usr/bin/env python3
"""Proof that the paper's main path runs on a TPU, end to end.

    python chip_smoke.py             # one chip: kernel parity, then training
    python chip_smoke.py --chips 4   # four chips: the mesh engine against
                                     # the one-device reference, nothing else

One process, everything on the chip, no fallback: it exits non-zero when
JAX finds no TPU (``JAX_PLATFORMS=cpu`` included), when the Pallas
kernels would run in interpret mode, or when any phase fails.  Data and
weights are made from ``--seed`` (``repro.data.synthetic``, random init).

One chip:
  1. Kernel parity.  The faithful AlexNet's reference forward pass (XLA,
     ``default_matmul_precision("highest")``) on a batch of 8 synthetic
     images; at each of the five conv layers and two LRN layers the
     compiled Pallas kernel runs on the same input and its max abs / rel
     error against the reference is printed beside the tolerance.
  2. Training.  ``repro.launch.train`` in-process with ``--arch alexnet
     --faithful --batch 256 --steps 8 --log-every 1``: the run header must
     show conv2d and lrn resolved to Pallas, and every logged loss must be
     finite.

Four chips: ``repro.launch.train --replicas 4`` (shard_map over
('data',), all_reduce exchange, 64 images per replica) for a few steps,
against the same 4 replicas run by ``make_param_avg_step`` (vmap over
axis 0) on ``jax.devices()[0]`` alone, from the same seed and batches,
both at "highest" matmul precision.  Losses and parameter updates must
agree; the compiled mesh step must hold an all-reduce, and each
replica's params must sit on its own device.

The last line of standard output is the JSON verdict, and only on
success: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
import argparse
import contextlib
import io
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

# Tolerances on max|got - ref| / max|ref| over a layer's output.  A
# structural fault (a wrong tap, row, group or channel) is an O(1) error.
# The reference runs at full f32 ("highest"); a TPU MXU product of f32
# operands at lower precision rounds them to bf16 first, which costs
# about 3e-3 of the output's scale.
CONV_TOL = 1e-2
# LRN is elementwise + a 5-wide window sum: no MXU, f32 throughout.
LRN_TOL = 1e-4
# Mesh engine vs one-device reference, both at "highest" precision: the
# same f32 arithmetic in a different reduction order.  (At the default
# precision each MXU product rounds its operands to bf16, and a 1e-7
# difference in a weight can flip that rounding, so after a few steps the
# two runs' conv1 updates, a sum of ~2e5 mostly cancelling terms, differ
# by about 1e-1 of their size with no fault in either.)  Losses are compared
# relative to the reference loss; parameters by their updates
# (params - init), max-normalised per leaf, so a missing or wrong
# exchange (per-replica updates differ at O(1)) cannot hide under the
# size of the weights.
MESH_LOSS_TOL = 1e-3
MESH_UPDATE_TOL = 1e-2

PARITY_BATCH = 8
TRAIN_ARGV = ["--arch", "alexnet", "--faithful", "--batch", "256",
              "--steps", "8", "--log-every", "1"]
MESH_STEPS = 4


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


class Tee(io.TextIOBase):
    """stdout that is also kept, so printed lines can be checked."""

    def __init__(self, out):
        self.out, self.kept = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.kept.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def run_trainer(argv):
    """``repro.launch.train.main(argv)`` in this process -> (Run, header,
    done line)."""
    from repro.launch import train
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        run = train.main(argv)
    lines = tee.kept.getvalue().splitlines()
    header = next(ln for ln in lines if ln.startswith("arch="))
    done = next(ln for ln in lines if ln.startswith("done:"))
    return run, header, done


def device_check(chips: int):
    import jax
    from repro.kernels.common import resolve_interpret
    devs = jax.devices()
    print(f"jax {jax.__version__}; {len(devs)} device(s): {devs}")
    print(f"platform {devs[0].platform}; device_kind {devs[0].device_kind}")
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX sees "
                         f"{devs[0].platform} devices); this script runs "
                         "only on a chip")
    if resolve_interpret():
        raise SystemExit("chip_smoke: the Pallas kernels would run in "
                         "interpret mode (REPRO_PALLAS_INTERPRET is set); "
                         "unset it")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX sees {len(devs)}")
    return devs


def rel_err(got, ref):
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(bool(np.isfinite(got).all()), "non-finite kernel output")
    err = float(np.abs(got - ref).max())
    return err, err / max(float(np.abs(ref).max()), 1e-30)


def kernel_parity(seed: int, interpret: bool = False) -> None:
    """Each faithful conv / LRN layer: compiled Pallas vs the XLA
    reference at "highest" precision, on the reference's own activations
    of synthetic images.  (``interpret=True`` rehearses it on a CPU.)"""
    import jax
    import jax.numpy as jnp
    from repro.configs import ALEXNET_FAITHFUL as cfg
    from repro.data import synthetic
    from repro.kernels.lrn import ops as lrn_ops
    from repro.models import alexnet

    params = alexnet.init(jax.random.PRNGKey(seed), cfg)
    images = next(synthetic.blob_images(cfg.n_classes, PARITY_BATCH,
                                        cfg.image_size, seed=seed))
    h = jnp.asarray(images["images"])
    lrn_kw = dict(n=cfg.lrn_n, alpha=cfg.lrn_alpha, beta=cfg.lrn_beta,
                  k=cfg.lrn_k)

    def conv(backend, cs):
        return jax.jit(lambda x, w, b: alexnet.conv2d(
            x, w, b, cs.stride, cs.padding, backend, relu=True,
            groups=cs.groups, interpret=interpret))

    def lrn(backend):
        return jax.jit(lambda x: lrn_ops.lrn(x, backend=backend,
                                             interpret=interpret, **lrn_kw))

    def compare(name, fn_pallas, fn_xla, x, *args, tol):
        with jax.default_matmul_precision("highest"):
            ref = fn_xla(x, *args)
        got = fn_pallas(x, *args)
        err, rel = rel_err(got, ref)
        ok = rel <= tol
        print(f"parity {name:5s} in {tuple(x.shape)} out "
              f"{tuple(ref.shape)}: max abs err {err:.3e}, max rel err "
              f"{rel:.3e} (tol {tol:.0e}) {'ok' if ok else 'FAIL'}",
              flush=True)
        check(ok, f"{name} parity {rel:.3e} > {tol:.0e}")
        return ref

    for i, (cp, cs) in enumerate(zip(params["convs"], cfg.convs)):
        h = compare(f"conv{i + 1}", conv("pallas", cs), conv("xla", cs), h,
                    cp["w"], cp["b"], tol=CONV_TOL)
        if cs.pool:
            h = alexnet.maxpool(h)
        if cs.lrn:
            h = compare(f"lrn{i + 1}", lrn("pallas"), lrn("xla"), h,
                        tol=LRN_TOL)


def train_one_chip(seed: int) -> None:
    run, header, done = run_trainer(TRAIN_ARGV + ["--seed", str(seed)])
    check(run.kernels == {"conv2d": "pallas", "lrn": "pallas"},
          f"kernels resolved to {run.kernels}, not Pallas")
    check(f"kernels={run.kernels}" in header, f"header: {header}")
    losses = [loss for _, loss in run.result.losses]
    check(len(losses) == 8, f"{len(losses)} logged losses, expected 8")
    check(all(math.isfinite(x) for x in losses), f"losses {losses}")
    check("replica spread" in done, f"done line: {done}")
    print(f"train: {len(losses)} finite losses {losses}; replica spread "
          f"{run.spread:.2e}", flush=True)


def mesh_vs_reference(seed: int, steps: int = MESH_STEPS,
                      train_args=("--faithful",)) -> None:
    """The four-chip phase (see the module docstring)."""
    import jax
    with jax.default_matmul_precision("highest"):
        _mesh_vs_reference(seed, steps, train_args)


def _mesh_vs_reference(seed, steps, train_args) -> None:
    import jax
    import numpy as np
    from repro.core import (ExchangeConfig, init_param_avg_state,
                            make_param_avg_step)
    from repro.launch import train
    from repro.numerics import get_policy
    from repro.optim.optimizers import for_numerics, get_optimizer
    from repro.sharding.specs import replica_sharding

    n_rep = 4
    argv = (["--arch", "alexnet", *train_args, "--batch", "256",
             "--replicas", str(n_rep), "--steps", str(steps),
             "--log-every", "1", "--seed", str(seed)])

    # the mesh engine, through the trainer
    run, header, _ = run_trainer(argv)
    check(" engine=mesh " in header, f"not the mesh engine: {header}")
    mesh_losses = [loss for _, loss in run.result.losses]
    mesh_params = run.result.state.params
    devs = jax.devices()[:n_rep]
    for leaf in jax.tree.leaves(mesh_params):
        on = [s.device for s in leaf.addressable_shards]
        check(sorted(d.id for d in on) == sorted(d.id for d in devs)
              and all(s.data.shape[0] == 1 for s in leaf.addressable_shards),
              f"replica params not one per device: {on}")
    print(f"mesh: each replica's params on its own device "
          f"({[d.id for d in devs]})", flush=True)

    # the same four replicas, vmapped on one device (reference engine)
    ap = train.build_parser()
    args = ap.parse_args(argv)
    npol = get_policy(args.numerics)
    build = train.build_alexnet(args, ap.error, npol)
    opt = for_numerics(get_optimizer(args.optimizer), npol)
    exch = ExchangeConfig(strategy=args.strategy)
    dev0 = jax.devices()[0]
    state = jax.device_put(init_param_avg_state(
        jax.random.PRNGKey(args.seed), build.init, opt, n_rep,
        exchange=exch, numerics=npol), dev0)
    init = jax.tree.map(np.asarray, state.params)
    step = jax.jit(make_param_avg_step(
        build.loss, opt, train.make_controller(args).schedule(),
        strategy=exch, numerics=npol), donate_argnums=0)
    stream = build.make_stream(n_rep)
    ref_losses = []
    for _ in range(steps):
        state, loss = step(state, jax.device_put(next(stream), dev0))
        ref_losses.append(float(loss))
    print(f"reference (vmap on {dev0}): losses {ref_losses}", flush=True)

    loss_err = max(abs(m - r) / abs(r) for m, r in zip(mesh_losses,
                                                       ref_losses))
    print(f"mesh losses {mesh_losses}; max rel loss diff {loss_err:.3e} "
          f"(tol {MESH_LOSS_TOL:.0e})", flush=True)
    check(len(mesh_losses) == steps and loss_err <= MESH_LOSS_TOL,
          f"losses disagree: {loss_err:.3e}")

    worst, worst_at = 0.0, None
    flat_ref = jax.tree_util.tree_flatten_with_path(state.params)[0]
    for (path, r), m, p0 in zip(flat_ref, jax.tree.leaves(mesh_params),
                                jax.tree.leaves(init)):
        d_ref = np.asarray(r, np.float64) - p0
        d_mesh = np.asarray(m, np.float64) - p0
        err = float(np.abs(d_mesh - d_ref).max()) / max(
            float(np.abs(d_ref).max()), 1e-30)
        if err >= worst:
            worst, worst_at = err, jax.tree_util.keystr(path)
    print(f"params: max rel update diff {worst:.3e} at {worst_at} "
          f"(tol {MESH_UPDATE_TOL:.0e})", flush=True)
    check(worst <= MESH_UPDATE_TOL, f"params disagree at {worst_at}")

    # the compiled mesh step holds the exchange's all-reduce
    mesh = jax.tree.leaves(mesh_params)[0].sharding.mesh
    batch_spec = jax.tree.map(
        lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        batch, replica_sharding(batch, mesh, replica_axes=("data",)))
    t0 = time.perf_counter()
    hlo = run.session.step_fn.lower(run.result.state,
                                    batch_spec).compile().as_text()
    n_ar = hlo.count("all-reduce(") + hlo.count("all-reduce-start(")
    print(f"compiled mesh step: {n_ar} all-reduce op(s) "
          f"(lowered in {time.perf_counter() - t0:.1f}s)", flush=True)
    check(n_ar > 0, "no all-reduce in the compiled mesh step")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernel parity + training on one chip; 4: the "
                    "mesh-vs-reference phase alone, on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(REPO, "src", "repro")):
        raise SystemExit("chip_smoke: src/repro is not next to this "
                         "script; run it from a checkout of the repo")
    import jax
    from repro.launch.host import enable_compile_cache

    cache = enable_compile_cache()
    devs = device_check(args.chips)
    print(f"compile cache: {cache}", flush=True)
    t0 = time.perf_counter()
    if args.chips == 1:
        kernel_parity(args.seed)
        print(f"kernel parity done at {time.perf_counter() - t0:.1f}s",
              flush=True)
        train_one_chip(args.seed)
    else:
        mesh_vs_reference(args.seed)
    print(f"all phases passed in {time.perf_counter() - t0:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
