"""Serving driver: the continuous-batching engine on this host's devices,
single-process or as a multi-process tier.

Builds a (reduced, randomly-initialized — or checkpoint-restored) model,
spins up ``repro.serving.ServingEngine`` with ``--slots`` fixed decode
slots on a replica mesh over the host devices, feeds it a synthetic
request stream, and reports tok/s + per-request p50/p99 latency.

    REPRO_DEVICES=2 PYTHONPATH=src python -m repro.launch.serve \
        --arch olmo-1b --smoke --slots 4 --requests 16 \
        --kernel-backend pallas

The engine serves every family in the zoo through the DecodeState
contract (docs/serving.md); ``--arch seamless-m4t-medium`` exercises the
encdec path with stub frames, ``--arch rwkv6-7b`` the constant-state
recurrent path.  Vision runs through the SAME admission loop:
``--arch alexnet`` serves image-classification requests (one class id
per image, batched through ``_admit_images`` — no decode ticks), and
``--images`` attaches raw pixels to every vlm request so
``phi-3-vision-4.2b`` prefills real (stub-encoded) patch embeddings.

Tier mode (docs/serving.md):

    python -m repro.launch.serve --arch olmo-1b --smoke --tier 2

spawns 2 engine worker processes (same model flags) behind a
``serving.Router`` and routes the stream through them; ``--disagg`` adds
a dedicated prefill worker and decode instances admit only pre-filled
snapshots.  The worker side of the same flags is ``--role
{engine,decode,prefill} --port P`` — spawned automatically by ``--tier``
or launched by hand for a multi-host layout.
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
import time

import jax
import numpy as np

from repro import models
from repro.configs import ALEXNET, ALEXNET_SMOKE, get_config, reduced
from repro.kernels.common import KernelPolicy
from repro.launch.host import enable_compile_cache
from repro.launch.mesh import make_replica_mesh
from repro.numerics import get_policy
from repro.serving import Request, Router, ServingEngine


def build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--images", action="store_true",
                    help="vlm: attach random raw pixels to every request "
                    "(encoded to patch embeddings at submit)")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--slots", type=int, default=4,
                    help="fixed decode slots (the continuous batch)")
    ap.add_argument("--capacity", type=int, default=128,
                    help="per-request position budget (ring capacity for "
                    "full-attention archs; SWA/recurrent state is smaller)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16,
                    help="mean synthetic prompt length (lengths vary "
                    "around it to exercise the buckets)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--ticks-per-dispatch", type=int, default=1,
                    help="device-resident decode ticks per host sync: the "
                    "drain sees a (slots, K) token block per dispatch")
    ap.add_argument("--draft-arch", default=None,
                    help="enable speculative decoding with this arch as "
                    "the draft model (greedy only; reduced under --smoke)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="> 0: make the draft a TRUNCATED member of the "
                    "target arch — the target's own first k layers + its "
                    "embed/unembed (use with --draft-arch self)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="draft tokens proposed per verify round (gamma)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="> 0: shared-prefix block-pool KV cache with this "
                    "many ring positions per block (single device, "
                    "full-attention archs)")
    ap.add_argument("--num-blocks", type=int, default=0,
                    help="pool size for --block-size (default: full "
                    "private provisioning, slots*capacity/bs + trash)")
    ap.add_argument("--kernel-backend", default="auto",
                    choices=["auto", "xla", "pallas"],
                    help="KernelPolicy backend — pallas engages the "
                    "flash-decode kernel (interpret mode on CPU hosts)")
    ap.add_argument("--numerics", default="fp32",
                    choices=["fp32", "bf16"],
                    help="NumericsPolicy preset for the served model "
                    "(docs/numerics.md)")
    ap.add_argument("--kv-cache-dtype", default="auto",
                    choices=["auto", "fp32", "bf16", "int8"],
                    help="KV-cache storage dtype: auto follows the model "
                    "dtype; int8 quantizes per head/slot with fp32 scales "
                    "— 2x decode slots at equal ring bytes")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    # ------------------------------------------------------------- tier ----
    ap.add_argument("--tier", "--instances", type=int, default=0,
                    dest="tier",
                    help="> 0: spawn this many engine worker processes and "
                    "route the request stream through serving.Router")
    ap.add_argument("--disagg", action="store_true",
                    help="tier mode: add a dedicated prefill worker; "
                    "decode instances admit only pre-filled snapshots")
    ap.add_argument("--role", default="driver",
                    choices=["driver", "router", "engine", "decode",
                             "prefill"],
                    help="worker roles serve one router connection on "
                    "--port; router is an alias for the --tier driver")
    ap.add_argument("--port", type=int, default=0,
                    help="worker roles: localhost port to listen on")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="worker backpressure bound (default 2x slots): "
                    "beyond it submits answer 'defer'")
    return ap


def build_cfg(args):
    if args.arch == "alexnet":
        cfg = ALEXNET_SMOKE if args.smoke else ALEXNET
    else:
        cfg = get_config(args.arch)
        if args.smoke:
            cfg = reduced(cfg, n_layers=args.layers or 2,
                          d_model=args.d_model or 256)
    npol = get_policy(args.numerics)
    if args.kv_cache_dtype != "auto":
        npol = dataclasses.replace(npol, kv_cache_dtype=args.kv_cache_dtype)
    return dataclasses.replace(
        cfg, kernels=KernelPolicy(backend=args.kernel_backend), numerics=npol)


def build_spec(args, cfg, params):
    """The speculative-decoding kwargs: an independent draft arch, or —
    with --draft-layers k — a truncated-layer member of the TARGET arch
    sharing its embed/unembed and first k blocks (serving/spec_decode.py
    truncated_draft), which is what makes acceptance high enough to pay."""
    if not args.draft_arch and not args.draft_layers:
        return {}
    if args.draft_layers:
        from repro.serving.spec_decode import truncated_draft
        dcfg, dparams = truncated_draft(cfg, params, args.draft_layers)
        return {"draft_params": dparams, "draft_cfg": dcfg,
                "spec_tokens": args.spec_tokens}
    dcfg = get_config(args.draft_arch)
    if args.smoke:
        dcfg = reduced(dcfg, n_layers=args.layers or 2,
                       d_model=args.d_model or 256)
    dcfg = dataclasses.replace(
        dcfg, kernels=KernelPolicy(backend=args.kernel_backend),
        numerics=cfg.numerics)
    if dcfg.vocab_size != cfg.vocab_size:
        dcfg = dataclasses.replace(dcfg, vocab_size=cfg.vocab_size)
    return {"draft_params": models.init(jax.random.PRNGKey(args.seed + 1),
                                        dcfg),
            "draft_cfg": dcfg, "spec_tokens": args.spec_tokens}


def build_engine(args, cfg, *, mesh=None):
    params = models.init(jax.random.PRNGKey(args.seed), cfg)
    spec = build_spec(args, cfg, params)
    return ServingEngine(params, cfg, slots=args.slots,
                         capacity=args.capacity,
                         temperature=args.temperature, top_k=args.top_k,
                         mesh=mesh, seed=args.seed,
                         ticks_per_dispatch=args.ticks_per_dispatch,
                         block_size=args.block_size,
                         num_blocks=args.num_blocks, **spec)


def make_requests(args, cfg):
    rs = np.random.default_rng(args.seed)
    reqs = []
    n_img = cfg.n_image_tokens if args.images else 0
    hi = max(args.capacity - args.max_new - n_img, 2)
    for i in range(args.requests):
        if cfg.family == "conv":
            reqs.append(Request(image=rs.standard_normal(
                (cfg.image_size, cfg.image_size, cfg.in_channels))))
            continue
        ln = int(np.clip(rs.integers(max(args.prompt_len // 2, 1),
                                     args.prompt_len * 2), 1, hi))
        reqs.append(Request(
            prompt=rs.integers(0, cfg.vocab_size, size=ln),
            max_new_tokens=args.max_new,
            image=rs.standard_normal((32, 32, 3)) if args.images else None))
    return reqs


def worker_argv(args):
    """The model/engine flags a spawned worker needs to build the SAME
    engine this driver would — tier instances must be homogeneous for
    drain/handoff to replay snapshots."""
    argv = ["--arch", args.arch, "--slots", str(args.slots),
            "--capacity", str(args.capacity),
            "--temperature", str(args.temperature),
            "--top-k", str(args.top_k),
            "--ticks-per-dispatch", str(args.ticks_per_dispatch),
            "--kernel-backend", args.kernel_backend,
            "--numerics", args.numerics,
            "--kv-cache-dtype", args.kv_cache_dtype,
            "--seed", str(args.seed)]
    if args.smoke:
        argv.append("--smoke")
    if args.layers is not None:
        argv += ["--layers", str(args.layers)]
    if args.d_model is not None:
        argv += ["--d-model", str(args.d_model)]
    if args.max_queue:
        argv += ["--max-queue", str(args.max_queue)]
    return argv


def run_worker(args):
    from repro.serving import tier
    if not args.port:
        raise SystemExit("worker roles need --port")
    cfg = build_cfg(args)
    if args.role == "prefill":
        params = models.init(jax.random.PRNGKey(args.seed), cfg)
        obj = tier.PrefillWorker(params, cfg, capacity=args.capacity,
                                 temperature=args.temperature,
                                 top_k=args.top_k, seed=args.seed)
    else:
        obj = build_engine(args, cfg)
    tier.worker_serve(obj, args.port,
                      max_queue=args.max_queue or None)


def run_tier(args):
    from repro.serving import tier
    cfg = build_cfg(args)
    if cfg.family == "conv" or args.images:
        raise SystemExit("the tier routes token requests; vision serves "
                         "single-process")
    argv = worker_argv(args)
    instances = [tier.spawn_worker("engine", argv, name=f"engine{i}")
                 for i in range(args.tier)]
    prefill = tier.spawn_worker("prefill", argv) if args.disagg else None
    for h in instances + ([prefill] if prefill else []):
        h.connect()
    router = Router(instances, prefill=prefill)
    reqs = make_requests(args, cfg)
    print(f"tier: {args.tier} instance(s)"
          + (" + prefill worker" if prefill else "")
          + f", arch={cfg.name} slots={args.slots}/instance")
    t0 = time.perf_counter()
    for r in reqs:
        router.submit(r)
    results = router.run_until_done()
    wall = time.perf_counter() - t0
    toks = sum(len(r["tokens"]) for r in results)
    lats = sorted(r["router_latency"] for r in results)
    p = lambda q: lats[min(int(q * len(lats)), len(lats) - 1)]  # noqa: E731
    st = router.stats()
    router.shutdown()
    print(f"served {len(results)} requests / {toks} tokens in {wall:.2f}s "
          f"({toks / wall:.1f} tok/s aggregate, "
          f"{router.deferred} deferred admissions, "
          f"dead={st['dead'] or 'none'})")
    print(f"router latency p50 {p(0.5) * 1e3:.0f}ms p99 {p(0.99) * 1e3:.0f}ms")
    print("serve OK")


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    if args.role in ("engine", "decode", "prefill"):
        run_worker(args)
        return
    if args.tier or args.role == "router":
        if not args.tier:
            raise SystemExit("--role router needs --tier N (instances to "
                             "spawn)")
        run_tier(args)
        return

    cfg = build_cfg(args)
    if args.images and cfg.family != "vlm":
        raise SystemExit(f"--images needs a vlm arch, {cfg.name} is "
                         f"{cfg.family}")

    n_dev = jax.device_count()
    mesh = make_replica_mesh(n_dev) if n_dev > 1 else None
    if mesh is not None and args.slots % n_dev:
        raise SystemExit(f"--slots {args.slots} must divide over "
                         f"{n_dev} devices")
    if args.max_new >= args.capacity:
        raise SystemExit(f"--max-new {args.max_new} must be < --capacity "
                         f"{args.capacity}: the ring holds capacity "
                         "positions, prompt included")

    engine = build_engine(args, cfg, mesh=mesh)
    reqs = make_requests(args, cfg)

    print(f"arch={cfg.name} family={cfg.family} devices={n_dev} "
          f"slots={args.slots} capacity={args.capacity} "
          f"kernels={cfg.kernels.describe()} "
          f"numerics={cfg.numerics.describe()}")
    t0 = time.perf_counter()
    results = engine.run(reqs)
    wall = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    lats = sorted(r.latency for r in results)
    p = lambda q: lats[min(int(q * len(lats)), len(lats) - 1)]  # noqa: E731
    print(f"served {len(results)} requests / {toks} tokens in {wall:.2f}s "
          f"({toks / wall:.1f} tok/s, {engine.decode_steps} decode ticks / "
          f"{engine.dispatches} dispatches, "
          f"{engine.prefill_compiles} prefill compiles)")
    if engine.spec_proposed:
        print(f"spec: {engine.spec_accepted}/{engine.spec_proposed} draft "
              f"tokens accepted "
              f"({engine.spec_accepted / engine.spec_proposed:.2f})")
    if engine.block_mgr is not None:
        print(f"blocks: peak {engine.block_mgr.peak}/{engine.block_mgr.nb} "
              f"in use, {engine.block_mgr.prefills_skipped} prefills "
              f"skipped")
    print(f"latency p50 {p(0.5) * 1e3:.0f}ms p99 {p(0.99) * 1e3:.0f}ms "
          f"ttft p50 {sorted(r.ttft for r in results)[len(results) // 2] * 1e3:.0f}ms")
    print("serve OK")


if __name__ == "__main__":
    main()
