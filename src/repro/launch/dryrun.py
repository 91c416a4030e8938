"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST be imported/run before any other jax usage: the first two lines force
512 host placeholder devices so the production meshes can be built.  Do NOT
replicate this env var anywhere else (tests/benches see 1 device).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma-7b \
        --shape train_4k --mesh pod1
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh pod1 pod2

Writes one JSON per combo under results/dryrun/ with memory analysis, HLO
cost analysis, and the collective-bytes breakdown parsed from the optimized
HLO — the inputs to the roofline table in EXPERIMENTS.md.

Scan-cost correction: XLA's cost analysis counts a while-loop body ONCE,
but our models scan over ``np_`` layer superblocks.  We therefore compile
two auxiliary depths (1 and 2 superblocks, identical shapes otherwise) and
extrapolate  total(np_) = outer + np_ * body  with body = c(2) - c(1),
outer = 2 c(1) - c(2), per metric (flops / bytes / collective bytes).  The
full-depth compile still provides the memory analysis (activation stacking
scales with depth) and proves the real config lowers.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402
import argparse
import dataclasses
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.configs import ASSIGNED, SHAPES, get_config, supports_shape
from repro.core import (as_exchanger, init_param_avg_state,
                        make_param_avg_step)
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh, mesh_chips)
from repro.models.transformer import block_kinds
from repro.optim import schedules
from repro.optim.optimizers import sgd_momentum
from repro.sharding.specs import (batch_sharding, cache_sharding,
                                  state_sharding)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

# Per-device budget (v5e: 16 GB HBM); above this for a full replica's train
# state we fall back to FSDP sharding of the big dims over 'data' and keep
# the replica (param-averaging) axis on 'pod' only.  This is the documented
# capacity limit of the paper's naive data parallelism (DESIGN.md §5).
FSDP_THRESHOLD_BYTES = 10e9


def train_state_bytes_per_device(cfg, model_size: int) -> float:
    n = cfg.n_params()
    return (2 * n + 4 * n) / model_size      # bf16 params + fp32 momentum


def pick_layout(cfg, mesh, mode: str):
    names = mesh.axis_names
    sizes = dict(zip(names, mesh.devices.shape))
    model_size = sizes.get("model", 1)
    if mode != "train":
        serve_heavy = (2 * cfg.n_params()) / model_size > FSDP_THRESHOLD_BYTES
        return None, ("data" if serve_heavy else None), 1
    heavy = train_state_bytes_per_device(cfg, model_size) > FSDP_THRESHOLD_BYTES
    if heavy:
        replica_axes = tuple(a for a in ("pod",) if a in names)
        fsdp = "data"
    else:
        replica_axes = tuple(a for a in ("pod", "data") if a in names)
        fsdp = None
    n_rep = 1
    for a in replica_axes:
        n_rep *= sizes[a]
    return replica_axes, fsdp, max(n_rep, 1)


def abstract_train_state(cfg, n_replicas: int, momentum_dtype="float32"):
    opt = sgd_momentum(state_dtype=momentum_dtype)
    rng = jax.random.PRNGKey(0)
    return jax.eval_shape(
        lambda: init_param_avg_state(rng, lambda r: models.init(r, cfg), opt,
                                     n_replicas))


def batch_structs(cfg, shape, n_replicas, replica_axes):
    spec = models.model_inputs(cfg, shape.global_batch, shape.seq_len)
    structs = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in spec.items()}
    if replica_axes is not None:
        structs = {
            k: jax.ShapeDtypeStruct(
                (n_replicas, v.shape[0] // n_replicas) + v.shape[1:], v.dtype)
            for k, v in structs.items()}
    return structs


def build_lowered(cfg, shape, mesh, mode, replica_axes, fsdp, n_rep,
                  attn_impl="qloop", strategy="all_reduce",
                  microbatch: int = 1, momentum_dtype: str = "float32"):
    """Lower one step function; returns the jax Lowered object."""
    # the attention impl rides on the config's KernelPolicy now; qloop is
    # the dry-run default (static KV slices -> near-exact HLO flops)
    from repro.kernels.common import policy_of
    cfg = dataclasses.replace(
        cfg, kernels=dataclasses.replace(policy_of(cfg),
                                         attention=attn_impl))
    if mode == "train":
        state_sh = abstract_train_state(cfg, n_rep, momentum_dtype)
        state_shard = state_sharding(state_sh, cfg, mesh,
                                     replica_axes=replica_axes,
                                     fsdp_axis=fsdp)
        bstructs = batch_structs(cfg, shape, n_rep, replica_axes)
        b_shard = batch_sharding(bstructs, mesh,
                                 batch_axes=replica_axes or (),
                                 inner_axis=fsdp)
        opt = sgd_momentum(state_dtype=momentum_dtype)
        # reference engine on purpose: production meshes combine replicas
        # with a model axis, which shard_map cannot yet delegate to GSPMD
        # (no partial-auto mode in the pinned jax) — same Exchanger API as
        # the mesh engine, axis-0 execution.
        step = make_param_avg_step(
            lambda p, b: models.loss_fn(p, cfg, b, remat=True),
            opt, schedules.constant(1e-2), strategy=as_exchanger(strategy),
            microbatch=microbatch)
        jitted = jax.jit(step, in_shardings=(state_shard, b_shard),
                         out_shardings=(state_shard,
                                        NamedSharding(mesh, P())))
        return jitted.lower(state_sh, bstructs)
    if mode == "prefill":
        params_sh = jax.eval_shape(
            lambda: models.init(jax.random.PRNGKey(0), cfg))
        p_shard = state_sharding(params_sh, cfg, mesh, fsdp_axis=fsdp)
        bstructs = batch_structs(cfg, shape, 1, None)
        bstructs.pop("labels")
        b_shard = batch_sharding(bstructs, mesh)

        def fn(params, batch):
            logits, _ = models.logits_fn(params, cfg, batch)
            return logits

        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
        return jitted.lower(params_sh, bstructs)
    # decode
    params_sh = jax.eval_shape(
        lambda: models.init(jax.random.PRNGKey(0), cfg))
    p_shard = state_sharding(params_sh, cfg, mesh, fsdp_axis=fsdp)
    b = shape.global_batch
    cache_sh = jax.eval_shape(
        lambda: models.init_decode_cache(cfg, b, shape.seq_len))
    c_shard = cache_sharding(cache_sh, cfg, mesh)
    toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    t_shard = batch_sharding(toks, mesh)
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def fn(params, cache, tokens, pos):
        return models.decode_step(params, cfg, cache, tokens, pos)

    jitted = jax.jit(fn,
                     in_shardings=(p_shard, c_shard, t_shard,
                                   NamedSharding(mesh, P())),
                     out_shardings=(None, c_shard))
    return jitted.lower(params_sh, cache_sh, toks, pos)


# ------------------------------------------------------- HLO text parsing ----

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")
_SHAPE_RE = re.compile(r"(f64|f32|bf16|f16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")


def _shape_bytes(segment: str) -> int:
    total = 0
    for dm in _SHAPE_RE.finditer(segment):
        dt, dims = dm.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-op-kind operand bytes summed over the module (per device).

    Operand sizes derive from the RESULT shape on the instruction's LHS:
    all-reduce / all-to-all / collective-permute move ~result-size data;
    all-gather's operand is result/group; reduce-scatter's is result*group.
    """
    out = {}
    for line in hlo_text.splitlines():
        m = None
        for kind in _COLL_KINDS:
            mm = re.search(rf"= *((?:\([^)]*\))|(?:\S+)) +{kind}"
                           rf"(?:-start)?\(", line)
            if mm:
                m = (kind, mm.group(1))
                break
        if m is None:
            continue
        kind, result_seg = m
        nbytes = _shape_bytes(result_seg)
        gm = _GROUPS_RE.search(line)
        group = len(gm.group(1).split(",")) if gm else 1
        if kind == "all-gather" and group:
            nbytes = nbytes // max(group, 1)
        elif kind == "reduce-scatter":
            nbytes = nbytes * group
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += nbytes
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


def analyze(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "transcendentals": float(cost.get("transcendentals", 0.0)),
        "collectives": parse_collectives(compiled.as_text()),
    }


def _correct(raw: dict, c1: dict, c2: dict, np_: int) -> dict:
    """corrected = raw + (np_-1) * body, with body = c2 - c1 measured from
    UNROLLED depth-1 and depth-2 compiles (exact per-superblock cost).  The
    raw scan compile counts the body once, so np_-1 copies are missing."""
    def addl(a, b):
        return max(b - a, 0.0) * (np_ - 1)

    out = {k: raw[k] + addl(c1[k], c2[k]) for k in ("flops", "bytes",
                                                    "transcendentals")}
    colls = {}
    kinds = (set(c1["collectives"]) | set(c2["collectives"]) |
             set(raw["collectives"])) - {"total_bytes"}
    for kind in kinds:
        r = raw["collectives"].get(kind, {"count": 0, "bytes": 0})
        a = c1["collectives"].get(kind, {"count": 0, "bytes": 0})
        b = c2["collectives"].get(kind, {"count": 0, "bytes": 0})
        colls[kind] = {
            "count": int(r["count"] + addl(float(a["count"]),
                                           float(b["count"]))),
            "bytes": r["bytes"] + addl(float(a["bytes"]), float(b["bytes"]))}
    colls["total_bytes"] = sum(v["bytes"] for v in colls.values()
                               if isinstance(v, dict))
    out["collectives"] = colls
    return out


def _with_depth(cfg, n_super: int):
    """Same config with n_layers = n_super*len(pattern) + remainder."""
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, n_layers=n_super,
                                   n_enc_layers=n_super)
    p = len(block_kinds(cfg))
    rem = cfg.n_layers % p
    return dataclasses.replace(cfg, n_layers=n_super * p + rem)


def _depth_units(cfg) -> int:
    """Number of scanned superblocks in the real config."""
    if cfg.family == "encdec":
        return cfg.n_layers          # enc and dec scale together
    return cfg.n_layers // len(block_kinds(cfg))


def make_mesh_named(mesh_name: str, mesh_shape=None):
    """pod1/pod2 production meshes; optional custom (data, model)
    factorization of the 256-chip pod for §Perf experiments."""
    if mesh_shape is not None:
        dims = tuple(int(x) for x in mesh_shape.split(","))
        if mesh_name == "pod2":
            return jax.make_mesh((2,) + dims, ("pod", "data", "model"))
        assert dims[0] * dims[1] == 256, dims
        return jax.make_mesh(dims, ("data", "model"))
    return make_production_mesh(multi_pod=(mesh_name == "pod2"))


def lower_one(arch: str, shape_name: str, mesh_name: str,
              attn_impl: str = "qloop", strategy: str = "all_reduce",
              layout_override=None, skip_aux: bool = False,
              variant: str = None, mesh_shape: str = None,
              microbatch: int = 1, momentum_dtype: str = "float32"):
    from repro.configs.variants import VARIANTS
    cfg = get_config(arch)
    if variant:
        cfg = VARIANTS[variant](cfg)
    shape = SHAPES[shape_name]
    mesh = make_mesh_named(mesh_name, mesh_shape)
    chips = mesh_chips(mesh)
    mode = shape.kind
    replica_axes, fsdp, n_rep = pick_layout(cfg, mesh, mode)
    if layout_override:
        replica_axes, fsdp, n_rep = layout_override(mesh, replica_axes,
                                                    fsdp, n_rep)
    info = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "chips": chips, "mode": mode, "n_replicas": n_rep,
            "replica_axes": list(replica_axes or []), "fsdp_axis": fsdp,
            "strategy": strategy, "attn_impl": attn_impl,
            "variant": variant, "mesh_shape": mesh_shape,
            "microbatch": microbatch,
            "params": cfg.n_params(),
            "active_params": cfg.n_active_params()}

    with mesh:
        t0 = time.time()
        lowered = build_lowered(cfg, shape, mesh, mode, replica_axes, fsdp,
                                n_rep, attn_impl, strategy, microbatch,
                                momentum_dtype)
        info["lower_s"] = round(time.time() - t0, 1)
        t1 = time.time()
        compiled = lowered.compile()
        info["compile_s"] = round(time.time() - t1, 1)

        mem = compiled.memory_analysis()
        info["memory"] = {
            k: getattr(mem, k, None)
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "alias_size_in_bytes")}
        raw = analyze(compiled)
        info["cost_raw"] = {k: raw[k] for k in ("flops", "bytes")}
        info["collectives_raw"] = raw["collectives"]

        np_ = _depth_units(cfg)
        if skip_aux or np_ <= 1:
            corrected = raw
            info["scan_correction"] = "none"
        else:
            from repro.models import _unroll
            aux = []
            try:
                _unroll.UNROLL = True
                for d in (1, 2):
                    cfg_d = _with_depth(cfg, d)
                    low_d = build_lowered(cfg_d, shape, mesh, mode,
                                          replica_axes, fsdp, n_rep,
                                          attn_impl, strategy, microbatch,
                                          momentum_dtype)
                    aux.append(analyze(low_d.compile()))
            finally:
                _unroll.UNROLL = False
            info["aux_flops"] = [a["flops"] for a in aux]
            info["aux_bytes"] = [a["bytes"] for a in aux]
            corrected = _correct(raw, aux[0], aux[1], np_)
            info["scan_correction"] = f"unrolled-body np={np_}"

    info["cost"] = {k: corrected[k] for k in ("flops", "bytes",
                                              "transcendentals")}
    info["collectives"] = corrected["collectives"]
    info["roofline"] = roofline_terms(info, cfg, shape, chips)
    return info


def roofline_terms(info: dict, cfg, shape, chips: int) -> dict:
    """The three roofline terms in seconds (cost analysis is per-device
    under SPMD, so per-chip peaks divide directly)."""
    flops = info["cost"]["flops"]
    bytes_ = info["cost"]["bytes"]
    coll = info["collectives"]["total_bytes"]
    compute_s = flops / PEAK_FLOPS_BF16
    memory_s = bytes_ / HBM_BW
    collective_s = coll / ICI_BW
    dom = max(("compute", compute_s), ("memory", memory_s),
              ("collective", collective_s), key=lambda kv: kv[1])[0]
    n_active = cfg.n_active_params()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6 if shape.kind == "train" else 2
    model_flops = mult * n_active * tokens
    hlo_total = flops * chips
    return {"compute_s": compute_s, "memory_s": memory_s,
            "collective_s": collective_s, "dominant": dom,
            "model_flops": model_flops, "hlo_flops_total": hlo_total,
            "useful_fraction": (model_flops / hlo_total) if hlo_total else None}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", nargs="*", default=["pod1"],
                    choices=["pod1", "pod2"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--attn-impl", default="qloop")
    ap.add_argument("--strategy", default="all_reduce")
    ap.add_argument("--skip-aux", action="store_true",
                    help="skip the scan-correction aux compiles (faster)")
    ap.add_argument("--variant", default=None,
                    help="named config variant (configs/variants.py)")
    ap.add_argument("--mesh-shape", default=None,
                    help="custom data,model factorization, e.g. 32,8")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--momentum-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()

    archs = args.arch or (ASSIGNED if args.all else ["gemma-7b"])
    shapes = args.shape or (list(SHAPES) if args.all else ["train_4k"])
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for mesh_name in args.mesh:
        for arch in archs:
            cfg = get_config(arch)
            for shape_name in shapes:
                arch_eff = arch
                if not supports_shape(cfg, SHAPES[shape_name]):
                    if arch == "gemma-7b" and shape_name == "long_500k":
                        arch_eff = "gemma-7b-swa"   # documented SWA variant
                    else:
                        print(f"SKIP {arch} x {shape_name} "
                              f"(unsupported, see DESIGN.md)")
                        continue
                tag = f"{arch}_{shape_name}_{mesh_name}"
                if args.variant:
                    tag += f"__{args.variant}"
                if args.strategy != "all_reduce":
                    tag += f"__{args.strategy}"
                if args.mesh_shape:
                    tag += f"__mesh{args.mesh_shape.replace(',', 'x')}"
                if args.microbatch > 1:
                    tag += f"__mb{args.microbatch}"
                if args.momentum_dtype != "float32":
                    tag += "__mombf16"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"CACHED {tag}")
                    continue
                print(f"RUN {tag} ...", flush=True)
                try:
                    info = lower_one(arch_eff, shape_name, mesh_name,
                                     attn_impl=args.attn_impl,
                                     strategy=args.strategy,
                                     skip_aux=args.skip_aux,
                                     variant=args.variant,
                                     mesh_shape=args.mesh_shape,
                                     microbatch=args.microbatch,
                                     momentum_dtype=args.momentum_dtype)
                    with open(path, "w") as f:
                        json.dump(info, f, indent=1)
                    r = info["roofline"]
                    print(f"  OK lower={info['lower_s']}s "
                          f"compile={info['compile_s']}s "
                          f"compute={r['compute_s']:.4f}s "
                          f"memory={r['memory_s']:.4f}s "
                          f"coll={r['collective_s']:.4f}s "
                          f"dom={r['dominant']} "
                          f"useful={r['useful_fraction']:.2f}", flush=True)
                except Exception as e:
                    failures.append((tag, repr(e)))
                    print(f"  FAIL {tag}: {e}")
                    traceback.print_exc()
    if failures:
        print("FAILURES:", failures)
        raise SystemExit(1)
    print("all requested dry-runs passed")


if __name__ == "__main__":
    main()
