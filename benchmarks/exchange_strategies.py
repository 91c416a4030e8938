"""Paper Fig. 2 analogue: weight exchange-and-average strategies.

Three tables:

1. bare exchange of an AlexNet-sized pytree per strategy — wall time + the
   collective ops each lowers to (from compiled HLO), the communication-
   schedule axis the paper explored with P2P copies on a PCIe switch;
2. full mesh-engine train step (shard_map, AlexNet-smoke) per strategy —
   end-to-end step time with the exchange on the critical path, the
   Table 1-style number — plus the (delay, compression) grid on the
   all_reduce strategy: delay=1 (one-step-stale overlapped exchange) x
   {none, bf16, topk} wire compression;
3. ``exchange_scaling`` — the replica-scaling curve this PR exists for:
   reference engine, sequential per-replica execution
   (``replica_exec="scan"``), fixed global batch, R in {1, 2, 4}.  At
   fixed global batch each replica's fwd/bwd runs at batch G/R, whose
   smaller working set is more cache-resident — the host analogue of
   the paper's multi-GPU scaling.  Timing is min-of-reps with the
   configs interleaved round-robin inside each rep, so background-load
   drift hits every config equally instead of whichever one ran during
   a busy window.

Tables 1-2 run over ``REPRO_DEVICES`` host-device replicas (default 4);
table 3 runs the reference engine in a single-device child.

    REPRO_DEVICES=4 PYTHONPATH=src python -m benchmarks.run \
        --only exchange_strategies
"""
from __future__ import annotations

import os

from benchmarks.common import emit, run_subprocess_bench

CHILD_EXCHANGE = """
import time, re, jax, jax.numpy as jnp
from repro.core import Exchanger, replica_specs
from repro.core.param_avg import shard_map
from repro.launch.mesh import make_replica_mesh
from repro.models import alexnet
from repro.configs import ALEXNET_SMOKE

R = jax.device_count()
mesh = make_replica_mesh(R)
params = alexnet.init(jax.random.PRNGKey(0), ALEXNET_SMOKE)
rep = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), params)
spec = replica_specs(rep, "data")
from repro.sharding.specs import replica_sharding
rep = jax.device_put(rep, replica_sharding(rep, mesh, replica_axes=("data",)))
n_bytes = sum(x.nbytes for x in jax.tree.leaves(rep))
pow2 = R & (R - 1) == 0
strats = ("all_reduce", "ring", "pairwise") if pow2 else \
    ("all_reduce", "ring")
if not pow2:
    print(f"# pairwise skipped: needs power-of-two replicas, got {R}")
for strat in strats:
    ex = Exchanger(strat, axis="data")
    f = jax.jit(shard_map(lambda t: ex.average(t), mesh=mesh,
                          in_specs=(spec,), out_specs=spec, check_vma=False))
    txt = f.lower(rep).compile().as_text()
    ops = {k: len(re.findall(k + r"(?:-start)?\\(", txt))
           for k in ("all-reduce", "collective-permute", "all-gather", "all-to-all")}
    want = ex.expected_collective
    assert ops.get(want), (strat, want, ops)
    jax.block_until_ready(f(rep))
    t0 = time.time()
    for _ in range(10):
        out = f(rep)
    jax.block_until_ready(out)
    us = (time.time() - t0) / 10 * 1e6
    opstr = ";".join(f"{k}:{v}" for k, v in ops.items() if v)
    print(f"RESULT,{strat},{us:.1f},replicas={R};bytes={n_bytes};{opstr}")
"""

CHILD_STEP = """
import time, jax, jax.numpy as jnp, numpy as np
from repro.configs import ALEXNET_SMOKE
from repro.core import (ExchangeConfig, init_param_avg_state,
                        make_mesh_param_avg_step, reshape_for_replicas)
from repro.launch.mesh import make_replica_mesh
from repro.models import alexnet
from repro.optim import schedules
from repro.optim.optimizers import sgd_momentum
from repro.sharding.specs import replica_sharding

R = jax.device_count()
cfg = ALEXNET_SMOKE
mesh = make_replica_mesh(R)
opt = sgd_momentum()
loss = lambda p, b: alexnet.loss_fn(p, cfg, b["images"], b["labels"])
rng = np.random.default_rng(0)
batch = reshape_for_replicas(
    {"images": jnp.asarray(rng.normal(size=(4 * R, cfg.image_size,
                                            cfg.image_size, 3)), jnp.float32),
     "labels": jnp.asarray(rng.integers(0, cfg.n_classes, 4 * R), jnp.int32)},
    R)
strats = ("all_reduce", "ring", "pairwise", "none") if R & (R - 1) == 0 \
    else ("all_reduce", "ring", "none")
grid = [(s, ExchangeConfig(strategy=s)) for s in strats]
# the overlapped/compressed variants on the all_reduce strategy
grid += [("all_reduce/delay1/" + c.compression, c) for c in (
    ExchangeConfig(delay=1),
    ExchangeConfig(delay=1, compression="bf16"),
    ExchangeConfig(delay=1, compression="topk", topk_frac=0.01))]
for name, exch in grid:
    state = init_param_avg_state(jax.random.PRNGKey(0),
                                 lambda r: alexnet.init(r, cfg), opt, R,
                                 exchange=exch)
    state = jax.device_put(state, replica_sharding(state, mesh,
                                                   replica_axes=("data",)))
    b = jax.device_put(batch, replica_sharding(batch, mesh,
                                               replica_axes=("data",)))
    step = jax.jit(make_mesh_param_avg_step(loss, opt,
                                            schedules.constant(0.01),
                                            mesh=mesh, strategy=exch,
                                            replica_axes=("data",)),
                   donate_argnums=0)   # state updates in place
    state, _ = step(state, b)          # compile + warm
    jax.block_until_ready(state)
    t0 = time.time()
    for _ in range(5):
        state, l = step(state, b)
    jax.block_until_ready(state)
    us = (time.time() - t0) / 5 * 1e6
    print(f"STEP,{name},{us:.1f},replicas={R};engine=mesh")
"""

CHILD_SCALING = """
import os, time, jax, numpy as np
from repro.configs import ALEXNET_SMOKE
from repro.core import (ExchangeConfig, init_param_avg_state,
                        make_param_avg_step, reshape_for_replicas)
from repro.models import alexnet
from repro.optim.optimizers import sgd_momentum

FAST = os.environ.get("REPRO_BENCH_FAST") == "1"
G = 128 if FAST else 512          # global batch, fixed across R
REPS, ITERS = (3, 1) if FAST else (8, 2)
R_GRID = (1, 4) if FAST else (1, 2, 4)
COMPS = ("none",) if FAST else ("none", "bf16", "topk")

cfg = ALEXNET_SMOKE
opt = sgd_momentum()
loss_fn = lambda p, b: alexnet.loss_fn(p, cfg, b["images"], b["labels"])
rng = np.random.default_rng(0)

grid = [("delay0/none", 1, ExchangeConfig())]
for comp in COMPS:
    exch = ExchangeConfig(delay=1, compression=comp,
                          topk_frac=0.01 if comp == "topk" else 1.0)
    for R in R_GRID:
        grid.append((f"delay1/{comp}", R, exch))

runs = []
for desc, R, exch in grid:
    host = {"images": rng.normal(size=(G, cfg.image_size, cfg.image_size,
                                       3)).astype(np.float32),
            "labels": rng.integers(0, cfg.n_classes, G).astype(np.int32)}
    state = init_param_avg_state(jax.random.PRNGKey(0),
                                 lambda r: alexnet.init(r, cfg), opt, R,
                                 exchange=exch)
    batch = jax.device_put(reshape_for_replicas(host, R))
    step = jax.jit(make_param_avg_step(loss_fn, opt, lambda s: 0.01,
                                       strategy=exch, replica_exec="scan"),
                   donate_argnums=0)
    state, _ = step(state, batch)          # compile + warm
    jax.block_until_ready(state.params)
    runs.append([desc, R, step, state, batch, []])
# interleaved min-of-reps (see module docstring)
for rep in range(REPS):
    for r in runs:
        t0 = time.perf_counter()
        for _ in range(ITERS):
            r[3], l = r[2](r[3], r[4])
        jax.block_until_ready(r[3].params)
        r[5].append((time.perf_counter() - t0) / ITERS)
base = min(runs[0][5])
for desc, R, _, _, _, ts in runs:
    t = min(ts)
    print(f"SCALE,{desc}/{R}rep,{t * 1e6:.1f},"
          f"speedup_vs_sync_1rep={base / t:.3f}x;G={G};exec=scan")
"""


def main():
    devices = int(os.environ.get("REPRO_DEVICES", "4"))
    out = run_subprocess_bench(CHILD_EXCHANGE, devices=devices)
    for line in out.splitlines():
        if line.startswith("#"):
            print(line, flush=True)
        elif line.startswith("RESULT"):
            _, strat, us, derived = line.split(",", 3)
            emit(f"exchange/{strat}", float(us), derived)
    out = run_subprocess_bench(CHILD_STEP, devices=devices)
    rows = []
    for line in out.splitlines():
        if line.startswith("STEP"):
            _, strat, us, derived = line.split(",", 3)
            emit(f"exchange_step/{strat}", float(us), derived)
            rows.append((strat, float(us)))
    if rows:                      # human-readable per-strategy table
        base = dict(rows).get("none")
        print("# strategy                     step_us  overhead_vs_none")
        for strat, us in rows:
            ovh = f"{us - base:+.1f}us" if base else "n/a"
            print(f"# {strat:28s} {us:9.1f}  {ovh}")
    # replica-scaling curve (single-device child; the reference engine
    # lays replicas on a leading axis, no fake XLA devices needed)
    out = run_subprocess_bench(CHILD_SCALING, devices=1, timeout=900)
    for line in out.splitlines():
        if line.startswith("SCALE"):
            _, name, us, derived = line.split(",", 3)
            emit(f"exchange_scaling/{name}", float(us), derived)


if __name__ == "__main__":
    main()
