"""Multi-device behaviour, run in subprocesses so the forced device count
never leaks into the main test process (per the dry-run isolation rule)."""
from _subproc import run_child


def test_param_avg_step_on_mesh():
    """The paper's step, actually sharded over 4 replicas x 2-way TP."""
    out = run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import models
from repro.configs import ARCHS, reduced
from repro.core import init_param_avg_state, make_param_avg_step, reshape_for_replicas, replica_spread
from repro.optim import schedules
from repro.optim.optimizers import sgd_momentum
from repro.sharding.specs import state_sharding, batch_sharding

assert jax.device_count() == 8
mesh = jax.make_mesh((4, 2), ("data", "model"))
cfg = reduced(ARCHS["olmo-1b"])
opt = sgd_momentum()
R = 4
state = init_param_avg_state(jax.random.PRNGKey(0), lambda r: models.init(r, cfg), opt, R)
sshard = state_sharding(jax.eval_shape(lambda: state), cfg, mesh, replica_axes=("data",))
state = jax.device_put(state, sshard)
step = jax.jit(make_param_avg_step(lambda p, b: models.loss_fn(p, cfg, b), opt, schedules.constant(1e-2)),
               in_shardings=(sshard, None), out_shardings=(sshard, NamedSharding(mesh, P())))
rng = jax.random.PRNGKey(1)
losses = []
for i in range(4):
    k = jax.random.fold_in(rng, i)
    batch = {"tokens": jax.random.randint(k, (8, 64), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (8, 64), 0, cfg.vocab_size)}
    state, loss = step(state, reshape_for_replicas(batch, R))
    losses.append(float(loss))
assert all(np.isfinite(losses)), losses
spread = float(replica_spread(state.params))
assert spread < 1e-5, spread
print("OK", losses[0], "->", losses[-1], "spread", spread)
""")
    assert "OK" in out


def test_sharded_equals_single_device():
    """Sharded param-avg step produces the same numbers as 1-device."""
    code_tpl = """
import jax, jax.numpy as jnp, numpy as np
from repro import models
from repro.configs import ARCHS, reduced
from repro.core import init_param_avg_state, make_param_avg_step, reshape_for_replicas
from repro.optim import schedules
from repro.optim.optimizers import sgd_momentum
cfg = reduced(ARCHS["olmo-1b"])
opt = sgd_momentum()
state = init_param_avg_state(jax.random.PRNGKey(0), lambda r: models.init(r, cfg), opt, 2)
step = jax.jit(make_param_avg_step(lambda p, b: models.loss_fn(p, cfg, b), opt, schedules.constant(1e-2)))
rng = jax.random.PRNGKey(1)
for i in range(3):
    k = jax.random.fold_in(rng, i)
    batch = {"tokens": jax.random.randint(k, (4, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (4, 32), 0, cfg.vocab_size)}
    state, loss = step(state, reshape_for_replicas(batch, 2))
print(float(loss))
"""
    l8 = float(run_child(code_tpl, devices=8).strip().splitlines()[-1])
    l1 = float(run_child(code_tpl, devices=1).strip().splitlines()[-1])
    assert abs(l8 - l1) < 1e-3, (l8, l1)


def test_exchange_strategies_lower_to_collectives():
    """ring/pairwise exchange lower to collective-permute; all_reduce to
    all-reduce — on a real multi-device mesh."""
    out = run_child("""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import exchange_average
mesh = jax.make_mesh((8,), ("data",))
x = {"w": jnp.arange(8.0 * 4).reshape(8, 4)}
sh = {"w": NamedSharding(mesh, P("data", None))}
for strat in ("all_reduce", "ring", "pairwise"):
    f = jax.jit(lambda t, s=strat: exchange_average(t, s), in_shardings=(sh,), out_shardings=sh)
    txt = f.lower(jax.device_put(x, sh)).compile().as_text()
    has_ar = "all-reduce" in txt
    has_cp = "collective-permute" in txt or "all-to-all" in txt or has_ar
    out = f(jax.device_put(x, sh))
    import numpy as np
    np.testing.assert_allclose(np.asarray(out["w"]),
                               np.broadcast_to(np.asarray(x["w"]).mean(0), (8, 4)), rtol=1e-6)
    print(strat, "all-reduce" if has_ar else "", "ok")
print("OK")
""")
    assert "OK" in out


def test_mesh_engine_real_model_on_mesh():
    """The mesh-native engine (shard_map + collectives) training a reduced
    transformer: finite losses, zero replica spread, all-reduce in HLO."""
    out = run_child("""
import jax, jax.numpy as jnp, numpy as np
from repro import models
from repro.configs import ARCHS, reduced
from repro.core import (init_param_avg_state, make_mesh_param_avg_step,
                        reshape_for_replicas, replica_spread)
from repro.launch.mesh import make_replica_mesh
from repro.optim import schedules
from repro.optim.optimizers import sgd_momentum
from repro.sharding.specs import replica_sharding

R = jax.device_count()
mesh = make_replica_mesh(R)
cfg = reduced(ARCHS["olmo-1b"])
opt = sgd_momentum()
state = init_param_avg_state(jax.random.PRNGKey(0),
                             lambda r: models.init(r, cfg), opt, R)
state = jax.device_put(state, replica_sharding(state, mesh,
                                               replica_axes=("data",)))
step = jax.jit(make_mesh_param_avg_step(
    lambda p, b: models.loss_fn(p, cfg, b), opt, schedules.constant(1e-2),
    mesh=mesh, replica_axes=("data",)))
rng = jax.random.PRNGKey(1)
losses = []
for i in range(3):
    k = jax.random.fold_in(rng, i)
    batch = {"tokens": jax.random.randint(k, (2 * R, 64), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (2 * R, 64), 0, cfg.vocab_size)}
    rb = reshape_for_replicas(batch, R)
    rb = jax.device_put(rb, replica_sharding(rb, mesh,
                                             replica_axes=("data",)))
    if i == 0:
        txt = step.lower(state, rb).compile().as_text()
        assert "all-reduce" in txt
    state, loss = step(state, rb)
    losses.append(float(loss))
assert all(np.isfinite(losses)), losses
spread = float(replica_spread(state.params))
assert spread < 1e-5, spread
print("OK", losses[0], "->", losses[-1], "spread", spread)
""", devices=4)
    assert "OK" in out


def test_small_mesh_dryrun_lowering():
    """dryrun's build_lowered machinery on a small host mesh: one dense,
    one moe, one ssm arch; train + decode."""
    out = run_child("""
import jax, jax.numpy as jnp
jax.devices()   # lock device count BEFORE dryrun import overwrites XLA_FLAGS
from repro.configs import ARCHS, SHAPES, reduced
import dataclasses
from repro.launch import dryrun as D
mesh = jax.make_mesh((2, 2), ("data", "model"))
for arch in ("olmo-1b", "mixtral-8x7b", "rwkv6-7b"):
    cfg = reduced(ARCHS[arch])
    shape = dataclasses.replace(SHAPES["train_4k"], seq_len=128, global_batch=8)
    lowered = D.build_lowered(cfg, shape, mesh, "train", ("data",), None, 2, "qloop")
    compiled = lowered.compile()
    assert compiled.cost_analysis()["flops"] > 0
    shape_d = dataclasses.replace(SHAPES["decode_32k"], seq_len=64, global_batch=4)
    lowered = D.build_lowered(cfg, shape_d, mesh, "decode", None, None, 1, "qloop")
    lowered.compile()
    print(arch, "ok")
print("OK")
""", devices=4)
    assert "OK" in out
