"""The paper's main path compiles for a TPU v5e — checked without a chip.

The TPU compiler is installed next to JAX and compiles for a chip that is
described, not attached (``jax.experimental.topologies``).  That refuses
what interpret mode cannot see: blocks not aligned to the (8, 128) tiling,
value-level gathers, more VMEM than a kernel may use, a program that does
not fit the device.  Nothing runs here, so this says nothing about
results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.  Code that asks ``jax.default_backend()``
still sees the CPU, so each test steers the kernels onto the compiled
path through the ``KernelPolicy`` (``interpret=False``, ``autotune=False``).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import ALEXNET_FAITHFUL
from repro.kernels.common import KernelPolicy
from repro.kernels.conv2d import ops as conv_ops
from repro.kernels.lrn.lrn import lrn_pallas

HBM_BYTES = 16e9        # one TPU v5e chip

COMPILED = KernelPolicy(backend="pallas", interpret=False, autotune=False)


def _faithful_layers():
    """(name, x shape without batch, ConvSpec) for the five conv layers,
    and the LRN input shapes, at the faithful net's real widths."""
    cfg = ALEXNET_FAITHFUL
    convs, lrns = [], []
    hw, cin = cfg.image_size, cfg.in_channels
    for i, cs in enumerate(cfg.convs):
        convs.append(pytest.param((hw, hw, cin), cs, id=f"conv{i + 1}"))
        hw = (hw + 2 * cs.padding - cs.kernel) // cs.stride + 1
        if cs.pool:
            hw = (hw - 3) // 2 + 1
        if cs.lrn:
            lrns.append(pytest.param((hw, hw, cs.out_channels),
                                     id=f"lrn{i + 1}"))
        cin = cs.out_channels
    return convs, lrns


CONV_LAYERS, LRN_LAYERS = _faithful_layers()


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any failure: no TPU lib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("hwc,cs", CONV_LAYERS)
def test_faithful_conv_compiles(one_chip, hwc, cs):
    batch = 32
    cin = hwc[-1]
    x = _spec((batch,) + hwc, jnp.float32, one_chip)
    w = _spec((cs.kernel, cs.kernel, cin // cs.groups, cs.out_channels),
              jnp.float32, one_chip)
    b = _spec((cs.out_channels,), jnp.float32, one_chip)

    def conv(x, w, b):
        return conv_ops.conv2d_fused(x, w, stride=cs.stride,
                                     padding=cs.padding, bias=b, relu=True,
                                     groups=cs.groups, interpret=False,
                                     autotune=False)

    compiled = jax.jit(conv).lower(x, w, b).compile()
    assert "tpu_custom_call" in compiled.as_text()
    oh = (hwc[0] + 2 * cs.padding - cs.kernel) // cs.stride + 1
    assert compiled.out_info.shape == (batch, oh, oh, cs.out_channels)


@pytest.mark.parametrize("hwc", LRN_LAYERS)
def test_faithful_lrn_compiles(one_chip, hwc):
    cfg = ALEXNET_FAITHFUL
    x = _spec((32,) + hwc, jnp.float32, one_chip)
    compiled = jax.jit(lambda x: lrn_pallas(
        x, n=cfg.lrn_n, alpha=cfg.lrn_alpha, beta=cfg.lrn_beta,
        k=cfg.lrn_k, interpret=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("hw,dtype,lead", [
    pytest.param(256, jnp.uint8, (256,), id="staged-uint8-256"),
    pytest.param(ALEXNET_FAITHFUL.image_size + 8, jnp.float32, (1, 256),
                 id="trainer-float32-margin")])
def test_image_preprocess_compiles(one_chip, hw, dtype, lead):
    """The loader's device program (mean subtraction, crop, mirror) at
    the faithful crop and batch 256 compiles, as the staged feed calls it
    and as the trainer's one-replica stream does, and its own memory
    stays within four batches' worth."""
    from repro.data.preprocess import _crop_flip_subtract
    cfg = ALEXNET_FAITHFUL
    c, crop = cfg.in_channels, cfg.image_size
    raw = _spec((256, hw, hw * c), dtype, one_chip)
    mean = _spec((hw, hw * c), jnp.float32, one_chip)
    draws = _spec((3, 256), jnp.int32, one_chip)
    compiled = _crop_flip_subtract.lower(raw, mean, draws, crop=crop,
                                         channels=c, lead=lead).compile()
    assert compiled.out_info.shape == lead + (crop, crop, c)
    mem = compiled.memory_analysis()
    batch = 256 * crop * crop * c * 4
    assert mem.temp_size_in_bytes + mem.output_size_in_bytes < 4 * batch


@pytest.fixture(scope="module")
def faithful_one_chip(one_chip):
    """The trainer's one-chip program, compiled: faithful AlexNet at 227²,
    global batch 256, reference engine with one replica."""
    from repro.core import init_param_avg_state, make_param_avg_step
    from repro.models import alexnet
    from repro.optim import schedules
    from repro.optim.optimizers import get_optimizer

    cfg = dataclasses.replace(ALEXNET_FAITHFUL, kernels=COMPILED)
    opt = get_optimizer("sgd_momentum")

    def loss(params, batch):
        return alexnet.loss_fn(params, cfg, batch["images"],
                               batch["labels"])

    step = make_param_avg_step(loss, opt, schedules.constant(0.01))
    state = jax.eval_shape(lambda: init_param_avg_state(
        jax.random.PRNGKey(0), lambda r: alexnet.init(r, cfg), opt, 1))
    state = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), state)
    size = cfg.image_size
    batch = {"images": _spec((1, 256, size, size, cfg.in_channels),
                             jnp.float32, one_chip),
             "labels": _spec((1, 256), jnp.int32, one_chip)}
    return jax.jit(step, donate_argnums=0).lower(state, batch).compile()


def test_faithful_train_step_fits_one_chip(faithful_one_chip):
    """The one-chip program compiles with the Pallas conv and LRN in it
    and fits one chip's HBM."""
    compiled = faithful_one_chip
    txt = compiled.as_text()
    # five conv kernels and two LRN kernels in the forward pass
    assert txt.count("tpu_custom_call") >= 7
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 1e9:.2f} GB does not fit one chip"


@pytest.fixture(scope="module")
def faithful_four_chips(topo):
    """The paper's four-GPU recipe on four described v5e chips, compiled:
    the mesh engine (shard_map over ('data',), one replica per chip, 64
    images each, all_reduce exchange)."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import init_param_avg_state, make_mesh_param_avg_step
    from repro.models import alexnet
    from repro.optim import schedules
    from repro.optim.optimizers import get_optimizer
    from repro.sharding.specs import replica_sharding

    n_rep, per_replica = 4, 64
    cfg = dataclasses.replace(ALEXNET_FAITHFUL, kernels=COMPILED)
    opt = get_optimizer("sgd_momentum")
    mesh = Mesh(np.array(topo.devices[:n_rep]), ("data",))

    def loss(params, batch):
        return alexnet.loss_fn(params, cfg, batch["images"],
                               batch["labels"])

    step = make_mesh_param_avg_step(loss, opt, schedules.constant(0.01),
                                    mesh=mesh, strategy="all_reduce",
                                    replica_axes=("data",))
    size = cfg.image_size

    def placed(tree):
        shard = replica_sharding(tree, mesh, replica_axes=("data",))
        return jax.tree.map(lambda s, sh: _spec(s.shape, s.dtype, sh),
                            tree, shard)

    state = placed(jax.eval_shape(lambda: init_param_avg_state(
        jax.random.PRNGKey(0), lambda r: alexnet.init(r, cfg), opt, n_rep)))
    batch = placed({
        "images": jax.ShapeDtypeStruct(
            (n_rep, per_replica, size, size, cfg.in_channels), jnp.float32),
        "labels": jax.ShapeDtypeStruct((n_rep, per_replica), jnp.int32)})
    return jax.jit(step, donate_argnums=0).lower(state, batch).compile()


def test_faithful_mesh_step_fits_four_chips(faithful_four_chips):
    """The mesh program compiles with the Pallas kernels in it, fits each
    chip's HBM and exchanges through an all-reduce."""
    compiled = faithful_four_chips
    txt = compiled.as_text()
    assert txt.count("tpu_custom_call") >= 7
    assert "all-reduce" in txt
    ma = compiled.memory_analysis()
    per_chip = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert per_chip < HBM_BYTES, f"{per_chip / 1e9:.2f} GB per chip"


def _phases_and_kinds(hlo_text: str):
    """``bench/``'s phase and op-kind classes of a compiled step."""
    import sys
    from pathlib import Path
    bench = str(Path(__file__).resolve().parents[2] / "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import phases
    import xplane
    return phases.hlo_phases(hlo_text), xplane.hlo_kinds(hlo_text)


@pytest.mark.parametrize("program", ["faithful_one_chip",
                                     "faithful_four_chips"])
def test_faithful_step_phases(program, request):
    """Every op of the chip's program falls in a phase of the step: the
    nine conv-grads in the backward, the five Pallas conv forwards in the
    forward, the collectives in the exchange (four chips)."""
    ph, kinds = _phases_and_kinds(
        request.getfixturevalue(program).as_text())
    by_kind = {}
    for name, kind in kinds.items():
        by_kind.setdefault(kind, []).append(ph[name])
    assert by_kind["conv_grad"] == ["backward"] * 9
    assert by_kind["conv_fwd"] == ["forward"] * 5
    assert set(by_kind.get("collective", [])) <= {"exchange"}
    assert ("collective" in by_kind) == (program == "faithful_four_chips")
    assert "other" not in ph.values()
    assert {"forward", "backward", "update"} <= set(ph.values())
