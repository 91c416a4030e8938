"""Phases of the step and the loader's spans (``bench/phases.py``): on a
hand-written HLO module, on the faithful step compiled here, and on a
small trace recorded here on the CPU."""
import re

import numpy as np
import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on sys.path)
import phases
import xplane


@pytest.mark.parametrize("op_name,phase", [
    ("jit(step)/jvp(train.loss)/jit(_conv_fused_impl)/pallas_call", "forward"),
    ("jit(step)/transpose(jvp(train.loss))/conv_general_dilated", "backward"),
    ("jit(step)/vmap(transpose(jvp(train.loss)))/dot_general", "backward"),
    ("jit(step)/train.update/mul", "update"),
    ("jit(step)/shard_map/train.exchange/psum", "exchange"),
    ("jit(step)/broadcast_in_dim", "other"),
    ("", "other"),
])
def test_phase_of_an_op_name(op_name, phase):
    assert phases.phase_of(op_name) == phase


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[4,6,6,8], param_1: f32[3,3,8,16]) -> f32[4,6,6,16] {
  %param_0 = f32[4,6,6,8]{3,2,1,0} parameter(0)
  %param_1 = f32[3,3,8,16]{3,2,1,0} parameter(1)
  ROOT %convolution.3 = f32[4,6,6,16]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/transpose(jvp(train.loss))/conv_general_dilated"}
}

%fused_computation.2 (param_0.2: f32[16]) -> f32[1,16] {
  %param_0.2 = f32[16]{0} parameter(0)
  %constant.1 = f32[] constant(0.25)
  %broadcast.1 = f32[16]{0} broadcast(%constant.1), dimensions={}
  %div.1 = f32[16]{0} multiply(%param_0.2, %broadcast.1), metadata={op_name="jit(step)/shard_map/train.exchange/div"}
  ROOT %bitcast.1 = f32[1,16]{1,0} bitcast(%div.1), metadata={op_name="jit(step)/shard_map/broadcast_in_dim"}
}

%fused_computation.3 (param_0.3: f32[16], param_1.3: f32[16]) -> f32[1,16] {
  %param_0.3 = f32[16]{0} parameter(0)
  %param_1.3 = f32[16]{0} parameter(1)
  %add.3 = f32[16]{0} add(%param_0.3, %param_1.3)
  ROOT %bitcast.3 = f32[1,16]{1,0} bitcast(%add.3), metadata={op_name="jit(step)/broadcast_in_dim"}
}

%body (b: f32[16]) -> f32[16] {
  %b = f32[16]{0} parameter(0)
  ROOT %mul.7 = f32[16]{0} multiply(%b, %b), metadata={op_name="jit(step)/train.update/while/body/mul"}
}

%cond (c: f32[16]) -> pred[] {
  %c = f32[16]{0} parameter(0)
  ROOT %constant.7 = pred[] constant(false)
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y)
}

ENTRY %main.9 (p0: f32[4,6,6,8], p1: f32[3,3,8,16], p2: f32[16], p3: f32[16]) -> f32[1,16] {
  %p0 = f32[4,6,6,8]{3,2,1,0} parameter(0), metadata={op_name="batch[\\'images\\']"}
  %p1 = f32[3,3,8,16]{3,2,1,0} parameter(1)
  %p2 = f32[16]{0} parameter(2)
  %p3 = f32[16]{0} parameter(3)
  %copy.1 = f32[4,6,6,8]{0,3,2,1} copy(%p0)
  %_conv_fused_impl.2 = f32[4,6,6,16]{3,2,1,0} custom-call(%copy.1, %p1), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(train.loss)/jit(_conv_fused_impl)/pallas_call"}
  %copy.2 = f32[4,6,6,16]{0,3,2,1} copy(%_conv_fused_impl.2)
  %fusion.7 = f32[4,6,6,16]{3,2,1,0} fusion(%copy.2, %p1), kind=kOutput, calls=%fused_computation.1
  %add.2 = f32[16]{0} add(%p2, %p3), metadata={op_name="jit(step)/train.update/add"}
  %all-reduce.5 = f32[16]{0} all-reduce(%add.2), replica_groups={}, to_apply=%add, metadata={op_name="jit(step)/shard_map/train.exchange/psum"}
  %fusion.8 = f32[1,16]{1,0} fusion(%all-reduce.5), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/shard_map/broadcast_in_dim"}
  %fusion.9 = f32[1,16]{1,0} fusion(%add.2, %p3), kind=kLoop, calls=%fused_computation.3
  %while.1 = f32[16]{0} while(%p3), condition=%cond, body=%body
  ROOT %tuple.1 = (f32[1,16]{1,0}, f32[4,6,6,16]{3,2,1,0}) tuple(%fusion.8, %fusion.7)
}
"""


def test_phases_from_a_modules_hlo():
    ph = phases.hlo_phases(HLO)
    assert ph["_conv_fused_impl.2"] == "forward"
    # a fusion without metadata: its root's
    assert ph["fusion.7"] == "backward"
    assert ph["add.2"] == "update"
    assert ph["all-reduce.5"] == "exchange"
    # a root that names no phase: the nearest value it is made from,
    # across the fused computation's parameters to the fusion's operands
    assert ph["fusion.8"] == "exchange"
    assert ph["fusion.9"] == "update"
    # XLA's copies: of a forward value, and of an argument (its user's)
    assert ph["copy.2"] == "forward"
    assert ph["copy.1"] == "forward"
    # a loop body's ops run on their own; a fusion's and a reducer's not
    assert ph["mul.7"] == "update"
    assert not {"convolution.3", "div.1", "add.9"} & set(ph)
    # the kinds the conv roofline reads are the same with the scopes
    assert xplane.hlo_kinds(HLO) == xplane.hlo_kinds(_unscoped(HLO))


def _unscoped(hlo_text: str) -> str:
    """The text with the phases' scopes taken out of every op_name, as a
    program without them would have it."""
    return re.sub(r"train\.(loss|update|exchange)/?", "", hlo_text)


@pytest.fixture(scope="module")
def faithful_step_hlo():
    """The trainer's one-chip step for the faithful net at test size,
    compiled here (``sut.build``)."""
    import seeding
    import sut
    cfg = bench_smoke.smoke_cell("alexnet-faithful.b256-resident")["config"]
    return sut.build(cfg, 1, seeding.init_params(cfg, 3)).step.as_text()


def test_faithful_step_splits_into_phases(faithful_step_hlo):
    txt = faithful_step_hlo
    ph = phases.hlo_phases(txt)
    bodies = dict(re.findall(r"^%([\w.\-]+) .*?\{\n(.*?)\n\}", txt,
                             re.S | re.M))
    matmuls, update = 0, 0
    for line in txt.splitlines():
        m = re.match(r"^\s*(?:ROOT )?%([\w.\-]+) = .*? ([\w\-]+)\(", line)
        if not m or m.group(1) not in ph:
            continue
        name, opcode = m.groups()
        body = "".join(bodies.get(c, "") for c in
                       re.findall(r"calls=%([\w.\-]+)", line))
        if opcode in ("convolution", "dot") or re.search(
                r" (convolution|dot)\(", body):
            matmuls += 1
            assert ph[name] in ("forward", "backward"), line[:200]
        if 'op_name="jit(step)/train.update' in line:
            update += 1
            assert ph[name] == "update", line[:200]
    assert matmuls >= 16 and update > 0
    assert set(ph.values()) >= {"forward", "backward", "update"}
    assert "exchange" not in ph.values()        # one replica: no exchange


def test_scopes_leave_the_op_kinds_as_they_were(faithful_step_hlo):
    kinds = xplane.hlo_kinds(faithful_step_hlo)
    bare = xplane.hlo_kinds(_unscoped(faithful_step_hlo))
    for kind in ("conv_grad", "conv_fwd"):
        assert sum(k == kind for k in kinds.values()) == \
            sum(k == kind for k in bare.values())
    assert sum(k == "conv_grad" for k in kinds.values()) > 0


def test_recorded_cpu_trace_with_loader_spans(tmp_path):
    """A loader thread's ``repro.data.*`` spans and a scoped step, recorded
    on the CPU: the spans load with their times inside ``bench.window``,
    and the step's ops fall in its phases."""
    import jax
    import jax.numpy as jnp
    from repro.data import PrefetchLoader
    from repro.data.preprocess import make_image_preprocess

    @jax.jit
    def step(w, x):
        def loss(w):
            with jax.named_scope(phases.LOSS):
                return jnp.tanh(x.reshape(x.shape[0], -1) @ w).sum()
        g = jax.grad(loss)(w)
        with jax.named_scope(phases.UPDATE):
            return w - 0.1 * g

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, (6, 4, 20, 20, 3), dtype=np.uint8)
    prep = make_image_preprocess(np.full((20, 20, 3), 128.0), crop=16)
    w = jnp.ones((16 * 16 * 3, 8)) * 1e-3
    step(w, jnp.zeros((4, 16, 16, 3))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=xplane.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        ld = PrefetchLoader(map(prep, ({"images": im} for im in images)))
        for b in ld:
            w = step(w, b["images"])
        w.block_until_ready()
        ld.close()
    jax.profiler.stop_trace()

    tr = xplane.load(tmp_path, chips=1)
    path = next(tmp_path.glob("**/*.xplane.pb"))
    win = phases.Window(path, tr.lo, tr.hi, 1)
    assert win.is_run and win.has_phases
    names = {n for n, _, _ in win.spans}
    assert names >= {f"repro.data.{s}" for s in (
        "fetch", "mean_subtract", "crop_flip", "device_put", "wait")}
    assert all(tr.lo <= s < e <= tr.hi for _, s, e in win.spans)
    # six batches, and the pull that found the stream exhausted
    assert sum(n == "repro.data.fetch" for n, _, _ in win.spans) == 7
    assert win.span_ms("repro.data.crop_flip") > 0
    assert win.span_ms("repro.data.no_such_stage") is None
    assert win.phase_s.get("forward", 0) > 0
    assert win.phase_s.get("update", 0) > 0
    assert any(phases.LOSS in t for t in phases.hlo_texts(path).values())
    # each wait carries its batch's sequence number (the seventh found
    # the stream exhausted)
    from jax.profiler import ProfileData
    ids = sorted(dict(ev.stats)["batch"]
                 for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events
                 if ev.name == "repro.data.wait")
    assert ids == list(range(7))
