"""Trace reduction (``bench/xplane.py``): on events built by hand, and on
a small trace recorded here on the CPU."""
import pytest

import bench_smoke  # noqa: F401  (puts bench/ and src/ on sys.path)
import xplane
from xplane import Op, Trace


def _op(name, s, e, kind="other"):
    return Op(name, float(s), float(e), kind, f"{name} {kind}")


def test_interval_algebra():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]
    assert xplane.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert xplane.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert xplane.clip([(0, 5), (8, 12)], 2, 10) == [(2, 5), (8, 10)]


def _two_chips():
    # window [100, 200) ns; chip 0: compute 100-140, all-reduce 130-170
    # (30 ns of it with nothing else running), idle 170-200.
    # chip 1: compute 100-180, an async all-reduce in flight 150-190
    # (10 ns exposed).
    chip0 = [_op("fusion.1", 100, 140),
             _op("all-reduce.1", 130, 170, "collective"),
             _op("fusion.0", 50, 90)]                 # before the window
    chip1 = [_op("fusion.1", 100, 180),
             _op("all-reduce-start.1", 150, 190, "collective_async")]
    spans = [("bench.window", 100, 200), ("bench.dispatch", 100, 110),
             ("bench.block", 110, 200)]
    return Trace([chip0, chip1], spans)


def test_busy_idle_and_exposed_collectives():
    tr = _two_chips()
    assert tr.window_s() == pytest.approx(100e-9)
    # chip 0 busy 100-170 (70); chip 1 busy 100-180 (80): an async
    # transfer in flight does not occupy the chip
    assert tr.busy_s() == pytest.approx(75e-9)
    assert tr.op_s(xplane.is_collective) == pytest.approx(40e-9)
    assert tr.exposed_s(xplane.is_collective) == pytest.approx(20e-9)
    assert tr.count(xplane.is_collective) == 1


def test_breakdown_names_ops_and_idle_gaps():
    bd = _two_chips().breakdown()
    names = dict(bd["device_ops"])
    assert names["fusion.1 other"] == pytest.approx(60e-9)
    assert names["all-reduce.1 collective"] == pytest.approx(20e-9)
    assert bd["idle_gaps"] == [["bench.block", pytest.approx(30e-9)]]


HLO = """\
HloModule jit_step

%fused_computation.1 (param_0: f32[4,6,6,8], param_1: f32[3,3,8,16]) -> f32[4,6,6,16] {
  %param_0 = f32[4,6,6,8]{3,2,1,0} parameter(0)
  %param_1 = f32[3,3,8,16]{3,2,1,0} parameter(1)
  ROOT %convolution.3 = f32[4,6,6,16]{3,2,1,0} convolution(%param_0, %param_1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_01io->b01f, metadata={op_name="jit(step)/transpose(jvp(transpose()))/conv_general_dilated"}
}

%fused_computation.2 (param_0.1: f32[4,16], param_1.1: f32[16,10]) -> (f32[4,10], f32[4,10]) {
  %param_0.1 = f32[4,16]{1,0} parameter(0)
  %param_1.1 = f32[16,10]{1,0} parameter(1)
  %convolution.4 = f32[4,10]{1,0} convolution(%param_0.1, %param_1.1), dim_labels=bf_io->bf, metadata={op_name="jit(step)/transpose(jvp())/dot_general"}
  ROOT %tuple.1 = (f32[4,10]{1,0}, f32[4,10]{1,0}) tuple(%convolution.4, %convolution.4)
}

ENTRY %main.9 (p0: f32[4,6,6,8], p1: f32[3,3,8,16], p2: f32[4,16], p3: f32[16,10]) -> f32[4,6,6,16] {
  %p0 = f32[4,6,6,8]{3,2,1,0} parameter(0)
  %p1 = f32[3,3,8,16]{3,2,1,0} parameter(1)
  %p2 = f32[4,16]{1,0} parameter(2)
  %p3 = f32[16,10]{1,0} parameter(3)
  %jvp_jit__conv_fused_impl__.2 = f32[4,6,6,16]{3,2,1,0} custom-call(%p0, %p1), custom_call_target="tpu_custom_call"
  %fusion.7 = f32[4,6,6,16]{3,2,1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1
  %fusion.8 = (f32[4,10]{1,0}, f32[4,10]{1,0}) fusion(%p2, %p3), kind=kOutput, calls=%fused_computation.2
  %all-reduce.5 = f32[4,6,6,16]{3,2,1,0} all-reduce(%fusion.7), replica_groups={}, to_apply=%add
  ROOT %add.1 = f32[4,6,6,16]{3,2,1,0} add(%all-reduce.5, %jvp_jit__conv_fused_impl__.2)
}
"""


def test_op_kinds_from_the_steps_hlo():
    kinds = xplane.hlo_kinds(HLO)
    assert kinds["jvp_jit__conv_fused_impl__.2"] == "conv_fwd"
    assert kinds["fusion.7"] == "conv_grad"
    assert kinds["fusion.8"] == "other"       # an FC matmul's gradient
    assert kinds["all-reduce.5"] == "collective"
    op = xplane._op("%fusion.7 = f32[4,6,6,16]{3,2,1,0} fusion(%p0, %p1)",
                    0, 1, kinds)
    assert xplane.is_conv_grad(op) and op.label == \
        "fusion.7 conv_grad f32[4,6,6,16]"
    assert xplane.is_collective(xplane._op("all-reduce-done.2", 0, 1, {}))


def test_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path),
                             profiler_options=xplane.profile_options())
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("bench.block"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    tr = xplane.load(tmp_path, chips=1)
    assert [n for n, _, _ in tr.spans].count("bench.dispatch") == 3
    assert 0 < tr.busy_s() <= tr.window_s()
    assert tr.breakdown()["device_ops"]
