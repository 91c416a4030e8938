"""Conv backend parity: fused implicit-GEMM vs two-stage im2col ref vs
lax.conv oracle, plus gradient parity, the no-HBM-im2col proof, and the
aligned-matmul no-pad guarantee."""
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alexnet import CONFIG as ALEXNET_FULL
from repro.kernels.conv2d import ops, ref
from repro.kernels.conv2d.conv2d import matmul_bias


@pytest.mark.parametrize("m,k,n,bm,bk,bn", [
    (64, 64, 64, 32, 32, 32),
    (100, 70, 50, 32, 32, 32),      # non-multiples: padding path
    (256, 128, 256, 128, 128, 128),
])
@pytest.mark.parametrize("relu", [False, True])
def test_matmul_bias(m, k, n, bm, bk, bn, relu):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (m, k))
    w = jax.random.normal(ks[1], (k, n)) * 0.1
    b = jax.random.normal(ks[2], (n,))
    out = matmul_bias(x, w, b, bm=bm, bk=bk, bn=bn, relu=relu)
    exp = ref.matmul_bias_ref(x, w, b, relu=relu)
    np.testing.assert_allclose(out, exp, rtol=1e-5, atol=1e-5)


def test_matmul_bias_autoblocks():
    """bm/bk/bn=None resolve through the tune cache (non-128 M/K/N)."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (150, 93))
    w = jax.random.normal(ks[1], (93, 37)) * 0.1
    b = jax.random.normal(ks[2], (37,))
    out = matmul_bias(x, w, b)
    np.testing.assert_allclose(out, ref.matmul_bias_ref(x, w, b),
                               rtol=1e-5, atol=1e-5)


def _collect_shapes(jaxpr, out):
    for eqn in jaxpr.eqns:
        for v in list(eqn.invars) + list(eqn.outvars):
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                out.add(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _collect_shapes(sub.jaxpr, out)
                elif isinstance(sub, jex_core.Jaxpr):
                    _collect_shapes(sub, out)
    return out


def _primitives(jaxpr, out):
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _primitives(sub.jaxpr, out)
                elif isinstance(sub, jex_core.Jaxpr):
                    _primitives(sub, out)
    return out


def test_matmul_bias_aligned_skips_padding():
    """Block-multiple operands must not pay the jnp.pad HBM copies."""
    x = jnp.ones((128, 64))
    w = jnp.ones((64, 128))
    b = jnp.ones((128,))
    aligned = jax.make_jaxpr(
        lambda x, w, b: matmul_bias(x, w, b, bm=128, bk=64, bn=128))(x, w, b)
    assert "pad" not in _primitives(aligned.jaxpr, set())
    misaligned = jax.make_jaxpr(
        lambda x, w, b: matmul_bias(x, w, b, bm=128, bk=128, bn=128))(
            jnp.ones((100, 70)), jnp.ones((70, 50)), jnp.ones((50,)))
    assert "pad" in _primitives(misaligned.jaxpr, set())


# the five full-AlexNet conv layers: real kernel/stride/padding/channel
# geometry, spatial dims reduced so interpret mode stays fast
ALEXNET_SHAPES = [
    pytest.param(cs.kernel, cs.stride, cs.padding, cin, cs.out_channels,
                 hw, id=f"conv{i + 1}")
    for i, (cs, cin, hw) in enumerate(zip(
        ALEXNET_FULL.convs,
        [ALEXNET_FULL.in_channels] + [c.out_channels
                                      for c in ALEXNET_FULL.convs[:-1]],
        [27, 8, 6, 6, 6]))
]


@pytest.mark.parametrize("kernel,stride,pad,cin,cout,hw", ALEXNET_SHAPES)
def test_conv_backend_parity_alexnet_shapes(kernel, stride, pad, cin, cout,
                                            hw):
    """fused == im2col_ref == lax.conv on every AlexNet layer geometry."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(ks[0], (2, hw, hw, cin))
    w = jax.random.normal(ks[1], (kernel, kernel, cin, cout)) * 0.05
    b = jax.random.normal(ks[2], (cout,)) * 0.1
    exp = np.asarray(ref.conv2d_ref(x, w, stride, pad) + b)
    fused = np.asarray(ops.conv2d_fused(x, w, stride=stride, padding=pad,
                                        bias=b))
    ref2 = np.asarray(ops.conv2d_im2col(x, w, stride=stride, padding=pad,
                                        bias=b))
    np.testing.assert_allclose(fused, exp, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ref2, exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("hw,cin,cout,kernel,stride,pad", [
    (33, 5, 7, 5, 2, 2),
    (27, 3, 16, 11, 4, 0),   # AlexNet conv1 shape family
    (16, 8, 8, 3, 1, 1),
    (14, 4, 6, 1, 1, 0),     # 1x1 conv
    (19, 3, 9, 3, 3, 2),     # odd stride + asymmetric-ish padding
    (21, 6, 5, 5, 3, 1),     # odd stride, non-pow2 channels
])
@pytest.mark.parametrize("impl", ["fused", "im2col_ref"])
def test_conv2d_parity_sweep(hw, cin, cout, kernel, stride, pad, impl):
    ks = jax.random.split(jax.random.PRNGKey(1), 2)
    x = jax.random.normal(ks[0], (2, hw, hw, cin))
    w = jax.random.normal(ks[1], (kernel, kernel, cin, cout)) * 0.1
    fn = ops.conv2d_fused if impl == "fused" else ops.conv2d_im2col
    out = fn(x, w, stride=stride, padding=pad)
    exp = ref.conv2d_ref(x, w, stride, pad)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["fused", "im2col_ref"])
def test_conv2d_bias_relu_fused(impl):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    x = jax.random.normal(ks[0], (1, 12, 12, 4))
    w = jax.random.normal(ks[1], (3, 3, 4, 8)) * 0.2
    b = jax.random.normal(ks[2], (8,))
    fn = ops.conv2d_fused if impl == "fused" else ops.conv2d_im2col
    out = fn(x, w, stride=1, padding=1, bias=b, relu=True)
    exp = jnp.maximum(ref.conv2d_ref(x, w, 1, 1) + b, 0.0)
    np.testing.assert_allclose(out, exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["fused", "im2col_ref"])
def test_bf16(impl):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = jax.random.normal(ks[0], (1, 16, 16, 4), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (3, 3, 4, 8)) * 0.1).astype(jnp.bfloat16)
    fn = ops.conv2d_fused if impl == "fused" else ops.conv2d_im2col
    out = fn(x, w, stride=1, padding=1)
    exp = ref.conv2d_ref(x, w, 1, 1)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_conv2d_fused_gradients_match_xla():
    """custom_vjp of the fused kernel == autodiff of the lax.conv oracle,
    for dx, dw AND db, through a ReLU epilogue."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(ks[0], (2, 12, 12, 3))
    w = jax.random.normal(ks[1], (3, 3, 3, 8)) * 0.1
    b = jax.random.normal(ks[2], (8,)) * 0.1

    def f_fused(x, w, b):
        return jnp.sum(jnp.sin(ops.conv2d_fused(
            x, w, stride=2, padding=1, bias=b, relu=True)))

    def f_xla(x, w, b):
        return jnp.sum(jnp.sin(jnp.maximum(
            ref.conv2d_ref(x, w, 2, 1) + b, 0.0)))

    got = jax.grad(f_fused, argnums=(0, 1, 2))(x, w, b)
    exp = jax.grad(f_xla, argnums=(0, 1, 2))(x, w, b)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-5)


def test_matmul_bias_gradients_match_ref():
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    x = jax.random.normal(ks[0], (37, 23))
    w = jax.random.normal(ks[1], (23, 19)) * 0.1
    b = jax.random.normal(ks[2], (19,))

    def f(mm):
        return lambda x, w, b: jnp.sum(jnp.cos(mm(x, w, b, relu=True)))

    got = jax.grad(f(matmul_bias), argnums=(0, 1, 2))(x, w, b)
    exp = jax.grad(f(ref.matmul_bias_ref), argnums=(0, 1, 2))(x, w, b)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(g, e, rtol=1e-4, atol=1e-5)


def test_fused_never_materializes_im2col():
    """The (B*OH*OW, K*K*C) patch tensor must not exist anywhere in the
    fused path's jaxpr — not even under the pallas_call — while the
    two-stage ref path provably does materialize it (detector sanity)."""
    b_, hw, cin, cout, kernel, stride, pad = 2, 11, 7, 11, 3, 2, 1
    x = jnp.ones((b_, hw, hw, cin))
    w = jnp.ones((kernel, kernel, cin, cout))
    oh = (hw + 2 * pad - kernel) // stride + 1
    patch_elems = b_ * oh * oh * kernel * kernel * cin

    fused = jax.make_jaxpr(lambda x, w: ops.conv2d_fused(
        x, w, stride=stride, padding=pad))(x, w)
    fused_sizes = {int(np.prod(s)) for s in _collect_shapes(fused.jaxpr,
                                                            set())}
    assert patch_elems not in fused_sizes, (
        "fused path materializes an im2col-sized tensor")

    ref_path = jax.make_jaxpr(lambda x, w: ops.conv2d_im2col(
        x, w, stride=stride, padding=pad))(x, w)
    ref_sizes = {int(np.prod(s)) for s in _collect_shapes(ref_path.jaxpr,
                                                          set())}
    assert patch_elems in ref_sizes, "detector failed to see ref's patches"


def test_weight_reorder_cached():
    w = jnp.arange(2 * 2 * 3 * 4, dtype=jnp.float32).reshape(2, 2, 3, 4)
    a = ops.reorder_weights(w)
    b = ops.reorder_weights(w)
    assert a is b                       # memoised for the same buffer
    np.testing.assert_array_equal(
        a, w.transpose(2, 0, 1, 3).reshape(12, 4))
    w2 = w + 1
    c = ops.reorder_weights(w2)
    assert c is not a


def test_alexnet_conv_layer_all_backends():
    """models.alexnet.conv2d dispatch: all three backends agree (incl.
    the fused relu epilogue)."""
    from repro.models import alexnet
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (2, 14, 14, 3))
    w = jax.random.normal(ks[1], (5, 5, 3, 8)) * 0.1
    b = jax.random.normal(ks[2], (8,))
    outs = {be: np.asarray(alexnet.conv2d(x, w, b, 2, 2, be, relu=True))
            for be in ("xla", "pallas", "pallas_im2col_ref")}
    np.testing.assert_allclose(outs["pallas"], outs["xla"],
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(outs["pallas_im2col_ref"], outs["xla"],
                               rtol=1e-4, atol=1e-4)
