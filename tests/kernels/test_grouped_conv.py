"""Grouped convolution fidelity: the paper's dual-GPU intra-layer split
(conv2/4/5 of AlexNet) as native ``groups`` in every conv backend.

Parity: fused implicit-GEMM == block-diag im2col ref == lax.conv with
``feature_group_count``, forward AND VJP, on the real AlexNet grouped
geometries.  Structure: a jaxpr walk proves the fused path still never
materializes the (B*OH*OW, K*K*Cin) patch tensor, and that NOTHING inside
the Pallas kernel is sized by the full channel counts — each tile reads
only its group's input slice (the no-cross-group-reads acceptance)."""
import jax
import jax.extend.core as jex_core
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.alexnet import FAITHFUL
from repro.kernels import common
from repro.kernels.conv2d import ops, ref


def _make(b, hw, cin, cout, kernel, groups, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, hw, hw, cin))
    w = jax.random.normal(ks[1], (kernel, kernel, cin // groups, cout)) * 0.1
    b_ = jax.random.normal(ks[2], (cout,)) * 0.1
    return x, w, b_


# the faithful net's grouped layers: real channel geometry, spatial dims
# reduced so interpret mode stays fast
GROUPED_ALEXNET = [
    pytest.param(cs.kernel, cs.stride, cs.padding, cin, cs.out_channels,
                 cs.groups, hw, id=f"conv{i + 1}")
    for i, (cs, cin, hw) in enumerate(zip(
        FAITHFUL.convs,
        [FAITHFUL.in_channels] + [c.out_channels
                                  for c in FAITHFUL.convs[:-1]],
        [27, 8, 6, 6, 6]))
    if cs.groups > 1
]


@pytest.mark.parametrize("kernel,stride,pad,cin,cout,groups,hw",
                         GROUPED_ALEXNET)
@pytest.mark.parametrize("impl", ["fused", "im2col_ref"])
def test_grouped_parity_alexnet_layers(kernel, stride, pad, cin, cout,
                                       groups, hw, impl):
    x, w, b = _make(2, hw, cin, cout, kernel, groups)
    fn = ops.conv2d_fused if impl == "fused" else ops.conv2d_im2col
    out = fn(x, w, stride=stride, padding=pad, bias=b, groups=groups)
    exp = ref.conv2d_ref(x, w, stride, pad, groups=groups) + b
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hw,cin,cout,kernel,stride,pad,groups", [
    (13, 8, 12, 3, 1, 1, 2),
    (9, 6, 9, 3, 1, 1, 3),        # non-pow2 per-group channels
    (11, 16, 16, 5, 2, 2, 4),
    (8, 4, 8, 1, 1, 0, 2),        # 1x1 grouped
    (14, 12, 10, 3, 2, 1, 2),     # cout/g not a lane multiple
])
@pytest.mark.parametrize("impl", ["fused", "im2col_ref"])
def test_grouped_parity_sweep(hw, cin, cout, kernel, stride, pad, groups,
                              impl):
    x, w, _ = _make(2, hw, cin, cout, kernel, groups, seed=1)
    fn = ops.conv2d_fused if impl == "fused" else ops.conv2d_im2col
    out = fn(x, w, stride=stride, padding=pad, groups=groups)
    exp = ref.conv2d_ref(x, w, stride, pad, groups=groups)
    np.testing.assert_allclose(out, exp, rtol=2e-4, atol=2e-4)


def test_grouped_fused_gradients_match_xla():
    """custom_vjp with groups: dx, dw, db through a ReLU epilogue == the
    lax.conv grouped oracle's autodiff."""
    x, w, b = _make(2, 10, 8, 12, 3, 2, seed=2)

    def f_fused(x, w, b):
        return jnp.sum(jnp.sin(ops.conv2d_fused(
            x, w, stride=1, padding=1, bias=b, relu=True, groups=2)))

    def f_xla(x, w, b):
        return jnp.sum(jnp.sin(jnp.maximum(
            ref.conv2d_ref(x, w, 1, 1, groups=2) + b, 0.0)))

    got = jax.grad(f_fused, argnums=(0, 1, 2))(x, w, b)
    exp = jax.grad(f_xla, argnums=(0, 1, 2))(x, w, b)
    for g, e in zip(got, exp):
        np.testing.assert_allclose(g, e, rtol=2e-4, atol=1e-5)


def test_grouped_registered_in_kernel_registry():
    """'conv2d_grouped' rides the KernelOp registry — the registry-driven
    parity loop in test_grad_parity covers it on every run."""
    assert "conv2d_grouped" in common.ops()
    op = common.get_op("conv2d_grouped")
    assert op.differentiable


def test_grouped_rejects_bad_divisibility():
    x = jnp.ones((1, 8, 8, 6))
    with pytest.raises(ValueError):
        ops.conv2d_fused(x, jnp.ones((3, 3, 2, 8)), stride=1, padding=1,
                         groups=2)            # 2*2 != cin 6
    with pytest.raises(ValueError):
        ops.conv2d_fused(x, jnp.ones((3, 3, 3, 9)), stride=1, padding=1,
                         groups=2)            # cout 9 % 2 != 0


# ------------------------------------------------------- structure proofs ----

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


def _pallas_inner_shapes(jaxpr, out):
    """Shapes that exist INSIDE pallas_call kernels only."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            inner = eqn.params["jaxpr"]
            _collect_shapes(getattr(inner, "jaxpr", inner), out)
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                if isinstance(sub, jex_core.ClosedJaxpr):
                    _pallas_inner_shapes(sub.jaxpr, out)
                elif isinstance(sub, jex_core.Jaxpr):
                    _pallas_inner_shapes(sub, out)
    return out


def test_grouped_fused_never_materializes_im2col():
    """groups > 1 must not fall back to a patch tensor: no intermediate
    with B*OH*OW*K*K*Cin elements anywhere in the fwd jaxpr (the grouped
    im2col ref provably has one — detector sanity)."""
    b_, hw, cin, cout, kernel, groups = 2, 11, 14, 20, 3, 2
    x = jnp.ones((b_, hw, hw, cin))
    w = jnp.ones((kernel, kernel, cin // groups, cout))
    oh = hw  # stride 1, pad 1, k 3
    patch_elems = b_ * oh * oh * kernel * kernel * cin

    fused = jax.make_jaxpr(lambda x, w: ops.conv2d_fused(
        x, w, stride=1, padding=1, groups=groups))(x, w)
    sizes = {int(np.prod(s)) for s in _collect_shapes(fused.jaxpr, set())}
    assert patch_elems not in sizes, \
        "grouped fused path materializes an im2col-sized tensor"

    ref_path = jax.make_jaxpr(lambda x, w: ops.conv2d_im2col(
        x, w, stride=1, padding=1, groups=groups))(x, w)
    ref_sizes = {int(np.prod(s)) for s in _collect_shapes(ref_path.jaxpr,
                                                          set())}
    assert patch_elems in ref_sizes, "detector failed to see ref's patches"


def test_grouped_kernel_never_reads_across_groups():
    """Inside the Pallas kernel no array carries the FULL channel counts:
    every ref is sized by Cin/G and (padded) Cout/G — the block-index
    maps hand each output tile exactly its own group's input slice, so a
    cross-group read is structurally impossible."""
    b_, hw, cin, cout, kernel, groups = 2, 10, 14, 20, 3, 2
    x = jnp.ones((b_, hw, hw, cin))
    w = jnp.ones((kernel, kernel, cin // groups, cout))
    jaxpr = jax.make_jaxpr(lambda x, w: ops.conv2d_fused(
        x, w, stride=1, padding=1, groups=groups, bm=32, bn=16))(x, w)
    inner = _pallas_inner_shapes(jaxpr.jaxpr, set())
    assert inner, "no pallas_call found in the fused path"
    offenders = [s for s in inner if cin in s or cout in s]
    assert not offenders, \
        f"kernel-internal arrays sized by full channels: {offenders}"
