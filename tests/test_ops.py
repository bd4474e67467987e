"""Caffe-semantics op tests, cross-checked against torch (CPU) oracles.

torch's ceil_mode pooling, grouped conv2d, and local_response_norm implement
the same semantics as native Caffe (which the reference called through
JavaCPP, `libs/CaffeNet.scala:91`), so they serve as an independent oracle.
"""
import numpy as np
import pytest
import torch
import torch.nn.functional as F

import jax
import jax.numpy as jnp

from sparknet_tpu.ops.lrn import _lrn_fused, _lrn_xla
from sparknet_tpu.ops.pooling import caffe_pool_output_size, pool2d


def nchw(x_nhwc):
    return np.transpose(x_nhwc, (0, 3, 1, 2))


def nhwc(x_nchw):
    return np.transpose(x_nchw, (0, 2, 3, 1))


@pytest.mark.parametrize("h,k,s,p", [
    (32, 3, 2, 0),   # cifar10 pool1-3: 32->16 via ceil
    (16, 3, 2, 0),
    (55, 3, 2, 0),   # alexnet pool1: 55->27
    (13, 3, 2, 0),   # alexnet pool5: 13->6
    (10, 2, 2, 0),
    (7, 3, 2, 1),
])
def test_pool_output_size_matches_torch(h, k, s, p):
    x = torch.zeros(1, 1, h, h)
    out = F.max_pool2d(x, k, stride=s, padding=p, ceil_mode=True)
    assert caffe_pool_output_size(h, k, s, p) == out.shape[-1]


@pytest.mark.parametrize("mode", ["MAX", "AVE"])
@pytest.mark.parametrize("h,k,s,p", [(32, 3, 2, 0), (13, 3, 2, 0), (8, 3, 2, 1)])
def test_pool2d_matches_torch(rng, mode, h, k, s, p):
    x = rng.standard_normal((2, h, h, 5), dtype=np.float32)
    got = np.asarray(pool2d(jnp.asarray(x), mode, k, s, p))
    xt = torch.from_numpy(nchw(x))
    if mode == "MAX":
        want = F.max_pool2d(xt, k, stride=s, padding=p, ceil_mode=True)
    else:
        want = F.avg_pool2d(xt, k, stride=s, padding=p, ceil_mode=True,
                            count_include_pad=True)
    np.testing.assert_allclose(got, nhwc(want.numpy()), rtol=1e-5, atol=1e-5)


#: the two portable LRN forms by name (`ops.lrn.lrn` picks between the
#: Pallas kernel and the fused form by backend; the kernel's own parity
#: tests are tests/test_pallas_lrn.py)
LRN_IMPLS = {
    "fused": _lrn_fused,
    "window": lambda x, n, alpha, beta, k: _lrn_xla(x, n, alpha=alpha,
                                                    beta=beta, k=k),
}


@pytest.mark.parametrize("impl", ["fused", "window"])
def test_lrn_matches_torch(rng, impl):
    x = rng.standard_normal((2, 7, 7, 16), dtype=np.float32)
    got = np.asarray(LRN_IMPLS[impl](jnp.asarray(x), 5, 1e-4, 0.75, 1.0))
    want = F.local_response_norm(torch.from_numpy(nchw(x)), size=5,
                                 alpha=1e-4, beta=0.75, k=1.0)
    np.testing.assert_allclose(got, nhwc(want.numpy()), rtol=1e-5, atol=1e-6)


def test_lrn_fused_gradient_matches_autodiff_of_window(rng):
    """The fused impl's closed-form Caffe backward (recomputed normalizer)
    vs autodiff of the reduce_window reference — must agree."""
    x = rng.standard_normal((3, 4, 4, 32), dtype=np.float32)
    dy = rng.standard_normal((3, 4, 4, 32), dtype=np.float32)

    def f(impl):
        return lambda x_: jnp.vdot(
            LRN_IMPLS[impl](x_, 5, 2e-4, 0.75, 1.0), jnp.asarray(dy))

    g_want = np.asarray(jax.grad(f("window"))(jnp.asarray(x)))
    g_got = np.asarray(jax.grad(f("fused"))(jnp.asarray(x)))
    np.testing.assert_allclose(g_got, g_want, rtol=1e-4, atol=1e-6)


def test_grouped_conv_matches_torch(rng):
    # AlexNet conv2 shape: group=2 (models/bvlc_reference_caffenet)
    x = rng.standard_normal((2, 9, 9, 8), dtype=np.float32)
    w_hwio = rng.standard_normal((3, 3, 4, 6), dtype=np.float32)  # group=2
    b = rng.standard_normal((6,), dtype=np.float32)
    y = jax.lax.conv_general_dilated(
        jnp.asarray(x), jnp.asarray(w_hwio), (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=2,
        precision=jax.lax.Precision.HIGHEST)
    got = np.asarray(y + b)
    w_oihw = np.transpose(w_hwio, (3, 2, 0, 1))
    want = F.conv2d(torch.from_numpy(nchw(x)), torch.from_numpy(w_oihw),
                    torch.from_numpy(b), stride=1, padding=1, groups=2)
    np.testing.assert_allclose(got, nhwc(want.numpy()), rtol=1e-4, atol=1e-4)


# NOTE: an argmax "k*k shift" maxpool formulation (fwd = max tree of
# strided views, bwd = argmax-routed scatter-adds, replacing XLA's
# select-and-scatter) was implemented and benchmarked at ~0.64x the
# reduce_window path's end-to-end throughput on v5e — the strided slices
# and scatters lower worse than select-and-scatter. Kept: the tie-routing
# semantics test below, which the reduce_window gradient must also satisfy.


def test_maxpool_tie_gradient_goes_to_first_max():
    """Caffe MaxPoolBackward routes the gradient to the FIRST max in
    row-major window order when values tie (select-and-scatter picks the
    same element)."""
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.ops.pooling import pool2d
    x = np.zeros((1, 2, 2, 1), np.float32)  # one 2x2 window, all tied
    g = jax.grad(lambda v: pool2d(v, "MAX", 2, 2, 0).sum())(jnp.asarray(x))
    np.testing.assert_array_equal(
        np.asarray(g)[0, :, :, 0], [[1.0, 0.0], [0.0, 0.0]])
