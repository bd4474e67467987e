"""Pallas maxpool-backward kernel vs oracles (interpreter mode on the CPU
mesh; the real-TPU path was A/B'd on the chip — see PERF.md §pool-backward
for why `auto` dispatch deliberately does NOT select it).

The load-bearing property is TIE ROUTING: Caffe's MaxPoolingLayer and
XLA's select-and-scatter both send each window's gradient to the FIRST
maximum in row-major window order, and ties are common on real data
(post-ReLU zeros). Tests use heavily quantized inputs so nearly every
window has ties."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from sparknet_tpu.ops import pallas_pool as pp
from sparknet_tpu.ops.pooling import pool2d


def _tie_heavy(rng, shape, levels=4):
    return np.maximum(
        rng.integers(-2, levels, shape), 0).astype(np.float32)


def _xla_bwd(x, dy, k, s):
    f = lambda a: lax.reduce_window(a, -jnp.inf, lax.max, (1, k, k, 1),
                                    (1, s, s, 1), ((0, 0),) * 4)
    return np.asarray(jax.vjp(f, jnp.asarray(x))[1](jnp.asarray(dy))[0])


@pytest.mark.parametrize("H,C,k,s", [(13, 8, 3, 2), (12, 8, 2, 2),
                                     (9, 16, 3, 1)])
def test_kernel_matches_oracle_and_xla(rng, H, C, k, s):
    N = 128
    x = _tie_heavy(rng, (N, H, H, C))
    OH = (H - k) // s + 1
    dy = rng.standard_normal((N, OH, OH, C)).astype(np.float32)
    assert pp.pallas_maxpool_supported(x.shape, x.dtype, k, s, 0)

    f = lambda a: pp.maxpool_pallas(a, k, s, True)  # interpret mode
    y, vjp = jax.vjp(f, jnp.asarray(x))
    (dx,) = vjp(jnp.asarray(dy))

    want_y = lax.reduce_window(jnp.asarray(x), -jnp.inf, lax.max,
                               (1, k, k, 1), (1, s, s, 1), ((0, 0),) * 4)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want_y))
    oracle = pp.maxpool_bwd_reference(x, dy, k, s)
    np.testing.assert_allclose(np.asarray(dx), oracle, atol=1e-5)
    np.testing.assert_allclose(_xla_bwd(x, dy, k, s), oracle, atol=1e-5)


def test_supported_gate():
    ok = pp.pallas_maxpool_supported
    assert ok((128, 13, 13, 8), np.float32, 3, 2, 0)
    assert not ok((100, 13, 13, 8), np.float32, 3, 2, 0)   # N % 128
    assert not ok((128, 13, 13, 5), np.float32, 3, 2, 0)   # C % sublanes
    assert not ok((128, 13, 13, 8), np.float32, 3, 2, 1)   # pad
    assert not ok((128, 32, 32, 8), np.float32, 3, 2, 0)   # ceil end-pad
    assert not ok((128, 2, 2, 8), np.float32, 3, 2, 0)     # tiny


def test_pool2d_impl_pallas_rejects_unsupported(rng):
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="impl='pallas' unsupported"):
        pool2d(x, "MAX", 3, 2, 0, impl="pallas")  # CPU backend + N%128


def test_pool2d_auto_consults_the_gate_and_degrades_to_xla():
    """r6 made `auto` a real dispatch: it consults the full gate
    (backend/shape) and takes the Pallas kernel where it passes —
    `RunConfig.pool_impl="xla"` is the explicit opt-out. This pins both
    halves: the gate IS consulted, and a False answer lands on the XLA
    lowering (never a crash). The r3 'auto stays on select-and-scatter'
    pin this replaces is now the per-deployment config decision, with the
    bench.py --mfu A/B rows as the standing evidence (PERF.md §r6)."""
    import sparknet_tpu.ops.pooling as pooling
    called = []
    orig = pooling._can_pallas_pool
    pooling._can_pallas_pool = lambda *a, **kw: called.append(a) or False
    try:
        x = jnp.zeros((128, 13, 13, 8), jnp.float32)
        y = pool2d(x, "MAX", 3, 2, 0)      # auto
        assert called                       # the gate decides now
        assert y.shape == (128, 6, 6, 8)    # gate said no -> XLA lowering
    finally:
        pooling._can_pallas_pool = orig
    # on this backend the real gate answers False (CPU without
    # interpret): auto == xla
    if not pooling._can_pallas_pool(x, 3, 2, 0):
        y_auto = pool2d(x, "MAX", 3, 2, 0)
        y_xla = pool2d(x, "MAX", 3, 2, 0, impl="xla")
        np.testing.assert_array_equal(np.asarray(y_auto), np.asarray(y_xla))


def test_pool2d_impl_xla_never_consults_the_gate():
    """impl='xla' is the documented wholesale opt-out: it must not consult
    the Pallas gate at all (the gate imports the Pallas toolchain — the
    explicit fallback has to work on a jax whose pallas import is
    broken)."""
    import sparknet_tpu.ops.pooling as pooling
    orig = pooling._can_pallas_pool

    def boom(*a, **kw):
        raise AssertionError("gate consulted under impl='xla'")

    pooling._can_pallas_pool = boom
    try:
        x = jnp.zeros((128, 13, 13, 8), jnp.float32)
        y = pool2d(x, "MAX", 3, 2, 0, impl="xla")
        assert y.shape == (128, 6, 6, 8)
    finally:
        pooling._can_pallas_pool = orig


def test_pool2d_auto_off_tpu_never_imports_the_toolchain(monkeypatch):
    """The DEFAULT impl='auto' off-TPU (no interpret) must be as
    import-free as 'xla': the gate's backend check runs before the
    pallas_pool import, so the default path also works on a jax whose
    pallas import is broken."""
    if jax.default_backend() == "tpu":
        pytest.skip("off-TPU contract")
    import builtins
    real_import = builtins.__import__

    def guarded(name, *a, **kw):
        if "pallas_pool" in name:
            raise AssertionError("pallas_pool imported under auto off-TPU")
        return real_import(name, *a, **kw)

    monkeypatch.setattr(builtins, "__import__", guarded)
    x = jnp.zeros((128, 13, 13, 8), jnp.float32)
    y = pool2d(x, "MAX", 3, 2, 0, impl="auto")
    assert y.shape == (128, 6, 6, 8)


def test_pool2d_impl_validation(rng):
    x = jnp.asarray(rng.standard_normal((4, 8, 8, 8)).astype(np.float32))
    with pytest.raises(ValueError, match="unknown pool impl"):
        pool2d(x, "MAX", 3, 2, 0, impl="palas")
    with pytest.raises(ValueError, match="MAX pooling only"):
        pool2d(x, "AVE", 3, 2, 0, impl="pallas")
