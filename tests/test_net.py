"""CompiledNet / JaxNet tests — mirrors the reference's CaffeNetSpec
(`src/test/scala/libs/CaffeNetSpec.scala`): construction, forward output
schema/shapes, forward purity (weights unchanged), save->load roundtrip —
plus gradient checks the reference never had.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sparknet_tpu import CompiledNet, net_from_prototxt
from sparknet_tpu.model.caffe_compat import (collection_to_params,
                                             params_to_collection)
from sparknet_tpu.model.weights import WeightCollection
from sparknet_tpu.net_api import JaxNet
from sparknet_tpu.solver import SolverConfig
from tiny_nets import ADULT, CIFARISH


@pytest.fixture(scope="module")
def tiny_net():
    return CompiledNet.compile(net_from_prototxt(CIFARISH))


def test_shapes_and_outputs(tiny_net):
    assert tiny_net.input_shapes["data"] == (4, 16, 16, 3)
    assert tiny_net.blob_shapes["conv1"] == (4, 16, 16, 8)
    assert tiny_net.blob_shapes["pool1"] == (4, 8, 8, 8)
    assert tiny_net.blob_shapes["prob"] == (4, 10)
    assert set(tiny_net.output_names) == {"prob", "loss", "acc"}


def test_forward_probabilities_sum_to_one(tiny_net):
    params = tiny_net.init_params(jax.random.PRNGKey(0))
    blobs = tiny_net.apply(params, tiny_net.example_batch())
    probs = np.asarray(blobs["prob"])
    np.testing.assert_allclose(probs.sum(-1), 1.0, rtol=1e-5)
    assert (probs >= 0).all()


def test_forward_purity(tiny_net):
    """forward/forwardBackward must not mutate weights
    (CaffeNetSpec.scala:48-70)."""
    net = JaxNet(net_from_prototxt(CIFARISH), solver=SolverConfig(base_lr=0.1))
    before = net.get_weights()
    batch = {k: np.asarray(v) for k, v in net.net.example_batch().items()}
    net.forward(batch)
    net.forward_backward(batch)
    after = net.get_weights()
    assert WeightCollection.check_equal(before, after, tol=0.0)
    net.step(batch)
    stepped = net.get_weights()
    assert not WeightCollection.check_equal(before, stepped, tol=1e-9)


def test_weight_roundtrip(tiny_net, tmp_path):
    """save -> load roundtrip preserves weights exactly
    (CaffeNetSpec.scala:72-82)."""
    net = JaxNet(net_from_prototxt(CIFARISH), seed=3)
    path = str(tmp_path / "w.npz")
    net.save_weights(path)
    net2 = JaxNet(net_from_prototxt(CIFARISH), seed=7)
    assert not WeightCollection.check_equal(net.get_weights(),
                                            net2.get_weights())
    net2.load_weights(path)
    assert WeightCollection.check_equal(net.get_weights(), net2.get_weights(),
                                        tol=0.0)


def test_caffe_layout_roundtrip(tiny_net):
    params = tiny_net.init_params(jax.random.PRNGKey(1))
    coll = params_to_collection(tiny_net, params)
    # Caffe layouts: conv OIHW, ip (out, in)
    assert coll["conv1"][0].shape == (8, 3, 5, 5)
    assert coll["ip1"][0].shape == (10, 8 * 8 * 8)
    back = collection_to_params(tiny_net, coll)
    for lname, lp in params.items():
        for pname, w in lp.items():
            np.testing.assert_array_equal(np.asarray(w),
                                          np.asarray(back[lname][pname]))


def test_adult_net_forward():
    net = JaxNet(net_from_prototxt(ADULT))
    batch = {"C0": np.random.default_rng(0).standard_normal(
        (64, 1), dtype=np.float32)}
    out = net.forward(batch)
    assert out["prob"].shape == (64, 10)
    np.testing.assert_allclose(out["prob"].sum(-1), 1.0, rtol=1e-5)


def test_output_schema(tiny_net):
    net = JaxNet(net_from_prototxt(CIFARISH))
    schema = net.output_schema()
    assert schema["prob"].shape == (10,)
    assert schema["loss"].shape == ()


def test_gradients_flow(tiny_net):
    params = tiny_net.init_params(jax.random.PRNGKey(0))
    batch = tiny_net.example_batch()
    grads = jax.grad(lambda p: tiny_net.apply(p, batch, train=True,
                                              rng=jax.random.PRNGKey(1))["loss"]
                     )(params)
    norms = [float(jnp.linalg.norm(g)) for lp in grads.values()
             for g in lp.values()]
    assert all(np.isfinite(norms)) and sum(norms) > 0


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of the jaxprs it calls with them."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _traced(net):
    """The primitives and the recomputation policies in the gradient of a
    net's training loss: traced, never run."""
    params = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p, b: net.apply(
        p, b, train=True, rng=jax.random.PRNGKey(1))["loss"]))(
            params, net.example_batch())
    eqns = list(_eqns(jaxpr.jaxpr))
    return ({e.primitive.name for e in eqns},
            [e.params["policy"] for e in eqns if e.primitive.name == "remat2"])


def test_a_block_whose_layers_name_nothing_is_the_bare_checkpoint(tiny_net):
    """Recomputation keeps named values only where a layer of the block
    names some (`seq_layers.KEPT_NAMES`): conv1 and pool1 as one block get
    `jax.checkpoint` with no policy, and no value is named."""
    from sparknet_tpu.model.spec import LayerSpec
    spec = tiny_net.spec
    blocked = CompiledNet.compile(spec.replace(layers=tuple(
        LayerSpec(**{**l.__dict__, "block": "stem"})
        if l.name in ("conv1", "pool1") else l for l in spec.layers)))
    primitives, policies = _traced(blocked)
    assert policies and all(p is None for p in policies)
    assert "name" not in primitives
    assert blocked.kept_makers() == {}


@pytest.mark.parametrize("build", ["tiny", "caffenet"])
def test_a_net_without_blocks_is_not_checkpointed_at_all(tiny_net, build):
    """CaffeNet's spec (and every prototxt's) has no `block`: its training
    loss traces with no `jax.checkpoint`, no policy and no named value, as it
    did before a block kept anything."""
    from sparknet_tpu.zoo import caffenet
    net = tiny_net if build == "tiny" else CompiledNet.compile(
        caffenet(batch=2, crop=67, n_classes=16))
    assert {l.block for l in net.spec.layers} == {None}
    primitives, policies = _traced(net)
    assert policies == [] and not {"remat2", "name"} & primitives
    assert "conv_general_dilated" in primitives
    assert net.kept_makers() == {}


def test_hidden_blob_extraction(tiny_net):
    """FeaturizerApp parity: request a hidden blob by name
    (apps/FeaturizerApp.scala:91-94)."""
    net = JaxNet(net_from_prototxt(CIFARISH))
    batch = {k: np.asarray(v) for k, v in net.net.example_batch().items()}
    out = net.forward(batch, blob_names=["ip1"])
    assert out["ip1"].shape == (4, 10)


def test_space_to_depth_conv_exact(rng):
    """The stride-s space-to-depth conv rewrite (image-stem convs like
    CaffeNet conv1) computes the same contraction as the direct
    convolution — same products, channel-grouped summation order — so
    forward values and weight gradients agree to f32 accumulation noise,
    odd and even geometries."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from sparknet_tpu.model.layers import apply_convolution, ApplyCtx
    from sparknet_tpu.model.spec import ConvolutionParam, LayerSpec

    for h, k, s in [(227, 11, 4), (224, 7, 2), (65, 5, 3)]:
        layer = LayerSpec(name="c", type="Convolution", bottoms=("x",),
                          tops=("y",),
                          conv=ConvolutionParam(num_output=32, kernel_size=k,
                                                stride=s, pad=0))
        x = rng.standard_normal((2, h, h, 3)).astype(np.float32)
        w = (0.1 * rng.standard_normal((k, k, 3, 32))).astype(np.float32)

        def direct(w, x):
            return lax.conv_general_dilated(
                x, w, (s, s), "VALID",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                precision=lax.Precision.HIGHEST)

        def rewritten(w, x):
            (y,) = apply_convolution(layer, {"w": jnp.asarray(w)},
                                     (jnp.asarray(x),), ApplyCtx())
            return y

        y_d = direct(jnp.asarray(w), jnp.asarray(x))
        y_r = rewritten(w, x)
        assert y_r.shape == y_d.shape, (h, k, s)
        np.testing.assert_allclose(np.asarray(y_r), np.asarray(y_d),
                                   rtol=1e-4, atol=1e-4)
        g_d = jax.grad(lambda w: (direct(w, jnp.asarray(x)) ** 2).sum())(
            jnp.asarray(w))
        g_r = jax.grad(lambda w: (rewritten(w, x) ** 2).sum())(
            jnp.asarray(w))
        np.testing.assert_allclose(np.asarray(g_r), np.asarray(g_d),
                                   rtol=1e-4, atol=1e-2)


def test_space_to_depth_gate():
    """Padded / grouped / stride-1 / wide-channel convs keep the direct
    form."""
    from sparknet_tpu.model.layers import _s2d_eligible
    from sparknet_tpu.model.spec import ConvolutionParam
    ok = ConvolutionParam(num_output=96, kernel_size=11, stride=4, pad=0)
    assert _s2d_eligible(ok, 3)
    import dataclasses
    assert not _s2d_eligible(dataclasses.replace(ok, pad=1), 3)
    assert not _s2d_eligible(dataclasses.replace(ok, group=2), 3)
    assert not _s2d_eligible(dataclasses.replace(ok, stride=1), 3)
    assert not _s2d_eligible(ok, 64)  # 64*16 channels: already MXU-friendly
