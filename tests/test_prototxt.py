"""Prototxt importer tests (tiny fixtures inline; reference files only read
if the read-only mount is present)."""
import os

import pytest

from sparknet_tpu.model.prototxt import (
    net_from_prototxt,
    net_from_prototxt_file,
    parse_message,
    solver_from_prototxt,
)
from tiny_nets import ADULT

SOLVER = """
# a comment
net: "whatever.prototxt"
base_lr: 0.001
momentum: 0.9
weight_decay: 0.004
lr_policy: "fixed"
max_iter: 4000
"""


def test_parse_message_generic():
    msg = parse_message('a: 1 b { c: "x" c: "y" } a: 2')
    assert msg["a"] == [1, 2]
    assert msg["b"][0]["c"] == ["x", "y"]


def test_adult_net():
    spec = net_from_prototxt(ADULT)
    assert spec.name == "adult"
    assert [i.name for i in spec.inputs] == ["C0"]
    assert spec.inputs[0].shape == (64, 1)
    ip = spec.layer_by_name("ip")
    assert ip.inner_product.num_output == 10
    assert ip.inner_product.weight_filler.type == "xavier"
    assert ip.params[0].lr_mult == 1 and ip.params[1].lr_mult == 2
    assert spec.layers[-1].type == "Softmax"


def test_solver_parse():
    cfg = solver_from_prototxt(SOLVER)
    assert cfg["base_lr"] == 0.001
    assert cfg["momentum"] == 0.9
    assert cfg["weight_decay"] == 0.004
    assert cfg["lr_policy"] == "fixed"
    assert cfg["max_iter"] == 4000


REFERENCE_CIFAR = "/root/reference/models/cifar10/cifar10_quick_train_test.prototxt"


@pytest.mark.skipif(not os.path.exists(REFERENCE_CIFAR),
                    reason="reference mount absent")
def test_reference_cifar10_prototxt():
    spec = net_from_prototxt_file(REFERENCE_CIFAR)
    assert spec.name == "CIFAR10_quick"
    types = [l.type for l in spec.layers]
    assert types.count("Convolution") == 3
    assert types.count("Pooling") == 3
    assert types.count("InnerProduct") == 2
    conv1 = spec.layer_by_name("conv1")
    assert conv1.conv.num_output == 32
    assert conv1.conv.pad == 2 and conv1.conv.kernel_size == 5
    assert conv1.conv.weight_filler.type == "gaussian"
    assert conv1.conv.weight_filler.std == 0.0001
    pool1 = spec.layer_by_name("pool1")
    assert pool1.pool.pool == "MAX" and pool1.pool.kernel_size == 3


REFERENCE_ALEXNET = "/root/reference/models/bvlc_reference_caffenet/train_val.prototxt"


@pytest.mark.skipif(not os.path.exists(REFERENCE_ALEXNET),
                    reason="reference mount absent")
def test_reference_caffenet_prototxt():
    spec = net_from_prototxt_file(
        REFERENCE_ALEXNET,
        input_shapes={"data": (256, 3, 227, 227), "label": (256, 1)})
    types = [l.type for l in spec.layers]
    assert types.count("Convolution") == 5
    assert types.count("LRN") == 2
    assert types.count("Dropout") == 2
    conv2 = spec.layer_by_name("conv2")
    assert conv2.conv.group == 2
    norm1 = spec.layer_by_name("norm1")
    assert norm1.lrn.local_size == 5 and norm1.lrn.alpha == 0.0001


def test_unimplemented_geometry_fields_rejected():
    """Recognized-but-unimplemented Caffe fields must fail loudly, not
    import a structurally different net with defaults."""
    import pytest
    from sparknet_tpu.model.prototxt import net_from_prototxt
    base = """
    name: "g"
    input: "data"
    input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
    layer {
      name: "c" type: "Convolution" bottom: "data" top: "c"
      convolution_param { num_output: 4 %s }
    }
    """
    for bad in ("kernel_h: 3 kernel_w: 5", "stride_h: 2", "pad_w: 1",
                "dilation: 2"):
        with pytest.raises(ValueError, match="not implemented|dilation"):
            net_from_prototxt(base % bad)
    # square geometry still imports
    net_from_prototxt(base % "kernel_size: 3 pad: 1")

    pool_bad = """
    name: "g"
    input: "data"
    input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
    layer {
      name: "p" type: "Pooling" bottom: "data" top: "p"
      pooling_param { pool: MAX kernel_h: 2 }
    }
    """
    with pytest.raises(ValueError, match="not implemented"):
        net_from_prototxt(pool_bad)

    concat_bad = """
    name: "g"
    input: "a"
    input_shape { dim: 1 dim: 4 }
    input: "b"
    input_shape { dim: 1 dim: 4 }
    layer {
      name: "cat" type: "Concat" bottom: "a" bottom: "b" top: "cat"
      concat_param { axis: 2 }
    }
    """
    with pytest.raises(ValueError, match="Concat axis"):
        net_from_prototxt(concat_bad)


def test_square_h_w_geometry_accepted():
    """kernel_h==kernel_w (etc.) is the SAME square geometry as kernel_size
    and must import, not be rejected (r2 review finding); conflicting
    base-vs-h/w values still fail."""
    from sparknet_tpu.model.prototxt import net_from_prototxt
    base = """
    name: "g"
    input: "data"
    input_shape { dim: 1 dim: 3 dim: 8 dim: 8 }
    layer {
      name: "c" type: "Convolution" bottom: "data" top: "c"
      convolution_param { num_output: 4 %s }
    }
    """
    spec = net_from_prototxt(base % "kernel_h: 3 kernel_w: 3 pad_h: 1 pad_w: 1")
    conv = [l for l in spec.layers if l.name == "c"][0]
    assert conv.conv.kernel_size == 3 and conv.conv.pad == 1
    import pytest
    with pytest.raises(ValueError, match="conflicting"):
        net_from_prototxt(base % "kernel_size: 5 kernel_h: 3 kernel_w: 3")
