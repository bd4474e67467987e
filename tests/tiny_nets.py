"""The small nets several suites train, written down once: a suite imports
them from here, never from another suite's test module."""
import numpy as np

#: the distributed trainers' net (tests/test_parallel.py and the suites that
#: hold other trainers, meshes and loops to the same rounds)
TINY_MLP = """
name: "tiny_mlp"
input: "data"
input_shape { dim: 8 dim: 6 }
input: "label"
input_shape { dim: 8 dim: 1 }
layer { name: "ip1" type: "InnerProduct" bottom: "data" top: "ip1"
        inner_product_param { num_output: 16
          weight_filler { type: "gaussian" std: 0.3 } } }
layer { name: "relu1" type: "ReLU" bottom: "ip1" top: "ip1" }
layer { name: "ip2" type: "InnerProduct" bottom: "ip1" top: "ip2"
        inner_product_param { num_output: 4
          weight_filler { type: "gaussian" std: 0.3 } } }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip2" bottom: "label" top: "loss" }
layer { name: "acc" type: "Accuracy" bottom: "ip2" bottom: "label" top: "acc" }
"""

#: the rounds `TINY_MLP` trains on the 8-virtual-device mesh
N_DEV = 8
TAU = 3
LOCAL_B = 8


def make_round_batches(seed):
    r = np.random.default_rng(seed)
    data = r.standard_normal((TAU, N_DEV * LOCAL_B, 6)).astype(np.float32)
    label = (data.sum(-1, keepdims=True) > 0).astype(np.int32) + \
        (data[..., :1] > 0.5).astype(np.int32)
    return {"data": data, "label": label}


#: conv -> pool -> ip at CIFAR's layout, 16 x 16 (tests/test_net.py, the solver)
CIFARISH = """
name: "tiny_cifar"
input: "data"
input_shape { dim: 4 dim: 3 dim: 16 dim: 16 }
input: "label"
input_shape { dim: 4 dim: 1 }
layer {
  name: "conv1" type: "Convolution" bottom: "data" top: "conv1"
  param { lr_mult: 1 } param { lr_mult: 2 }
  convolution_param {
    num_output: 8 pad: 2 kernel_size: 5 stride: 1
    weight_filler { type: "gaussian" std: 0.01 }
    bias_filler { type: "constant" }
  }
}
layer { name: "pool1" type: "Pooling" bottom: "conv1" top: "pool1"
        pooling_param { pool: MAX kernel_size: 3 stride: 2 } }
layer { name: "relu1" type: "ReLU" bottom: "pool1" top: "pool1" }
layer { name: "ip1" type: "InnerProduct" bottom: "pool1" top: "ip1"
        inner_product_param { num_output: 10
          weight_filler { type: "gaussian" std: 0.1 } } }
layer { name: "prob" type: "Softmax" bottom: "ip1" top: "prob" }
layer { name: "loss" type: "SoftmaxWithLoss" bottom: "ip1" bottom: "label" top: "loss" }
layer { name: "acc" type: "Accuracy" bottom: "ip1" bottom: "label" top: "acc" }
"""

#: the reference's adult.prototxt (tests/test_prototxt.py, the net's apps)
ADULT = """
name: "adult"
input: "C0"
input_shape { dim: 64 dim: 1 }
layer {
  name: "ip"
  type: "InnerProduct"
  bottom: "C0"
  top: "ip"
  param { lr_mult: 1 }
  param { lr_mult: 2 }
  inner_product_param {
    num_output: 10
    weight_filler { type: "xavier" }
    bias_filler { type: "constant" }
  }
}
layer { name: "prob" type: "Softmax" bottom: "ip" top: "prob" }
"""
