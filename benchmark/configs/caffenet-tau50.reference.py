"""Plain reference for the `caffenet-tau50` configuration.

BVLC reference CaffeNet (models/bvlc_reference_caffenet train_val.prototxt +
solver.prototxt) written out in straightforward `jax.numpy`: float32, matmul
precision `highest`, no kernels, no cache, one batch at a time. It imports
nothing of the program and takes nothing the program made: the benchmark
makes the weights (`init_params`) and the rows, and hands both sides the same.

Departures from the published description, each because the comparison needs
the two sides to see the same random draw:
  * dropout masks are drawn from the key derivation the program documents
    (`split(round_key, workers)[w]` -> `split(., tau)[t]` ->
    `fold_in(., crc32(layer name))` -> `bernoulli(keep)`); a mask is input,
    like a row. A PR that changes that derivation changes what `correct`
    compares and needs a benchmark PR beside it.
  * weights are stored HWIO / (in, out) with the fc6 input flattened in
    Caffe's C,H,W order, so that the program takes them as they are.

`precision` other than "float32" is the CONTROL (see `LIMITS` below): the
same mathematics with both operands of every convolution and inner product
and the cotangent of its output (so: both operands of all three matmuls)
rounded to int8 or fp8, per tensor and symmetric, which is the step below the
configuration's bfloat16.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# (name, kind, args) in execution order -- train_val.prototxt, TRAIN phase
LAYERS = (
    ("conv1", "conv", dict(cout=96, k=11, stride=4, pad=0, group=1, std=0.01, bias=0.0)),
    ("relu1", "relu", {}),
    ("pool1", "pool", dict(k=3, stride=2)),
    ("norm1", "lrn", dict(size=5, alpha=1e-4, beta=0.75)),
    ("conv2", "conv", dict(cout=256, k=5, stride=1, pad=2, group=2, std=0.01, bias=1.0)),
    ("relu2", "relu", {}),
    ("pool2", "pool", dict(k=3, stride=2)),
    ("norm2", "lrn", dict(size=5, alpha=1e-4, beta=0.75)),
    ("conv3", "conv", dict(cout=384, k=3, stride=1, pad=1, group=1, std=0.01, bias=0.0)),
    ("relu3", "relu", {}),
    ("conv4", "conv", dict(cout=384, k=3, stride=1, pad=1, group=2, std=0.01, bias=1.0)),
    ("relu4", "relu", {}),
    ("conv5", "conv", dict(cout=256, k=3, stride=1, pad=1, group=2, std=0.01, bias=1.0)),
    ("relu5", "relu", {}),
    ("pool5", "pool", dict(k=3, stride=2)),
    ("fc6", "fc", dict(cout=4096, std=0.005, bias=1.0)),
    ("relu6", "relu", {}),
    ("drop6", "dropout", dict(ratio=0.5)),
    ("fc7", "fc", dict(cout=4096, std=0.005, bias=1.0)),
    ("relu7", "relu", {}),
    ("drop7", "dropout", dict(ratio=0.5)),
    ("fc8", "fc", dict(cout=None, std=0.01, bias=0.0)),  # cout = n_classes
)

#: What `correct` holds a cell of this configuration to: the check round
#: (round 0 at the configuration's `check_lr_scale`) against `round_reference`.
#: Each limit stands with the v5e readings it was set from (PR 24; PERF.md
#: section 2 repeats them): "sound" is the largest value the program gave over
#: 30 readings on 22 seeds (one chip) and 18 on 10 seeds (four chips),
#: "control" the smallest the fp8 control gave over 3 + 3 seeds.
LIMITS = {
    # ||m_prog - m_ref|| / ||m_ref|| over PROBE_LEAF's momentum, worst worker:
    # the number the lower-precision control fails. Sound 0.00253-0.00270,
    # control 0.0329-0.0348 (int8: 1.09-1.12): the limit is 3x the one and a
    # quarter of the other.
    "probe_diff": 0.008,
    # worst leaf of | ||m_prog|| - ||m_ref|| | / max(||m_ref||, median leaf)
    # over the momentum after the round, the gradient as the optimizer got it.
    # Lower precision moves it little (control 0.015-0.045), so it is held
    # against the faults it is there to catch (a worker's rows or the exchange
    # left out). Sound at most 0.0222.
    "momentum_gap": 0.06,
    # the same over the parameters' change across the round; held against a
    # round that returns its state unchanged (gap 1.0). Sound at most 0.0121.
    "update_gap": 0.04,
    # |program round loss - reference round loss|; held against part of the
    # batch left out. Sound at most 6.1e-5.
    "loss_gap": 2e-4,
}
#: the step below the configuration's bfloat16 (int8, read too, fails by far)
CONTROL_PRECISION = "fp8"
#: the leaf whose momentum is compared element by element: the classifier's
#: weights, whose gradient is the last activations times (softmax - one-hot),
#: so it carries the forward pass's rounding and none of the chaos that
#: fifty steps grow in the early layers
PROBE_LEAF = ("fc8", "w")


def _pool_out(size: int, k: int, stride: int) -> int:
    return -(-(size - k) // stride) + 1  # Caffe rounds up


def param_shapes(crop: int, n_classes: int) -> dict:
    """{layer: {"w": shape, "b": shape}} and nothing else."""
    shapes, h, c = {}, crop, 3
    for name, kind, a in LAYERS:
        if kind == "conv":
            shapes[name] = {"w": (a["k"], a["k"], c // a["group"], a["cout"]),
                            "b": (a["cout"],)}
            h = (h + 2 * a["pad"] - a["k"]) // a["stride"] + 1
            c = a["cout"]
        elif kind == "pool":
            h = _pool_out(h, a["k"], a["stride"])
        elif kind == "fc":
            cout = a["cout"] or n_classes
            fan_in = c * h * h if h else c
            shapes[name] = {"w": (fan_in, cout), "b": (cout,)}
            h, c = 0, cout
    return shapes


def init_params(seed: int, crop: int, n_classes: int) -> dict:
    """The benchmark's weights: one jitted call on the device, from the seed.
    Gaussian weight fillers and constant bias fillers as train_val.prototxt
    gives them."""
    shapes = param_shapes(crop, n_classes)
    fill = {name: a for name, kind, a in LAYERS if kind in ("conv", "fc")}

    @jax.jit
    def make(key):
        out = {}
        for i, (name, sh) in enumerate(shapes.items()):
            out[name] = {
                "w": fill[name]["std"] * jax.random.normal(
                    jax.random.fold_in(key, i), sh["w"], jnp.float32),
                "b": jnp.full(sh["b"], fill[name]["bias"], jnp.float32)}
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31) + 17))


# -- the lower-precision control ------------------------------------------

def _quantize(x, precision: str):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    if precision == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if precision == "fp8":
        s = amax / 448.0
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_to(x, precision: str):
    """An operand of a forward matmul, rounded; its gradient passes through."""
    return x if precision == "float32" else _quantize(x, precision)


_round_to.defvjp(lambda x, precision: (_round_to(x, precision), None),
                 lambda precision, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round_grad(y, precision: str):
    """A matmul's output: untouched forward, its cotangent (an operand of
    both backward matmuls) rounded on the way back."""
    return y


_round_grad.defvjp(
    lambda y, precision: (y, None),
    lambda precision, _, g: (g if precision == "float32"
                             else _quantize(g, precision),))


# -- forward, loss ---------------------------------------------------------

def _lrn(x, size, alpha, beta):
    half = size // 2
    sq = jnp.pad(x * x, ((0, 0), (0, 0), (0, 0), (half, half)))
    c = x.shape[-1]
    s = sum(sq[..., i:i + c] for i in range(size))
    return x / jnp.power(1.0 + (alpha / size) * s, beta)


def _max_pool(x, k, stride):
    h = x.shape[1]
    end = (_pool_out(h, k, stride) - 1) * stride + k - h
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, k, k, 1), (1, stride, stride, 1),
        ((0, 0), (0, max(end, 0)), (0, max(end, 0)), (0, 0)))


def loss(params, data, label, step_key, precision: str = "float32"):
    """Mean softmax cross-entropy of one batch, TRAIN phase."""
    x = data.astype(jnp.float32)
    for name, kind, a in LAYERS:
        if kind == "conv":
            x = lax.conv_general_dilated(
                _round_to(x, precision), _round_to(params[name]["w"], precision),
                (a["stride"],) * 2, ((a["pad"],) * 2,) * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                feature_group_count=a["group"])
            x = _round_grad(x, precision) + params[name]["b"]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        elif kind == "pool":
            x = _max_pool(x, a["k"], a["stride"])
        elif kind == "lrn":
            x = _lrn(x, a["size"], a["alpha"], a["beta"])
        elif kind == "fc":
            if x.ndim == 4:  # Caffe flattens C,H,W
                x = jnp.transpose(x, (0, 3, 1, 2)).reshape(x.shape[0], -1)
            x = _round_to(x, precision) @ _round_to(params[name]["w"], precision)
            x = _round_grad(x, precision) + params[name]["b"]
        elif kind == "dropout":
            keep = 1.0 - a["ratio"]
            mask = jax.random.bernoulli(
                jax.random.fold_in(step_key, zlib.crc32(name.encode())),
                keep, x.shape)
            x = jnp.where(mask, x / keep, 0.0)
    logp = jax.nn.log_softmax(x, axis=-1)
    return -jnp.mean(jnp.take_along_axis(
        logp, label.reshape(-1, 1).astype(jnp.int32), axis=-1))


# -- Caffe SGD -------------------------------------------------------------

def learning_rate(solver: dict, it):
    if solver["lr_policy"] == "step":
        return solver["base_lr"] * jnp.power(
            solver["gamma"], jnp.floor(it / solver["stepsize"]))
    if solver["lr_policy"] == "fixed":
        return jnp.asarray(solver["base_lr"], jnp.float32)
    raise ValueError(f"lr_policy {solver['lr_policy']!r} is not in this reference")


@functools.partial(jax.jit, static_argnames=("solver_items", "precision"),
                   donate_argnums=(0, 1))
def sgd_step(params, momentum, it, data, label, step_key, *, solver_items,
             precision="float32"):
    """V <- mu V + lr lr_mult (g + wd decay_mult W); W <- W - V, with the
    prototxt's lr_mult 1/2 and decay_mult 1/0 for weights/biases."""
    solver = dict(solver_items)
    with jax.default_matmul_precision("highest"):
        value, grads = jax.value_and_grad(loss)(params, data, label, step_key,
                                                precision)
    rate = learning_rate(solver, it.astype(jnp.float32))
    new_p, new_m = {}, {}
    for name, lp in params.items():
        new_p[name], new_m[name] = {}, {}
        for pn, w in lp.items():
            lr_mult, decay = (1.0, solver["weight_decay"]) if pn == "w" else (2.0, 0.0)
            v = (solver["momentum"] * momentum[name][pn]
                 + rate * lr_mult * (grads[name][pn] + decay * w))
            new_p[name][pn], new_m[name][pn] = w - v, v
    return new_p, new_m, it + 1, value


def step_keys(round_key, n_workers: int, worker: int, tau: int):
    """The per-step dropout keys of one worker's round (see module note)."""
    return jax.random.split(jax.random.split(round_key, n_workers)[worker], tau)


def worker_round(params, rows, round_key, *, tau, solver, n_workers=1,
                 worker=0, precision="float32", device=None):
    """One worker's tau local steps from `params` with zero momentum.
    `rows(t)` gives step t's (data, label) for this worker. Dispatches
    without waiting, so several workers' rounds run side by side, one on
    each device. Returns (params, momentum, [tau losses]) on `device`."""
    put = functools.partial(jax.device_put, device=device)
    p = put(jax.tree.map(jnp.array, params))
    m = jax.tree.map(jnp.zeros_like, p)
    it = put(jnp.zeros((), jnp.int32))
    keys = put(step_keys(round_key, n_workers, worker, tau))
    losses = []
    solver_items = tuple(sorted(solver.items()))
    for t in range(tau):
        data, label = rows(t)
        p, m, it, value = sgd_step(p, m, it, put(data), put(label), keys[t],
                                   solver_items=solver_items,
                                   precision=precision)
        losses.append(value)
    return p, m, losses


def round_reference(params0, rows, round_key, *, tau, solver, n_workers=1,
                    precision="float32", devices=None):
    """What one round of this configuration should produce: per-leaf norms of
    the momentum (per worker) and of the parameters' change, and the loss.
    `rows(t, w)` gives worker w's rows of step t. One worker, so the
    boundary average is the identity; the avg4 reference calls this with
    four."""
    devices = devices or jax.devices()[:n_workers]
    outs = [worker_round(params0, lambda t, w=w: rows(t, w),
                         round_key, tau=tau, solver=solver, n_workers=n_workers,
                         worker=w, precision=precision,
                         device=devices[w % len(devices)])
            for w in range(n_workers)]
    host = [jax.tree.map(np.asarray, (p, m)) for p, m, _ in outs]
    mean_p = jax.tree.map(lambda *xs: np.mean(np.stack(xs), axis=0, dtype=np.float64),
                          *[h[0] for h in host])
    p0 = jax.tree.map(np.asarray, params0)
    return {
        "loss": float(np.mean([[float(v) for v in ls] for _, _, ls in outs])),
        "update_norms": _norms(jax.tree.map(lambda a, b: a - b, mean_p, p0)),
        "momentum_norms": [_norms(h[1]) for h in host],
        "probe": [h[1][PROBE_LEAF[0]][PROBE_LEAF[1]] for h in host],
    }


def _norms(tree) -> dict:
    return {f"{ln}/{pn}": float(np.linalg.norm(np.asarray(x, np.float64).ravel()))
            for ln, lp in tree.items() for pn, x in lp.items()}
