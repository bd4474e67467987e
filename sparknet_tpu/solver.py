"""Caffe-semantics SGD solver as a jitted functional update.

The reference's SGD lived entirely inside native Caffe
(`FloatSGDSolver.ApplyUpdate`, wrapped at reference `libs/CaffeSolver.scala:11-18`):
momentum, lr policy, per-blob lr_mult/decay_mult, weight decay, all configured
by `SolverParameter` prototxt. Here the same semantics are a pure function
over a pytree, so the whole train step (forward + backward + update) compiles
to one XLA executable and the optimizer state is first-class, checkpointable
data.

Caffe SGD update rule (SGDSolver<Dtype>::ComputeUpdateValue semantics):

    local_rate  = rate(iter) * lr_mult
    local_decay = weight_decay * decay_mult
    V <- momentum * V + local_rate * (grad + local_decay * W)
    W <- W - V

LR policies (Caffe `GetLearningRate`): fixed, step, exp, inv, multistep, poly,
sigmoid.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .model.net import CompiledNet, PyTree


@dataclass(frozen=True)
class SolverConfig:
    base_lr: float = 0.01
    lr_policy: str = "fixed"
    gamma: float = 0.1
    stepsize: int = 100000
    stepvalue: Tuple[int, ...] = ()
    power: float = 1.0
    max_iter: int = 10000
    momentum: float = 0.9
    weight_decay: float = 0.0
    iter_size: int = 1
    # Storage dtype for the velocity (momentum history). "float32" is
    # Caffe-exact. "bfloat16" is an OPT-IN speed knob: each step still
    # computes the update in f32 and applies the UNROUNDED velocity to the
    # weights — only the stored history is rounded — but it halves the
    # optimizer-state HBM stream that bounds the fc tail (PERF.md: fc6/7/8
    # wgrad+update fusions run at the memory roofline streaming f32 state).
    # Not the default because accuracy-parity (PARITY.md) is pinned to the
    # exact rule.
    velocity_dtype: str = "float32"

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "SolverConfig":
        solver_type = d.get("type", "SGD")
        if solver_type not in ("SGD",):
            raise ValueError(
                f"unsupported solver type {solver_type!r} (only SGD with "
                f"momentum is implemented — fail loudly rather than silently "
                f"training with different dynamics)")
        fields = {f.name for f in dataclasses.fields(SolverConfig)}
        kw = {k: v for k, v in d.items() if k in fields}
        if "stepvalue" in kw:
            kw["stepvalue"] = tuple(kw["stepvalue"])
        return SolverConfig(**kw)


def learning_rate(cfg: SolverConfig, it: jnp.ndarray) -> jnp.ndarray:
    """rate(iter) for every Caffe lr_policy; `it` may be traced."""
    it = it.astype(jnp.float32)
    p = cfg.lr_policy
    if p == "fixed":
        return jnp.asarray(cfg.base_lr, jnp.float32)
    if p == "step":
        current = jnp.floor(it / cfg.stepsize)
        return cfg.base_lr * jnp.power(cfg.gamma, current)
    if p == "exp":
        return cfg.base_lr * jnp.power(cfg.gamma, it)
    if p == "inv":
        return cfg.base_lr * jnp.power(1.0 + cfg.gamma * it, -cfg.power)
    if p == "multistep":
        if not cfg.stepvalue:
            return jnp.asarray(cfg.base_lr, jnp.float32)
        steps = jnp.asarray(cfg.stepvalue, jnp.float32)
        current = jnp.sum(it[None] >= steps)
        return cfg.base_lr * jnp.power(cfg.gamma, current.astype(jnp.float32))
    if p == "poly":
        return cfg.base_lr * jnp.power(1.0 - it / cfg.max_iter, cfg.power)
    if p == "sigmoid":
        return cfg.base_lr / (1.0 + jnp.exp(-cfg.gamma * (it - cfg.stepsize)))
    raise ValueError(f"unknown lr_policy {p!r}")


@jax.tree_util.register_dataclass
@dataclass
class SolverState:
    """Optimizer state pytree: momentum history + iteration counter.

    NOTE (parity): in the reference, momentum history is worker-local native
    state that never crosses the wire — only net blobs are averaged
    (`libs/CaffeNet.scala:123-137`). The distributed trainer preserves that:
    it averages `params`, never `SolverState.momentum`.
    """

    momentum: PyTree
    it: jnp.ndarray  # scalar int32 iteration counter


class SgdSolver:
    """Functional SGD solver bound to a CompiledNet.

    `step` is the analogue of the reference's `Solver.step(rowIt)`
    (`libs/CaffeSolver.scala:15-18`): forward + backward + ApplyUpdate, except
    compiled into a single XLA executable (donated args, so updates are
    in-place on device).
    """

    def __init__(self, net: CompiledNet, cfg: SolverConfig,
                 loss_blob: str = "loss"):
        if cfg.velocity_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                f"velocity_dtype {cfg.velocity_dtype!r}: expected 'float32' "
                f"(Caffe-exact) or 'bfloat16' (opt-in, see SolverConfig)")
        self.net = net
        self.cfg = cfg
        self.loss_blob = loss_blob
        self._lr_mults, self._decay_mults = _param_multipliers(net)
        self._step = jax.jit(self._step_impl, donate_argnums=(0, 1))

    # -- state --------------------------------------------------------------

    def init_state(self, params: PyTree) -> SolverState:
        vdt = jnp.dtype(self.cfg.velocity_dtype)
        zeros = jax.tree.map(lambda w: jnp.zeros(w.shape, vdt), params)
        return SolverState(momentum=zeros, it=jnp.zeros((), jnp.int32))

    # -- single-step update (pure) ------------------------------------------

    @jax.named_scope("solver_update")
    def update(self, params: PyTree, state: SolverState, grads: PyTree,
               lr_scale: Any = 1.0) -> Tuple[PyTree, SolverState]:
        """Apply one Caffe-SGD update given precomputed grads (pure fn).
        Traced under the scope `solver_update`: what a compiled step's
        report (obs.device.program_report) counts as the optimizer.

        `lr_scale` is a runtime (traceable) multiplier on the policy rate —
        the health supervisor's LR-backoff knob. It is an input, not a
        config field, so backing off after a rollback does NOT recompile
        the round (SolverConfig values are baked in at trace time)."""
        rate = learning_rate(self.cfg, state.it) * lr_scale

        def upd(path_key, w, v, g):
            lr_mult, decay_mult = path_key
            local_rate = rate * lr_mult
            local_decay = self.cfg.weight_decay * decay_mult
            # compute in the weight dtype (f32); only the STORED history is
            # in velocity_dtype — the weight sees the unrounded velocity
            v_new = (self.cfg.momentum * v.astype(w.dtype)
                     + local_rate * (g + local_decay * w))
            return w - v_new, v_new.astype(v.dtype)

        new_params: PyTree = {}
        new_mom: PyTree = {}
        for lname, lparams in params.items():
            new_params[lname], new_mom[lname] = {}, {}
            for pname, w in lparams.items():
                mults = self._lr_mults[lname][pname], self._decay_mults[lname][pname]
                nw, nv = upd(mults, w, state.momentum[lname][pname],
                             grads[lname][pname])
                new_params[lname][pname] = nw
                new_mom[lname][pname] = nv
        return new_params, SolverState(momentum=new_mom, it=state.it + 1)

    def _step_impl(self, params, state, batch, rng):
        k = self.cfg.iter_size
        if k == 1:
            (loss, blobs), grads = jax.value_and_grad(
                lambda p: self.net.loss_fn(self.loss_blob)(p, batch, rng),
                has_aux=True)(params)
        else:
            # Caffe iter_size semantics (SGDSolver::Step): accumulate grads
            # over iter_size micro-batches, normalize by 1/iter_size, ONE
            # ApplyUpdate, ONE iteration-counter bump. The incoming batch
            # carries iter_size × net-batch examples on the leading axis.
            micro = {kk: v.reshape((k, v.shape[0] // k) + v.shape[1:])
                     for kk, v in batch.items()}
            rngs = jax.random.split(rng, k)

            def accum(carry, xs):
                mb, sub = xs
                l, g = jax.value_and_grad(
                    lambda p: self.net.loss_fn(self.loss_blob)(
                        p, mb, sub)[0])(params)
                acc_l, acc_g = carry
                return (acc_l + l / k,
                        jax.tree.map(lambda a, b: a + b / k, acc_g, g)), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            from .parallel.mesh import scan_unroll
            (loss, grads), _ = jax.lax.scan(
                accum, (jnp.zeros((), jnp.float32), zeros), (micro, rngs),
                unroll=scan_unroll(k))
        new_params, new_state = self.update(params, state, grads)
        return new_params, new_state, loss

    # -- public API ---------------------------------------------------------

    def step(self, params: PyTree, state: SolverState,
             batch: Dict[str, jnp.ndarray], rng: Optional[jax.Array] = None
             ) -> Tuple[PyTree, SolverState, jnp.ndarray]:
        """One jitted train step (one UPDATE: with iter_size=k the batch
        must hold k x net-batch examples — k accumulation micro-batches).
        Returns (params, state, loss)."""
        if rng is None:
            rng = jax.random.fold_in(jax.random.PRNGKey(0), int(state.it))
        k = self.cfg.iter_size
        if k > 1:
            for kk, v in batch.items():
                if v.shape[0] % k:
                    raise ValueError(
                        f"{kk}: batch dim {v.shape[0]} not divisible by "
                        f"iter_size {k} (pass iter_size x net-batch "
                        f"examples per step)")
        return self._step(params, state, batch, rng)


def _param_multipliers(net: CompiledNet):
    """Per-blob lr_mult/decay_mult, by layer and by the layer's own
    parameter names.

    Caffe convention (reference prototxts, e.g.
    `models/cifar10/cifar10_quick_train_test.prototxt` `param { lr_mult: 1 }
    param { lr_mult: 2 }`): a layer's first ParamSpec is for its weight
    "w", the second for its bias "b". Any other parameter, and one the spec
    says nothing about, takes its name's default (`layers.param_defaults`:
    1.0 / 1.0, except that a norm's scale is not decayed and a router's
    selection bias is neither trained nor decayed).
    """
    from .model.layers import param_defaults
    names = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    positional = {"w": 0, "b": 1}
    lr: Dict[str, Dict[str, float]] = {}
    decay: Dict[str, Dict[str, float]] = {}
    for layer in net.spec.layers:
        if layer.name not in names:
            continue
        lr[layer.name], decay[layer.name] = {}, {}
        for pn in names[layer.name]:
            i = positional.get(pn, len(layer.params))
            spec = (layer.params[i] if i < len(layer.params)
                    else param_defaults(pn))
            lr[layer.name][pn] = spec.lr_mult
            decay[layer.name][pn] = spec.decay_mult
    return lr, decay
