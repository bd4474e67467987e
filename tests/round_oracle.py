"""The plain reference of one τ-averaging round, for the tests of the round.

What a round IS, written down once and serially: every worker starts from
the same weights and its own momentum, takes τ `value_and_grad` +
`SgdSolver.update` steps on its own rows and keys (a worker with a budget
`tau_by_worker[w]` stops there; the schedule clock still advances by τ),
then the weights are averaged over the workers and the momentum is not. The
round's loss is each worker's mean over the steps it ran, averaged over the
workers; the health scalars come out of the same loop.

A test holds ONE trainer configuration (a trainer class, one arm of a
switch) to this, so that deleting the other arm of the switch costs that
arm's cases and no reference. It knows a trainer by its public surface only
(`averaged_params`, the state's leading worker axis on the momentum,
`last_health`) and imports nothing of `sparknet_tpu.parallel`: the scan,
the peeled step, the boundary and the masking of `_round_math` are what it
checks, not what it is made of.
"""
import dataclasses

import numpy as np

import jax
import jax.numpy as jnp

from sparknet_tpu.solver import SgdSolver, SolverState


@dataclasses.dataclass
class Round:
    """A round's outputs: logical weights, one momentum tree a worker, the
    schedule clock, the loss and the health scalars."""
    params: dict
    momenta: list
    it: int
    loss: float
    grad_norm: float
    nonfinite_by_worker: np.ndarray


_STEPS: dict = {}  # (id(net), solver_cfg) -> (net, its jitted step)


def _step_fn(net, solver_cfg):
    """One worker's one step, jitted once a (net, solver): loss, gradients,
    the Caffe SGD update, and the step's squared gradient norm."""
    key = (id(net), solver_cfg)
    if key not in _STEPS:
        _STEPS[key] = (net, _make_step(net, solver_cfg))
    return _STEPS[key][1]


def _make_step(net, solver_cfg):
    solver = SgdSolver(net, solver_cfg)
    loss_fn = net.loss_fn()

    @jax.jit
    def step(params, sstate, batch, key):
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, key), has_aux=True)(params)
        grad_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                      for g in jax.tree.leaves(grads))
        params, sstate = solver.update(params, sstate, grads)
        return params, sstate, loss, grad_sq

    return step


def _finite(tree) -> bool:
    return all(np.all(np.isfinite(np.asarray(x, np.float32)))
               for x in jax.tree.leaves(tree))


def serial_round(net, solver_cfg, params, momenta, it, batches, rng, tau,
                 tau_by_worker=None) -> Round:
    """One round from (`params`, one momentum tree a worker in `momenta`,
    clock `it`) over the host stack `batches` = {name: [tau, workers *
    local_batch, ...]} under the round key `rng`."""
    step = _step_fn(net, solver_cfg)
    n = len(momenta)
    rows = next(iter(batches.values())).shape[1]
    assert rows % n == 0, (rows, n)
    b = rows // n
    worker_keys = jax.random.split(rng, n)
    ends, worker_losses, worst_sq, bad = [], [], [], []
    for w in range(n):
        p = params
        s = SolverState(momentum=momenta[w], it=jnp.asarray(it, jnp.int32))
        budget = tau if tau_by_worker is None else int(tau_by_worker[w])
        step_keys = jax.random.split(worker_keys[w], tau)
        losses, grad_sqs = [], []
        for t in range(budget):
            rows_t = {k: jnp.asarray(v[t, w * b:(w + 1) * b])
                      for k, v in batches.items()}
            p, s, loss, grad_sq = step(p, s, rows_t, step_keys[t])
            losses.append(float(loss))
            grad_sqs.append(float(grad_sq))
        ends.append((p, s.momentum))
        worker_losses.append(sum(losses) / max(budget, 1))
        worst_sq.append(max(grad_sqs, default=0.0))
        bad.append(not (np.all(np.isfinite(losses)) and _finite(p)
                        and _finite(s.momentum)))
    return Round(
        params=jax.tree.map(lambda *xs: sum(xs) / n, *[p for p, _ in ends]),
        momenta=[m for _, m in ends], it=it + tau,
        loss=float(np.mean(worker_losses)),
        grad_norm=float(np.sqrt(np.sum(worst_sq))),
        nonfinite_by_worker=np.asarray(bad, np.float32))


def split_state(trainer, state):
    """(logical weights, one momentum tree a worker, clock) of a trainer's
    state, in either state layout: the momentum's leading axis is the
    worker's in both, the weights come through `averaged_params`."""
    params = jax.tree.map(np.asarray, trainer.averaged_params(state))
    momenta = [jax.tree.map(lambda x: np.asarray(x)[w], state.momentum)
               for w in range(trainer.n_data)]
    return params, momenta, int(np.asarray(state.it).reshape(-1)[0])


def assert_round_matches(trainer, start, after, loss, batches, rng,
                         tau_by_worker=None, rtol=2e-5, atol=1e-6):
    """`trainer.train_round` took the state that `split_state` read as
    `start` (read it BEFORE the round: the round donates its state) to
    (`after`, `loss`): hold weights, every worker's momentum, the clock,
    the loss and (where the trainer computes them) the health scalars to
    the serial round from the same start."""
    want = serial_round(trainer.net, trainer.solver.cfg, *start, batches,
                        rng, trainer.tau, tau_by_worker)
    params, momenta, it = split_state(trainer, after)

    def close(got, ref, what):
        for (path, g), (_, r) in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree_util.tree_leaves_with_path(ref)):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(r), rtol=rtol, atol=atol,
                err_msg=f"{what}{jax.tree_util.keystr(path)}")

    close(params, want.params, "params")
    for w, (got, ref) in enumerate(zip(momenta, want.momenta)):
        close(got, ref, f"momentum[worker {w}]")
    assert it == want.it, (it, want.it)
    np.testing.assert_allclose(float(loss), want.loss, rtol=rtol, atol=atol)
    health = trainer.last_health
    if health is not None:
        np.testing.assert_allclose(float(health["grad_norm"]),
                                   want.grad_norm, rtol=1e-4)
        np.testing.assert_array_equal(
            np.asarray(health["nonfinite_by_worker"]),
            want.nonfinite_by_worker)
        assert float(health["nonfinite"]) == float(
            want.nonfinite_by_worker.sum())
