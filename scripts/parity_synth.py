"""Recipe-scale accuracy evidence on synthetic CIFAR (r4, VERDICT item 4a).

Real CIFAR-10 is unreachable offline, so this runs the FULL cifar10_quick
recipe — lr 0.001 fixed, momentum 0.9, weight decay 0.004, batch 100,
4000 iterations (reference `models/cifar10/cifar10_quick_solver.prototxt:
12-22`, `apps/CifarApp.scala:20,127`) — on the deterministic synthetic
CIFAR stand-in (`sparknet_tpu.data.synth`), twice:

  - 1 worker  (plain serial SGD — the reference's single-worker baseline)
  - 8 workers, tau=10 local-SGD parameter averaging (the paper's scheme;
    per-worker data partitions, random round windows per reference
    `apps/CifarApp.scala:131-133`, momentum worker-local)

and writes both accuracy curves to PARITY_SYNTH_r04.json. The claim this
artifact supports: the tau-averaging dynamics CONVERGE at recipe scale —
the 8-worker curve tracks the serial curve to comparable final accuracy —
on a 4000-iteration run, not just the 30-round CI gates.

The round math here is the ParallelTrainer's (`_round_impl`: scan of
SgdSolver.update steps, then worker-mean of params, momentum NOT averaged)
with the worker axis vmapped instead of shard_mapped, so the whole study
fits one real chip with the corpus resident in HBM;
`tests/test_parity.py::test_parity_synth_round_matches_trainer` pins the
vmapped round against ParallelTrainer.train_round on the CPU mesh.

Run: python scripts/parity_synth.py [--iters 4000] [--out PARITY_SYNTH_r04.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from sparknet_tpu import CompiledNet
from sparknet_tpu.parallel.mesh import scan_unroll
from sparknet_tpu.data import synth
from sparknet_tpu.solver import SgdSolver, SolverConfig, SolverState
from sparknet_tpu.zoo import cifar10_quick

BATCH = 100
TAU = 10
N_TRAIN = 50_000
N_TEST = 10_000
EVAL_EVERY = 50  # rounds (= 500 iters; reference logged every 5 rounds)


def build(batch: int = BATCH):
    net = CompiledNet.compile(cifar10_quick(batch=batch))
    cfg = SolverConfig(base_lr=0.001, momentum=0.9, weight_decay=0.004,
                       lr_policy="fixed")
    return net, SgdSolver(net, cfg)


def make_round_fn(net, solver, n_workers: int, tau: int, batch: int):
    """One jitted round: each worker runs tau SGD steps on its indexed
    batches (gathered from the device-resident corpus), then params are
    worker-averaged (momentum worker-local) — ParallelTrainer._round_impl
    with the worker axis vmapped."""
    loss_fn = net.loss_fn("loss")

    def one_worker(params, momentum, it, idx, corpus, labels):
        def step(carry, ix):
            p, m, i = carry
            b = {"data": jnp.take(corpus, ix, axis=0),
                 "label": jnp.take(labels, ix, axis=0)}
            (loss, _), grads = jax.value_and_grad(
                lambda q: loss_fn(q, b, jax.random.PRNGKey(0)),
                has_aux=True)(p)
            p, st = solver.update(p, SolverState(momentum=m, it=i), grads)
            return (p, st.momentum, st.it), loss
        (params, momentum, it), losses = jax.lax.scan(
            step, (params, momentum, it), idx, unroll=scan_unroll(tau))
        return params, momentum, it, losses

    @jax.jit
    def round_fn(params, momentum, it, idx, corpus, labels):
        # params/momentum: [W, ...] stacked; idx: [W, tau, batch] int32
        params, momentum, it_w, losses = jax.vmap(
            one_worker, in_axes=(0, 0, None, 0, None, None)
        )(params, momentum, it, idx, corpus, labels)
        params = jax.tree.map(lambda x: jnp.broadcast_to(
            jnp.mean(x, axis=0, keepdims=True), x.shape), params)
        return params, momentum, it_w[0], jnp.mean(losses)

    return round_fn


def make_eval_fn(net, batch: int, n_test: int):
    n_batches = n_test // batch

    @jax.jit
    def eval_all(params, data, labels):
        # one dispatch for the whole test set (100 per-batch dispatches
        # would each pay the dispatch-and-fetch round trip)
        d = data[:n_batches * batch].reshape((n_batches, batch)
                                             + data.shape[1:])
        l = labels[:n_batches * batch].reshape(n_batches, batch, 1)

        def body(_, xy):
            blobs = net.apply(params, {"data": xy[0], "label": xy[1]},
                              train=False)
            return None, blobs["accuracy"]
        _, accs = jax.lax.scan(body, None, (d, l))
        return jnp.mean(accs)
    return eval_all


def run(n_workers: int, iters: int, seed: int = 0):
    net, solver = build()
    rounds = iters // TAU
    t0 = time.time()

    print(f"[{n_workers}w] generating synthetic corpus...", file=sys.stderr)
    train_x, train_y = synth.synthetic_cifar(N_TRAIN, seed=seed)
    test_x, test_y = synth.synthetic_cifar(N_TEST, seed=seed,
                                           start=N_TRAIN)
    mean = train_x.mean(axis=0)
    nhwc = lambda a: np.ascontiguousarray(
        (a - mean).transpose(0, 2, 3, 1)).astype(np.float32)
    corpus = jax.device_put(nhwc(train_x))
    labels = jax.device_put(train_y[:, None])
    test_corpus = jax.device_put(nhwc(test_x))
    test_labels = jax.device_put(test_y[:, None])
    print(f"[{n_workers}w] corpus on device "
          f"({time.time() - t0:.0f}s)", file=sys.stderr)

    params0 = net.init_params(jax.random.PRNGKey(seed))
    params = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_workers,) + x.shape), params0)
    momentum = jax.tree.map(jnp.zeros_like, params)
    it = jnp.zeros((), jnp.int32)

    round_fn = make_round_fn(net, solver, n_workers, TAU, BATCH)
    eval_fn = make_eval_fn(net, BATCH, N_TEST)

    # per-worker contiguous data partitions (reference repartition.cache);
    # each round draws a RANDOM WINDOW inside the partition
    # (CifarApp.scala:131-133)
    part = N_TRAIN // n_workers
    r = np.random.default_rng((seed, n_workers))

    def round_indices():
        idx = np.empty((n_workers, TAU, BATCH), np.int32)
        for w in range(n_workers):
            start = w * part + r.integers(0, part - TAU * BATCH + 1)
            idx[w] = np.arange(start, start + TAU * BATCH).reshape(TAU, BATCH)
        return idx

    def evaluate(params_w):
        p1 = jax.tree.map(lambda x: x[0], params_w)
        return float(eval_fn(p1, test_corpus, test_labels))

    curve = []
    for rnd in range(rounds):
        if rnd % EVAL_EVERY == 0:
            acc = evaluate(params)
            curve.append({"iter": rnd * TAU, "test_accuracy": round(acc, 4)})
            print(f"[{n_workers}w] iter {rnd * TAU}: acc {acc:.4f} "
                  f"({time.time() - t0:.0f}s)", file=sys.stderr)
        params, momentum, it, loss = round_fn(params, momentum, it,
                                              round_indices(), corpus,
                                              labels)
    final = evaluate(params)
    curve.append({"iter": rounds * TAU, "test_accuracy": round(final, 4)})
    print(f"[{n_workers}w] FINAL iter {rounds * TAU}: acc {final:.4f} "
          f"({time.time() - t0:.0f}s)", file=sys.stderr)
    return {"workers": n_workers, "tau": TAU if n_workers > 1 else 1,
            "final_test_accuracy": round(final, 4), "curve": curve,
            "wall_s": round(time.time() - t0, 1),
            "final_loss": float(loss)}


def main():
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--iters", type=int, default=4000)
    p.add_argument("--out", default="PARITY_SYNTH_r04.json")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    results = {
        "recipe": {"model": "cifar10_quick", "base_lr": 0.001,
                   "momentum": 0.9, "weight_decay": 0.004,
                   "lr_policy": "fixed", "batch": BATCH,
                   "max_iter": args.iters,
                   "source": "models/cifar10/cifar10_quick_solver.prototxt"},
        "dataset": {"kind": "synthetic_cifar (sparknet_tpu.data.synth)",
                    "n_train": N_TRAIN, "n_test": N_TEST,
                    "seed": args.seed},
        "platform": str(jax.devices()[0]),
        "runs": [run(1, args.iters, seed=args.seed),
                 run(8, args.iters, seed=args.seed)],
    }
    s, m = results["runs"]
    results["summary"] = {
        "serial_final": s["final_test_accuracy"],
        "avg8_tau10_final": m["final_test_accuracy"],
        "gap": round(s["final_test_accuracy"]
                     - m["final_test_accuracy"], 4),
    }
    from sparknet_tpu.obs import run_metadata
    results["meta"] = run_metadata()
    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps(results["summary"]))


if __name__ == "__main__":
    main()
