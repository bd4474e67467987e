"""Elastic, preemption-tolerant pod training (parallel/elastic.py + the
train loop's resize path): membership declaration (stale -> full-jitter
re-probe -> evict, never a single missed beat; joiner adoption), the live
resize through the verified checkpoint store, min_workers
checkpoint-and-halt, per-worker τ masking, and the promoted
elastic-momentum A/B smoke."""
import json
import os
import random
import time

import numpy as np
import pytest

from sparknet_tpu import CompiledNet, net_from_prototxt
from sparknet_tpu.apps.train_loop import train
from sparknet_tpu.data.dataset import ArrayDataset
from sparknet_tpu.obs.pod import worker_heartbeat_path
from sparknet_tpu.parallel import ParallelTrainer, make_mesh
from sparknet_tpu.parallel.elastic import (ELASTIC_RELAUNCH_EXIT,
                                           ElasticRelaunch,
                                           MembershipController)
from sparknet_tpu.solver import SolverConfig
from sparknet_tpu.utils.config import ElasticConfig, RunConfig
from sparknet_tpu.utils.health import TrainingHealthError, liveness_classify
from sparknet_tpu.utils.heartbeat import HeartbeatWriter, read_heartbeat
from sparknet_tpu.utils.logger import Logger
from tiny_nets import TINY_MLP


# -- heartbeat age + the shared dead-vs-slow rule ----------------------------

def test_read_heartbeat_returns_age(tmp_path):
    p = str(tmp_path / "hb.json")
    HeartbeatWriter(p).beat(3, status="ok")
    hb = read_heartbeat(p)
    assert hb["age_s"] is not None and hb["age_s"] < 5.0
    # a backdated beat reads as old through the SAME field
    rec = json.load(open(p))
    rec["t"] = time.time() - 1000
    json.dump(rec, open(p, "w"))
    assert read_heartbeat(p)["age_s"] > 900


def test_liveness_classify_dead_vs_slow():
    assert liveness_classify(None, 60) == "missing"
    assert liveness_classify({"status": "ok"}, 60) == "missing"  # no t
    assert liveness_classify({"status": "ok", "age_s": 1.0}, 60) == "ok"
    assert liveness_classify({"status": "ok", "age_s": 90.0}, 60) == "stale"
    assert liveness_classify({"status": "done", "age_s": 1.0}, 60) == "done"
    for s in ("spike", "nonfinite", "rollback", "degraded"):
        assert liveness_classify({"status": s, "age_s": 1.0}, 60) == "sick"
    # SLOW is not a liveness verdict: a fresh beat with a huge round_s
    # is "ok" here — only the straggler attribution may flag it
    assert liveness_classify(
        {"status": "ok", "age_s": 1.0, "round_s": 100.0}, 60) == "ok"


# -- MembershipController ----------------------------------------------------

def _beat(pod_dir, worker, status="ok", age=0.0, **kv):
    p = worker_heartbeat_path(str(pod_dir), worker)
    HeartbeatWriter(p, interval_s=0.0).beat(0, status=status, force=True,
                                            **kv)
    if age:
        rec = json.load(open(p))
        rec["t"] = time.time() - age
        json.dump(rec, open(p, "w"))


def _controller(pod_dir, n=3, **cfg_kw):
    cfg_kw.setdefault("stale_after_s", 60.0)
    cfg_kw.setdefault("reprobe_backoff_s", 0.0)  # immediate re-probes
    cfg_kw.setdefault("dead_probes", 2)
    cfg_kw.setdefault("poll_interval_s", 0.0)
    return MembershipController(
        ElasticConfig(enabled=True, **cfg_kw), str(pod_dir),
        self_worker=0, expected_workers=n, rng=random.Random(0))


def test_never_evicts_on_a_single_missed_beat(tmp_path):
    pod = tmp_path / "pod"
    for i in (1, 2):
        _beat(pod, i)
    c = _controller(pod, n=3)
    assert c.poll(0) is None  # first poll seeds membership
    assert c.members == {"0", "1", "2"}
    _beat(pod, 2, age=1000)  # worker 2 goes silent
    # sighting 1 only SUSPECTS; probes 1 and 2 must both still see it
    # stale before the eviction fires
    assert c.poll(1) is None
    assert "2" in c._suspect
    assert c.poll(2) is None          # probe 1 of 2
    ev = c.poll(3)                    # probe 2 of 2 -> dead
    assert ev is not None and ev.dead == ("2",) and ev.epoch == 1
    assert ev.reasons["2"] == "stale"
    assert c.members == {"0", "1"}
    assert c.audit[-1]["dead"] == ["2"]


def test_fresh_beat_clears_suspicion(tmp_path):
    pod = tmp_path / "pod"
    _beat(pod, 1)
    c = _controller(pod, n=2)
    c.poll(0)
    _beat(pod, 1, age=1000)
    assert c.poll(1) is None and "1" in c._suspect
    _beat(pod, 1)  # the worker comes back before the probes run out
    assert c.poll(2) is None
    assert not c._suspect and c.members == {"0", "1"}


def test_done_is_a_graceful_leave_without_probes(tmp_path):
    pod = tmp_path / "pod"
    _beat(pod, 1)
    c = _controller(pod, n=2)
    c.poll(0)
    _beat(pod, 1, status="done")
    ev = c.poll(1)
    assert ev is not None and ev.dead == ("1",)
    assert ev.reasons["1"] == "done"


def test_joiner_adopted_and_denied(tmp_path):
    pod = tmp_path / "pod"
    c = _controller(pod, n=1)
    c.poll(0)
    assert c.members == {"0"}
    _beat(pod, 5)  # a brand-new worker id offers a fresh beat
    ev = c.poll(1)
    assert ev is not None and ev.joined == ("5",) and ev.n_workers == 2
    # deny policy: the same offer is ignored (warned once)
    c2 = _controller(pod / "2", n=1, rejoin="deny")
    c2.poll(0)
    _beat(pod / "2", 7)
    with pytest.warns(RuntimeWarning, match="rejoin policy"):
        assert c2.poll(1) is None
    assert c2.members == {"0"}


def test_stale_leftover_outside_declared_range_never_joins(tmp_path):
    pod = tmp_path / "pod"
    _beat(pod, 9, age=1000)  # a previous incarnation's dead file
    c = _controller(pod, n=2)
    c.poll(0)
    assert c.members == {"0", "1"}  # declared range only
    assert c.poll(1) is None        # and it never joins while stale


def test_stale_leftover_inside_declared_range_not_seeded(tmp_path):
    """The exit-75 relaunch-bounce breaker: a relaunched pod whose
    declared range still names a permanently-lost worker must NOT seed
    it from its leftover stale heartbeat (that would re-evict it and
    relaunch forever) — but the worker rejoins through adopt the moment
    it beats fresh."""
    pod = tmp_path / "pod"
    _beat(pod, 1)              # alive peer
    _beat(pod, 2, age=1000)    # previous incarnation's dead worker
    c = _controller(pod, n=3)
    assert c.poll(0) is None
    assert c.members == {"0", "1"}  # leftover excluded at seeding
    assert c.audit[-1]["seed_leftovers"] == ["2"]
    assert c.poll(1) is None        # ...and never evicted (no bounce)
    _beat(pod, 2)                   # the worker comes back
    ev = c.poll(2)
    assert ev is not None and ev.joined == ("2",)
    assert c.members == {"0", "1", "2"}


def test_expected_but_never_beating_worker_is_evicted(tmp_path):
    pod = tmp_path / "pod"
    c = _controller(pod, n=2)  # worker 1 declared but NEVER beats
    c.poll(0)
    assert c.members == {"0", "1"}
    assert c.poll(1) is None   # suspect
    assert c.poll(2) is None   # probe 1
    ev = c.poll(3)             # probe 2 -> dead
    assert ev is not None and ev.dead == ("1",)
    assert ev.reasons["1"] == "missing"


def test_tau_by_worker_two_worker_median_budgets_the_slow_one(tmp_path):
    """The review-pinned 2-worker case: with round times {1.0, 2.0} the
    median is their MIDPOINT (utils.health._median), so the slow worker
    gets a genuinely shorter budget — an upper-middle 'median' would
    hand everyone full τ and adaptation could never engage at pod size
    2."""
    pod = tmp_path / "pod"
    _beat(pod, 0, round_s=1.0)
    _beat(pod, 1, round_s=2.0)
    c = _controller(pod, n=2, tau_adapt=True)
    c.poll(0)
    out = c.tau_by_worker(4)
    assert out == {"0": 4, "1": 3}  # round(4 * 1.5 / 2.0) == 3
    # uniform pod: every budget is full τ -> None (nothing to adapt)
    _beat(pod, 1, round_s=1.0)
    c.poll(1, force=True)
    assert c.tau_by_worker(4) is None
    # 2-worker extreme skew: the midpoint median caps the cut at ~τ/2
    # (the straggler is still half the pod's evidence)
    _beat(pod, 1, round_s=100.0)
    c.poll(2, force=True)
    assert c.tau_by_worker(4)["1"] == 2  # round(4 * 50.5 / 100)
    # 3-worker pod: a true outlier is floored at tau_min, fast workers
    # keep full τ
    _beat(pod, 2, round_s=1.0)
    ev = c.poll(3, force=True)
    assert ev is not None and ev.joined == ("2",)
    out3 = c.tau_by_worker(4)
    assert out3 == {"0": 4, "1": c.cfg.tau_min, "2": 4}


@pytest.mark.chaos
def test_tau_adapt_through_train_loop(tmp_path):
    """tau_adapt end to end on a 2-devices-per-worker pod: the per-WORKER
    budget dict expands to the per-DATA-GROUP vector (4 groups, 2
    workers), so the slow worker's BOTH device groups run the shorter
    budget — sized per membership it would crash the trainer's
    per-group assert."""
    pod = tmp_path / "pod"
    hb1 = HeartbeatWriter(worker_heartbeat_path(str(pod), 1),
                          interval_s=0.0)
    hb1.beat(0, status="ok", round_s=0.5, force=True)
    cfg = _tiny_cfg(tmp_path, 4, max_rounds=6)
    cfg.tau = 4
    cfg.elastic.expected_workers = 2  # 2 workers x 2 device groups
    cfg.elastic.tau_adapt = True

    def hook(rnd, state):
        hb1.beat(rnd, status="ok", round_s=0.5, force=True)

    log = Logger(str(tmp_path / "l.txt"), echo=False)
    # worker 0 (this loop) reports no round_s until its first flush ran;
    # after that the controller sees {0: fast, 1: 0.5s} and budgets
    st = train(cfg, net_from_prototxt(TINY_MLP), _tiny_ds(), None,
               logger=log, round_hook=hook)
    log.close()
    # layout-neutral topology probe (momentum rows == data groups)
    assert np.asarray(st.momentum[list(st.momentum)[0]]["w"]).shape[0] == 4
    # the loop ran with a 4-entry vector (or full-τ None) — no assert
    # fired, and training completed across heterogeneous budgets


# -- per-worker τ masking (elastic_tau) --------------------------------------

def _tiny(n_dev, tau=3, cls=ParallelTrainer, **kw):
    net = CompiledNet.compile(net_from_prototxt(TINY_MLP))
    scfg = SolverConfig(base_lr=0.05, momentum=0.9, lr_policy="fixed")
    return cls(net, scfg, make_mesh(n_dev), tau=tau, **kw)


def _tiny_batches(n_dev, tau=3, b=4, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((tau, n_dev * b, 6)).astype(np.float32)
    label = (data.sum(-1, keepdims=True) > 0).astype(np.int32)
    return {"data": data, "label": label}


def test_elastic_tau_full_vector_matches_legacy(trainer_cls):
    import jax
    t0 = _tiny(4, cls=trainer_cls)
    t1 = _tiny(4, elastic_tau=True, cls=trainer_cls)
    b = _tiny_batches(4)
    s0, l0 = t0.train_round(t0.init_state(jax.random.PRNGKey(0)), b,
                            jax.random.PRNGKey(1))
    s1, l1 = t1.train_round(t1.init_state(jax.random.PRNGKey(0)), b,
                            jax.random.PRNGKey(1))
    assert abs(float(l0) - float(l1)) < 1e-6
    for ln in s0.params:
        for pn in s0.params[ln]:
            np.testing.assert_allclose(
                np.asarray(s0.params[ln][pn]), np.asarray(s1.params[ln][pn]),
                rtol=1e-5, atol=1e-7, err_msg=f"{ln}/{pn}")


def test_tau_by_worker_all_ones_equals_tau1_trainer(trainer_cls):
    """Masking oracle: every worker budgeted 1 step == a τ=1 trainer on
    the first slice (same per-worker rng rows by construction)."""
    import jax
    t_el = _tiny(4, elastic_tau=True, cls=trainer_cls)
    b = _tiny_batches(4)
    sA, lA = t_el.train_round(t_el.init_state(jax.random.PRNGKey(0)), b,
                              jax.random.PRNGKey(1),
                              tau_by_worker=[1, 1, 1, 1])
    t_ref = _tiny(4, tau=1, cls=trainer_cls)
    sB, lB = t_ref.train_round(t_ref.init_state(jax.random.PRNGKey(0)),
                               {k: v[:1] for k, v in b.items()},
                               jax.random.PRNGKey(1))
    assert abs(float(lA) - float(lB)) < 1e-6
    for ln in sA.params:
        for pn in sA.params[ln]:
            np.testing.assert_allclose(
                np.asarray(sA.params[ln][pn]), np.asarray(sB.params[ln][pn]),
                rtol=1e-5, atol=1e-7, err_msg=f"{ln}/{pn}")


def test_tau_by_worker_changes_are_recompile_free(trainer_cls):
    import jax
    t = _tiny(2, elastic_tau=True, cls=trainer_cls)
    b = _tiny_batches(2)
    s = t.init_state(jax.random.PRNGKey(0))
    # two priming rounds: steady state is ONE executable plus a fast-path
    # key for its own output layout (the second round's input), which the
    # two layouts reach one round apart
    s, _ = t.train_round(s, b, jax.random.PRNGKey(1))
    s, _ = t.train_round(s, b, jax.random.PRNGKey(1))
    n0 = t.compiled_variants()
    for vec in ([2, 3], [1, 1], [3, 2]):
        s, _ = t.train_round(s, b, jax.random.PRNGKey(2),
                             tau_by_worker=vec)
    assert t.compiled_variants() == n0  # a traced input, not a shape
    with pytest.raises(ValueError):
        _tiny(2).train_round(s, b, jax.random.PRNGKey(3),
                             tau_by_worker=[1, 1])
    # resized() carries the whole configuration (and the CLASS) to the
    # new mesh
    t2 = t.resized(1)
    assert type(t2) is trainer_cls
    assert (t2.n_devices, t2.tau, t2.elastic_tau) == (1, t.tau, True)


# -- the train loop's elastic resize path ------------------------------------

def _tiny_cfg(tmp_path, n_dev, max_rounds, **kw):
    kw.setdefault("elastic", ElasticConfig(
        enabled=True, expected_workers=n_dev, stale_after_s=30.0,
        reprobe_backoff_s=0.0, dead_probes=2, poll_interval_s=0.0,
        min_workers=1))
    return RunConfig(model="prototxt-inline", n_devices=n_dev,
                     local_batch=8, tau=2, max_rounds=max_rounds,
                     eval_every=0, workdir=str(tmp_path),
                     checkpoint_dir=str(tmp_path / "ck"),
                     checkpoint_every=3, pod_dir=str(tmp_path / "pod"),
                     heartbeat_every_s=0.0, **kw)


def _tiny_ds(n=512, seed=0):
    r = np.random.default_rng(seed)
    data = r.standard_normal((n, 6)).astype(np.float32)
    label = (data.sum(-1, keepdims=True) > 0).astype(np.int32)
    return ArrayDataset({"data": data, "label": label})


def _kill(pod_dir, worker):
    """Backdate the worker's beat so it reads stale immediately (the
    deterministic stand-in for 'the VM was preempted minutes ago')."""
    p = worker_heartbeat_path(str(pod_dir), worker)
    rec = json.load(open(p))
    rec["t"] = time.time() - 1e4
    json.dump(rec, open(p, "w"))


@pytest.mark.chaos
@pytest.mark.parametrize("impl", ["shard_map", "named"])
def test_elastic_evict_and_rejoin_through_train_loop(tmp_path, impl):
    """THE tentpole path: a worker's heartbeat goes stale mid-run -> the
    loop evicts it at the τ boundary (resize 2 devices -> 1; restored
    from the verified checkpoint under the replica layout, RE-PLACED
    live under the NamedSharding layout), it comes back -> rejoin
    (1 -> 2). Every eviction/rejoin lands in the JSONL audit trail and
    training keeps descending across both resizes — under BOTH trainer
    implementations."""
    pod = tmp_path / "pod"
    hb1 = HeartbeatWriter(worker_heartbeat_path(str(pod), 1),
                          interval_s=0.0)
    hb1.beat(0, status="ok", round_s=0.01, force=True)
    cfg = _tiny_cfg(tmp_path, 2, max_rounds=12, trainer_impl=impl)
    shapes, killed, rejoined = [], [False], [False]

    def hook(rnd, state):
        # layout-neutral topology probe: replicated momentum rows count
        # the data groups in BOTH layouts ([n_devices] replica rows vs
        # [n_data] logical worker rows; tp == 1 here so they coincide)
        shapes.append(
            np.asarray(state.momentum[list(state.momentum)[0]]
                       ["w"]).shape[0])
        if not killed[0] and rnd == 2:
            killed[0] = True
            _kill(pod, 1)
        elif killed[0] and not rejoined[0] and min(shapes) == 1:
            rejoined[0] = True
            hb1.beat(rnd, status="ok", round_s=0.01, force=True)
        elif not killed[0]:
            hb1.beat(rnd, status="ok", round_s=0.01, force=True)

    jsonl = str(tmp_path / "m.jsonl")
    log = Logger(str(tmp_path / "l.txt"), echo=False, jsonl_path=jsonl)
    train(cfg, net_from_prototxt(TINY_MLP), _tiny_ds(), None, logger=log,
          round_hook=hook)
    log.close()
    recs = [json.loads(l) for l in open(jsonl)]
    resizes = [r for r in recs if r.get("event") == "resize"]
    assert any(r["dead"] == ["1"] for r in resizes), resizes
    assert any(r["joined"] == ["1"] for r in resizes), resizes
    assert sorted(set(shapes)) == [1, 2]  # both topologies actually ran
    epochs = [r["epoch"] for r in resizes]
    assert epochs == sorted(epochs) and epochs[-1] == 2
    losses = [r["loss"] for r in recs if "loss" in r]
    assert losses[-1] < losses[0]  # survived BOTH resizes and kept learning
    if impl == "named":
        # the logical layout resizes by RE-PLACEMENT, not store read-back
        assert "re-placed live state" in open(str(tmp_path / "l.txt")).read()


@pytest.mark.chaos
def test_elastic_below_min_workers_checkpoints_and_halts(tmp_path):
    """Dropping below min_workers is a LOUD halt, never a hang: the loop
    writes a verified checkpoint at the boundary, then raises
    TrainingHealthError naming the dead worker."""
    from sparknet_tpu.utils import checkpoint as ck

    pod = tmp_path / "pod"
    HeartbeatWriter(worker_heartbeat_path(str(pod), 1),
                    interval_s=0.0).beat(0, status="ok", force=True)
    cfg = _tiny_cfg(tmp_path, 2, max_rounds=40)
    cfg.elastic.min_workers = 2

    def hook(rnd, state):
        if rnd == 1:
            _kill(pod, 1)

    log = Logger(str(tmp_path / "l.txt"), echo=False,
                 jsonl_path=str(tmp_path / "m.jsonl"))
    with pytest.raises(TrainingHealthError, match="min_workers"):
        train(cfg, net_from_prototxt(TINY_MLP), _tiny_ds(), None,
              logger=log, round_hook=hook)
    log.close()
    step = ck.newest_verified_step(cfg.checkpoint_dir)
    assert step is not None and step >= 1  # the boundary snapshot landed
    recs = [json.loads(l) for l in open(str(tmp_path / "m.jsonl"))]
    assert any(r.get("event") == "resize" and r["dead"] == ["1"]
               for r in recs)


@pytest.mark.chaos
def test_elastic_resume_after_halt_continues(tmp_path):
    """The checkpoint the halt left behind is a working resume point: a
    relaunch at the surviving size picks it up through the normal elastic
    resume path and finishes the run."""
    test_elastic_below_min_workers_checkpoints_and_halts(tmp_path)
    cfg = _tiny_cfg(tmp_path, 1, max_rounds=6)
    cfg.elastic.min_workers = 1
    cfg.elastic.expected_workers = 1
    log_path = str(tmp_path / "l2.txt")
    log = Logger(log_path, echo=False)
    st = train(cfg, net_from_prototxt(TINY_MLP), _tiny_ds(), None,
               logger=log)
    log.close()
    assert np.asarray(st.momentum[list(st.momentum)[0]]["w"]).shape[0] == 1
    assert "ELASTIC resume" in open(log_path).read()


def test_membership_change_without_reshardable_source_relaunches(tmp_path):
    """A source that cannot reshard in-process (streaming) turns a
    membership change into checkpoint + ElasticRelaunch (SystemExit 75)
    — the launcher's relaunch-as-joiner signal — never a hang."""
    from sparknet_tpu.apps.train_loop import run_loop
    from sparknet_tpu.data.dataset import RoundSampler
    from sparknet_tpu.utils import checkpoint as ck

    pod = tmp_path / "pod"
    HeartbeatWriter(worker_heartbeat_path(str(pod), 1),
                    interval_s=0.0).beat(0, status="ok", force=True)
    cfg = _tiny_cfg(tmp_path, 2, max_rounds=40)

    class NoReshard:  # next_round but no reshard(): streaming-shaped
        stateless_rounds = True

        def __init__(self, sampler):
            self._s = sampler

        def next_round(self, round_index=None):
            return self._s.next_round(round_index)

    trainer = _tiny(2, tau=cfg.tau)
    src = NoReshard(RoundSampler(_tiny_ds(), 2, cfg.local_batch, cfg.tau))

    def hook(rnd, state):
        if rnd == 1:
            _kill(pod, 1)

    log = Logger(str(tmp_path / "l.txt"), echo=False)
    with pytest.raises(ElasticRelaunch) as ei:
        run_loop(cfg, trainer, src, None, log, round_hook=hook,
                 trainer_factory=None)
    log.close()
    assert ei.value.code == ELASTIC_RELAUNCH_EXIT == 75
    assert ck.newest_verified_step(cfg.checkpoint_dir) is not None


# -- the promoted elastic-momentum A/B smoke (satellite) ---------------------

def test_elastic_momentum_ab_smoke(tmp_path):
    """Short-rounds run/resume smoke of scripts/elastic_momentum_ab.py:
    the A/B harness whose verdict (norm_rescale) the elastic resize
    applies must keep running end to end — every policy resumes 8->4 and
    8->2 and produces the summary/winner schema."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "elastic_momentum_ab",
        os.path.join(os.path.dirname(__file__), "..", "scripts",
                     "elastic_momentum_ab.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    out_path = str(tmp_path / "ab.json")
    out = mod.main(["--seeds", "1", "--rounds-pre", "2",
                    "--rounds-post", "3", "--out", out_path])
    assert out["winner"] in mod.POLICIES
    for pol in mod.POLICIES:
        for nd in (4, 2):
            assert len(out["results"][pol][nd]) == 1
            assert "max_rel_dev" in out["results"][pol][nd][0]
    on_disk = json.load(open(out_path))
    assert on_disk["summary"].keys() == out["summary"].keys()
