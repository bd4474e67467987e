"""Training health supervisor: anomaly signals, rollback, checkpoint
integrity (the detect -> rollback -> recover story, plus the hardening
satellites). All deterministic — fault injection is config-keyed
(utils/health.py), never random."""
import json
import os

import numpy as np
import pytest

import jax

from sparknet_tpu.utils import checkpoint as ckpt
from sparknet_tpu.utils.config import RunConfig
from sparknet_tpu.utils.health import (HealthConfig, HealthMonitor,
                                       TrainingHealthError, poison_batch)
from sparknet_tpu.utils.logger import Logger


# -- HealthMonitor classification ------------------------------------------


def _warmed_monitor(cfg=None, n=12, base=2.0):
    mon = HealthMonitor(cfg or HealthConfig(min_history=4))
    for r in range(n):
        assert mon.observe(r, base + 0.01 * (r % 3)) == "ok"
    return mon


def test_monitor_classifies_spike_and_recovers():
    mon = _warmed_monitor()
    assert mon.observe(100, 50.0) == "spike"
    assert mon.rollback_needed is None  # isolated spike: skip-and-continue
    # the spike did NOT enter the window: the next normal loss is ok
    assert mon.observe(101, 2.01) == "ok"
    assert mon.counts["spike"] == 1


def test_monitor_repeated_spikes_latch_rollback():
    mon = _warmed_monitor(HealthConfig(min_history=4, spike_patience=3))
    for r in range(3):
        assert mon.observe(100 + r, 50.0) == "spike"
    assert mon.rollback_needed == "repeated spikes"


def test_monitor_nonfinite_latches_rollback():
    mon = _warmed_monitor()
    assert mon.observe(100, float("nan")) == "nonfinite"
    assert mon.rollback_needed == "nonfinite"
    mon2 = _warmed_monitor()
    assert mon2.observe(100, 2.0, nonfinite_count=3.0) == "nonfinite"
    assert mon2.rollback_needed == "nonfinite"
    # a nonfinite grad norm with FINITE loss/params is overflow in the
    # squared-norm telemetry, not poisoned state: spike, not nonfinite
    mon3 = _warmed_monitor()
    assert mon3.observe(100, 2.0, grad_norm=float("inf")) == "spike"
    assert mon3.rollback_needed is None


def test_monitor_needs_history_before_spike_classification():
    mon = HealthMonitor(HealthConfig(min_history=8))
    # an early wild loss is NOT a spike: no baseline yet (fresh nets start
    # anywhere)
    assert mon.observe(0, 1000.0) == "ok"
    assert mon.observe(1, 2.0) == "ok"


def test_monitor_loss_drop_is_not_a_spike():
    mon = _warmed_monitor()
    assert mon.observe(100, 0.001) == "ok"  # one-sided: improvement is fine


def test_monitor_rollback_budget_hard_fails():
    mon = _warmed_monitor(HealthConfig(min_history=4, max_rollbacks=1))
    mon.observe(100, float("nan"))
    assert mon.consume_rollback() == "nonfinite"  # 1st: within budget
    mon.observe(101, float("nan"))
    with pytest.raises(TrainingHealthError, match="budget"):
        mon.consume_rollback()


def test_monitor_anomaly_tags_checkpoint_window():
    mon = _warmed_monitor(HealthConfig(min_history=4, window=8))
    assert not mon.recently_anomalous(50)
    mon.observe(100, 50.0)
    assert mon.recently_anomalous(101)
    assert not mon.recently_anomalous(100 + 8)
    # consuming a rollback clears the taint: restored state predates it
    mon.observe(110, float("nan"))
    mon.consume_rollback()
    assert not mon.recently_anomalous(111)


def test_poison_batch_spares_integer_labels():
    b = {"data": np.ones((2, 3), np.float32), "label": np.ones((2,), np.int32)}
    p = poison_batch(b, "nan")
    assert np.isnan(p["data"]).all() and (p["label"] == 1).all()
    assert np.isfinite(b["data"]).all()  # original untouched
    s = poison_batch(b, "spike", scale=100.0)
    assert (s["data"] == 100.0).all()


def test_health_config_round_trips_through_run_config():
    cfg = RunConfig.from_dict({"health": {"spike_mad": 5.0,
                                          "inject_nan_rounds": [3]}})
    assert cfg.health.spike_mad == 5.0
    assert cfg.health.inject_nan_rounds == (3,)
    over = cfg.with_overrides('max_rounds=7')
    assert over.health.spike_mad == 5.0 and over.max_rounds == 7
    with pytest.raises(ValueError, match="unknown health config"):
        RunConfig.from_dict({"health": {"nope": 1}})


# -- on-device health scalars ----------------------------------------------


@pytest.fixture(scope="module")
def tiny_trainer(trainer_cls):
    """Parametrized over BOTH trainer implementations (conftest
    trainer_cls): the [n_data+1] health psum layout and its per-worker
    attribution must hold identically under the shard_map replica layout
    and the NamedSharding logical layout."""
    from sparknet_tpu import CompiledNet, net_from_prototxt
    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.solver import SolverConfig
    from tiny_nets import TINY_MLP
    net = CompiledNet.compile(net_from_prototxt(TINY_MLP))
    cfg = SolverConfig(base_lr=0.05, momentum=0.9, lr_policy="fixed")
    return trainer_cls(net, cfg, make_mesh(), tau=3)


def _mlp_batches(seed):
    from tiny_nets import make_round_batches
    return make_round_batches(seed)


def test_round_health_scalars_clean(tiny_trainer):
    state = tiny_trainer.init_state(jax.random.PRNGKey(0))
    state, loss = tiny_trainer.train_round(state, _mlp_batches(1),
                                           jax.random.PRNGKey(42))
    h = tiny_trainer.last_health
    assert float(h["nonfinite"]) == 0.0
    gn = float(h["grad_norm"])
    assert np.isfinite(gn) and gn > 0.0
    assert np.isfinite(float(loss))


def test_round_health_scalars_flag_nan_poison(tiny_trainer):
    state = tiny_trainer.init_state(jax.random.PRNGKey(0))
    batches = poison_batch(_mlp_batches(2), "nan")
    state, loss = tiny_trainer.train_round(state, batches,
                                           jax.random.PRNGKey(43))
    # every data group saw poison: the psum'd flag counts all 8 workers
    assert float(tiny_trainer.last_health["nonfinite"]) == 8.0
    np.testing.assert_array_equal(
        np.asarray(tiny_trainer.last_health["nonfinite_by_worker"]),
        np.ones(8, np.float32))
    assert not np.isfinite(float(loss))


def test_round_health_attributes_single_bad_worker(tiny_trainer):
    """NaNs fed to ONE worker's shard light exactly that worker's slot in
    the [n_data] attribution vector: the per-worker flag reads the
    PRE-average local state, so the weight-averaging pmean (which smears
    the NaN onto every replica one sync later) cannot erase the origin.
    A consistently bad host/feed is argmax of this vector."""
    bad = 5
    state = tiny_trainer.init_state(jax.random.PRNGKey(0))
    batches = _mlp_batches(3)
    per = batches["data"].shape[1] // 8  # [tau, n_dev*local_b, ...] rows
    data = batches["data"].copy()
    data[:, bad * per:(bad + 1) * per] = np.nan
    state, loss = tiny_trainer.train_round(
        state, {"data": data, "label": batches["label"]},
        jax.random.PRNGKey(44))
    h = tiny_trainer.last_health
    vec = np.asarray(h["nonfinite_by_worker"])
    expect = np.zeros(8, np.float32)
    expect[bad] = 1.0
    np.testing.assert_array_equal(vec, expect)
    assert float(h["nonfinite"]) == 1.0
    assert int(np.argmax(vec)) == bad
    # and the averaged params ARE poisoned (the attribution beat the
    # smear, it didn't prevent it — rollback is still the remedy)
    avg = tiny_trainer.averaged_params(state)
    assert not np.isfinite(np.asarray(avg["ip1"]["w"])).all()


def test_lr_scale_shrinks_the_update(tiny_trainer):
    k = jax.random.PRNGKey(0)
    p0 = np.asarray(tiny_trainer.averaged_params(
        tiny_trainer.init_state(k))["ip1"]["w"]).copy()

    def delta(scale):
        s = tiny_trainer.init_state(k)
        s, _ = tiny_trainer.train_round(s, _mlp_batches(1),
                                        jax.random.PRNGKey(42),
                                        lr_scale=scale)
        p = np.asarray(tiny_trainer.averaged_params(s)["ip1"]["w"])
        return np.abs(p - p0).max()

    full, half = delta(1.0), delta(0.5)
    assert half < full * 0.75  # backed-off rounds take smaller steps
    assert half > 0.0


# -- checkpoint integrity ---------------------------------------------------


def _save_steps(d, n=3, seed=0):
    r = np.random.default_rng(seed)
    trees = {}
    for s in range(1, n + 1):
        trees[s] = {"a": {"w": r.standard_normal((4, 3)).astype(np.float32)},
                    "it": np.asarray([s] * 2)}
        ckpt.save(str(d), trees[s], step=s)
    return trees


def _silently_corrupt(npz_path):
    """Path wrapper over the one canonical digest-evading corruption
    helper (fake_stores.corrupt_npz_bytes): flip one value but rewrite a
    VALID archive, the silent at-rest corruption only the recorded
    sha256 digests can catch."""
    from fake_stores import corrupt_npz_bytes
    with open(npz_path, "rb") as f:
        raw = f.read()
    with open(npz_path, "wb") as f:
        f.write(corrupt_npz_bytes(raw))


def test_digest_verification_rejects_flipped_byte(tmp_path):
    trees = _save_steps(tmp_path / "ck", n=3)
    _silently_corrupt(tmp_path / "ck" / "step-3" / "state.npz")

    assert not ckpt.verify(str(tmp_path / "ck" / "step-3"))
    assert ckpt.verify(str(tmp_path / "ck" / "step-2"))
    # auto-latest restore falls back to step 2 BIT-exactly
    with pytest.warns(RuntimeWarning):
        flat, step, _ = ckpt.restore_flat(str(tmp_path / "ck"))
    assert step == 2
    np.testing.assert_array_equal(flat["a/w"], trees[2]["a"]["w"])
    # explicit-step restore of the corrupt one fails loudly
    with pytest.raises(ckpt.CheckpointCorruptError, match="digest"):
        ckpt.restore_flat(str(tmp_path / "ck"), step=3)
    assert ckpt.newest_verified_step(str(tmp_path / "ck")) == 2


def test_truncated_npz_rejected_and_falls_back(tmp_path):
    trees = _save_steps(tmp_path / "ck", n=2)
    npz = tmp_path / "ck" / "step-2" / "state.npz"
    npz.write_bytes(npz.read_bytes()[:40])  # torn copy
    with pytest.warns(RuntimeWarning):
        flat, step, _ = ckpt.restore_flat(str(tmp_path / "ck"))
    assert step == 1
    np.testing.assert_array_equal(flat["a/w"], trees[1]["a"]["w"])


def test_bad_meta_json_is_not_a_checkpoint(tmp_path):
    _save_steps(tmp_path / "ck", n=2)
    meta = tmp_path / "ck" / "step-2" / "meta.json"
    meta.write_text("{ torn json")
    with pytest.warns(RuntimeWarning):
        assert ckpt.latest_step(str(tmp_path / "ck")) == 1
    with pytest.warns(RuntimeWarning):
        _, step, _ = ckpt.restore_flat(str(tmp_path / "ck"))
    assert step == 1
    os.remove(meta)  # missing entirely: same story
    with pytest.warns(RuntimeWarning):
        assert ckpt.latest_step(str(tmp_path / "ck")) == 1


def test_digestless_legacy_checkpoint_still_restores(tmp_path):
    tree = {"a": np.arange(6, dtype=np.float32)}
    path = ckpt.save(str(tmp_path / "ck"), tree, step=1)
    meta = json.load(open(os.path.join(path, "meta.json")))
    del meta["digests"]  # simulate a pre-integrity-format checkpoint
    json.dump(meta, open(os.path.join(path, "meta.json"), "w"))
    assert ckpt.verify(path)  # vacuous digest check
    flat, step, _ = ckpt.restore_flat(str(tmp_path / "ck"))
    assert step == 1
    np.testing.assert_array_equal(flat["a"], tree["a"])


def test_retain_protects_newest_verified(tmp_path):
    _save_steps(tmp_path / "ck", n=5)
    for s in (4, 5):  # corrupt the two newest
        npz = tmp_path / "ck" / f"step-{s}" / "state.npz"
        raw = bytearray(npz.read_bytes())
        raw[-10] ^= 0x01
        npz.write_bytes(bytes(raw))
    ckpt.retain(str(tmp_path / "ck"), keep=2)
    # keep-window is {4, 5}, but step 3 is the newest VERIFIED one: kept
    assert sorted(os.listdir(tmp_path / "ck")) == \
        ["step-3", "step-4", "step-5"]


def test_save_sweeps_stale_tmp_dirs(tmp_path):
    d = tmp_path / "ck"
    os.makedirs(d / ".tmp-deadbeef")  # SIGKILL'd writer's leftovers
    (d / ".tmp-deadbeef" / "state.npz").write_bytes(b"partial")
    ckpt.save(str(d), {"a": np.zeros(2)}, step=1)
    assert sorted(os.listdir(d)) == ["step-1"]


def test_anomalous_checkpoints_skipped_by_rollback_selector(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save(d, {"a": np.zeros(2)}, step=1)
    ckpt.save(d, {"a": np.ones(2)}, step=2, extra={"anomalous": True})
    assert ckpt.newest_verified_step(d) == 2
    assert ckpt.newest_verified_step(d, skip_anomalous=True) == 1


# -- the composed story: injected fault -> detect -> rollback -> recover ----


def _train_with_injection(tmp_path, health, max_rounds=8, log_every=1,
                          checkpoint_every=1, **cfg_kw):
    from sparknet_tpu.data import cifar
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.solver import SolverConfig
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.zoo import cifar10_quick

    d = str(tmp_path / "cifar")
    if not os.path.isdir(d):
        cifar.write_synthetic(d, n_per_file=40)
    train_ds = ArrayDataset(cifar.CifarLoader(d).train_batch_dict())
    cfg = RunConfig(
        solver=SolverConfig(base_lr=0.01, momentum=0.9, lr_policy="fixed"),
        tau=2, local_batch=4, eval_every=0, max_rounds=max_rounds, seed=0,
        workdir=str(tmp_path), log_every=log_every,
        checkpoint_dir=str(tmp_path / "ck"),
        checkpoint_every=checkpoint_every, health=health, **cfg_kw)
    jsonl = str(tmp_path / "metrics.jsonl")
    state = train(cfg, cifar10_quick(batch=4), train_ds,
                  logger=Logger(str(tmp_path / "log.txt"), echo=False,
                                jsonl_path=jsonl))
    recs = [json.loads(ln) for ln in open(jsonl)]
    return cfg, state, recs


@pytest.mark.chaos
def test_injected_nan_round_detected_rolled_back_and_recovered(tmp_path):
    """The acceptance path: a forced-NaN round at R is detected within one
    log_every window, the run rolls back to the last verified checkpoint,
    completes to max_rounds, and the final loss is finite."""
    R = 3
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(inject_nan_rounds=(R,), min_history=2),
        max_rounds=8)

    events = [r for r in recs if r.get("event") == "rollback"]
    assert len(events) == 1
    ev = events[0]
    assert ev["reason"] == "nonfinite"
    assert ev["target_step"] <= R  # restored a pre-fault checkpoint
    assert ev["retry"] == 1

    # round-accounting: the poisoned pass over R logged a nonfinite loss
    # (serialized as null — NaN is not valid JSON), the retried pass a
    # finite one, and every round 0..max_rounds-1 has a finite FINAL
    # occurrence (the retry wins)
    by_round = {}
    for r in recs:
        if "loss" in r:
            by_round.setdefault(r["step"], []).append(r["loss"])
    assert any(x is None for x in by_round[R])
    assert by_round[R][-1] is not None and np.isfinite(by_round[R][-1])
    for rr in range(cfg.max_rounds):
        last = by_round[rr][-1]
        assert last is not None and np.isfinite(last), f"round {rr}"
    # detection within one log_every window of the fault
    nonf = [r["step"] for r in recs if r.get("health") == "nonfinite"]
    assert nonf and min(nonf) == R

    # the run completed: final checkpoint at max_rounds, fully finite
    flat, step, extra = ckpt.restore_flat(cfg.checkpoint_dir)
    assert step == cfg.max_rounds
    assert all(np.isfinite(np.asarray(a)).all() for a in flat.values())
    assert "anomalous" not in extra  # recovery cleared the taint
    # the supervisor's recovery state rides the checkpoint: a preemption-
    # resume must not silently revert the backoff / retried data order
    assert extra["health"] == {"retry": 1, "lr_scale": 0.5, "rollbacks": 1}


@pytest.mark.chaos
def test_heartbeat_and_worker_attribution_in_loop(tmp_path):
    """The loop-level surface of both satellites: with heartbeat_path
    set, the run leaves a fresh heartbeat whose status reflects the
    outcome ("done", rollbacks counted), and the poisoned round's JSONL
    row carries the worst-worker attribution."""
    from sparknet_tpu.utils.heartbeat import read_heartbeat, staleness_s
    hb_path = str(tmp_path / "hb.json")
    R = 3
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(inject_nan_rounds=(R,), min_history=2),
        max_rounds=6, heartbeat_path=hb_path, heartbeat_every_s=0.0)
    hb = read_heartbeat(hb_path)
    assert hb is not None and hb["role"] == "train"
    assert hb["status"] == "done" and hb["step"] == cfg.max_rounds
    assert hb["rollbacks"] == 1
    assert staleness_s(hb) < 120
    # the nonfinite round's metrics row names the worst worker (the
    # injection poisons every worker's shard, so index 0 wins the argmax
    # and ALL workers are flagged)
    row = next(r for r in recs if r.get("health") == "nonfinite")
    assert row["step"] == R
    assert row["worst_worker"] == 0
    assert row["nonfinite_workers"] == 8  # every worker's shard poisoned


@pytest.mark.chaos
def test_two_separate_incidents_each_detected(tmp_path):
    """Injection keys on per-round first execution, not the global retry
    generation: a second configured fault AFTER an earlier rollback still
    fires and is recovered independently."""
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(inject_nan_rounds=(2, 5), min_history=2),
        max_rounds=8)
    events = [r for r in recs if r.get("event") == "rollback"]
    assert len(events) == 2
    assert [e["retry"] for e in events] == [1, 2]
    flat, step, _ = ckpt.restore_flat(cfg.checkpoint_dir)
    assert step == cfg.max_rounds
    assert all(np.isfinite(np.asarray(a)).all() for a in flat.values())


@pytest.mark.chaos
def test_injected_fault_with_batched_log_every(tmp_path):
    """log_every > 1: health scalars stay on device between flushes, and
    detection still lands within one window (<= log_every rounds late)."""
    R = 2
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(inject_nan_rounds=(R,), min_history=2),
        max_rounds=8, log_every=3)
    events = [r for r in recs if r.get("event") == "rollback"]
    assert len(events) == 1
    flat, step, _ = ckpt.restore_flat(cfg.checkpoint_dir)
    assert step == cfg.max_rounds
    assert all(np.isfinite(np.asarray(a)).all() for a in flat.values())


@pytest.mark.chaos
def test_injected_spikes_skip_then_rollback_and_tag_checkpoints(tmp_path):
    """Spike path: repeated injected spikes cross spike_patience and roll
    back; checkpoints taken in the unhealthy window carry the anomalous
    tag (and the anomalous_checkpoint event lands in the JSONL — the
    Logger.event/step collision regression)."""
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(min_history=2, spike_mad=6.0,
                               spike_patience=2,
                               inject_spike_rounds=(4, 5),
                               # gentle: x30 inputs spike the loss but stay
                               # finite (x1000 would overflow to NaN and
                               # test the nonfinite path instead)
                               inject_spike_scale=30.0),
        max_rounds=8, checkpoint_every=2)
    assert any(r.get("health") == "spike" for r in recs)
    kinds = {r["event"] for r in recs if "event" in r}
    assert "rollback" in kinds
    rb = next(r for r in recs if r.get("event") == "rollback")
    assert rb["reason"] == "repeated spikes"
    for ev in (r for r in recs if r.get("event") == "anomalous_checkpoint"):
        assert ev["checkpoint_step"] > 0  # event carries the tagged step
    flat, step, _ = ckpt.restore_flat(cfg.checkpoint_dir)
    assert step == cfg.max_rounds
    assert all(np.isfinite(np.asarray(a)).all() for a in flat.values())


@pytest.mark.chaos
def test_unrecoverable_without_checkpoints_fails_loudly(tmp_path):
    from sparknet_tpu.data import cifar
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.solver import SolverConfig
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.zoo import cifar10_quick

    d = str(tmp_path / "cifar")
    cifar.write_synthetic(d, n_per_file=40)
    train_ds = ArrayDataset(cifar.CifarLoader(d).train_batch_dict())
    cfg = RunConfig(
        solver=SolverConfig(base_lr=0.01, momentum=0.9, lr_policy="fixed"),
        tau=2, local_batch=4, eval_every=0, max_rounds=6, seed=0,
        workdir=str(tmp_path),  # NO checkpoint_dir
        health=HealthConfig(inject_nan_rounds=(2,), min_history=2))
    with pytest.raises(TrainingHealthError, match="checkpoint"):
        train(cfg, cifar10_quick(batch=4), train_ds,
              logger=Logger(echo=False))


@pytest.mark.chaos
def test_corrupt_latest_checkpoint_resume_falls_back_bit_exactly(tmp_path):
    """Corrupt-checkpoint chaos: byte-flip the newest checkpoint of a real
    run; resume must reject it via digest verification and restore the
    previous step bit-exactly."""
    cfg, state, _ = _train_with_injection(
        tmp_path, HealthConfig(), max_rounds=4, checkpoint_every=2)
    ckdir = cfg.checkpoint_dir
    assert ckpt.latest_step(ckdir) == 4
    good, good_step, _ = ckpt.restore_flat(ckdir, step=2)

    # the loop writes the sharded layout by default since r8: corrupt
    # whichever state file the step holds (state.npz, or a shard file)
    step_dir = os.path.join(ckdir, "step-4")
    victims = sorted(f for f in os.listdir(step_dir)
                     if f == "state.npz" or f.startswith("shard-"))
    _silently_corrupt(os.path.join(step_dir, victims[0]))

    with pytest.warns(RuntimeWarning, match="digest mismatch"):
        flat, step, _ = ckpt.restore_flat(ckdir)
    assert step == 2
    assert sorted(flat) == sorted(good)
    for k in good:
        np.testing.assert_array_equal(flat[k], good[k], err_msg=k)


@pytest.mark.chaos
def test_injection_inert_when_supervisor_disabled(tmp_path):
    """enabled=False must disarm the injection hooks too: poisoning a run
    with nothing watching would recreate the silent-NaN failure mode this
    subsystem exists to prevent."""
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(enabled=False, inject_nan_rounds=(2,)),
        max_rounds=4)
    losses = [r["loss"] for r in recs if "loss" in r]
    assert len(losses) == cfg.max_rounds
    assert all(x is not None and np.isfinite(x) for x in losses)
    assert not any("event" in r for r in recs)


def test_healthy_run_has_no_health_events(tmp_path):
    """Steady state: no spikes, no rollbacks, no extra sync — the metrics
    stream carries grad_norm but no health/event records."""
    cfg, state, recs = _train_with_injection(
        tmp_path, HealthConfig(), max_rounds=4)
    assert not any("event" in r for r in recs)
    assert not any("health" in r for r in recs)
    gnorms = [r["grad_norm"] for r in recs if "grad_norm" in r]
    assert len(gnorms) == cfg.max_rounds
    assert all(np.isfinite(g) and g > 0 for g in gnorms)
    # vanilla runs write pre-health-format checkpoint extras (no recovery
    # state key rides along when nothing was recovered)
    _, _, extra = ckpt.restore_flat(cfg.checkpoint_dir)
    assert "health" not in extra and "anomalous" not in extra


# -- gcs backoff satellites -------------------------------------------------


def test_retry_delay_full_jitter_not_synchronized(monkeypatch):
    from sparknet_tpu.data import gcs
    delays = {gcs.retry_delay(2) for _ in range(32)}
    assert len(delays) > 1  # jittered, not the old deterministic 2.0 s
    assert all(0.0 <= d <= gcs.BACKOFF_S * 4 for d in delays)


def test_retry_delay_honors_retry_after_floor():
    import email.message
    import urllib.error
    from sparknet_tpu.data import gcs

    hdrs = email.message.Message()
    hdrs["Retry-After"] = "7"
    err = urllib.error.HTTPError("http://x", 429, "too many", hdrs, None)
    for _ in range(8):
        assert gcs.retry_delay(0, err) >= 7.0
    # non-429s and date-form headers keep the jittered delay
    err500 = urllib.error.HTTPError("http://x", 500, "ise", hdrs, None)
    assert gcs.retry_delay(0, err500) <= gcs.BACKOFF_S
    bad = email.message.Message()
    bad["Retry-After"] = "Wed, 21 Oct 2026 07:28:00 GMT"
    err_bad = urllib.error.HTTPError("http://x", 429, "tm", bad, None)
    assert gcs.retry_delay(0, err_bad) <= gcs.BACKOFF_S
