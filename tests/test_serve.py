"""`sparknet_tpu.serve` — dynamic batching, hot-reload, parity, chaos.

Tier-1 (CPU mesh, local/fake stores, small nets). The contracts pinned:

  - batching policy: max-batch flush, oldest-request deadline flush,
    queue-capacity backpressure, batches never exceed their bucket.
  - concurrency: N client threads, every request answered exactly once
    with ITS OWN answer (responses keyed to request content).
  - parity: padded rows are BITWISE-identical to an unpadded forward at
    the same compiled bucket (padding is lossless); across different
    buckets outputs are allclose (XLA may re-associate per-shape — the
    same contract training accepts, pinned empirically here).
  - chaos: a checkpoint hot-swap lands mid-traffic without dropping or
    corrupting a single response; a corrupt snapshot is rejected
    (digest verify) with traffic unharmed; a poisoned-but-valid
    snapshot is rolled back by the canary.
"""
import json
import os
import threading
import time
import urllib.request
from concurrent.futures import Future

import numpy as np
import pytest

from sparknet_tpu.net_api import JaxNet
from sparknet_tpu.serve import (DeadlineExpiredError, DynamicBatcher,
                                InferenceServer, ModelManager,
                                QueueFullError, ServeConfig,
                                ServeModelError, zeros_batch)
from sparknet_tpu.serve.model_manager import params_from_checkpoint_flat
from sparknet_tpu.utils import checkpoint as ckpt
from sparknet_tpu.utils.heartbeat import read_heartbeat
from sparknet_tpu.zoo import lenet


def _example(i: int) -> dict:
    """Deterministic per-request input keyed on i — responses can be
    matched back to the request that produced them."""
    r = np.random.default_rng(1000 + i)
    return {"data": r.standard_normal((28, 28, 1)).astype(np.float32)}


@pytest.fixture(scope="module")
def net():
    return JaxNet(lenet(batch=4))


@pytest.fixture()
def server(net):
    cfg = ServeConfig(max_batch=4, max_wait_ms=10.0,
                      outputs=("fc2", "prob"), metrics_every_batches=0)
    with InferenceServer(net, cfg) as srv:
        yield srv


# -- batcher policy ----------------------------------------------------------

def test_batcher_flushes_at_max_batch():
    b = DynamicBatcher(max_batch=4, max_wait_s=60.0)  # deadline far away
    for i in range(9):
        b.submit({"x": np.float32(i)})
    got = b.next_batch()
    assert [r.payload["x"] for r in got] == [0, 1, 2, 3]  # FIFO, full
    assert len(b.next_batch()) == 4
    # 1 leftover: the deadline (not size) must flush it
    b.max_wait_s = 0.01
    t0 = time.perf_counter()
    got = b.next_batch()
    assert len(got) == 1 and got[0].payload["x"] == 8
    assert time.perf_counter() - t0 < 5.0


def test_batcher_deadline_keyed_on_oldest():
    """A steady trickle must not reset the timer: the batch closes at
    oldest.t_enqueue + max_wait even while new requests keep arriving."""
    b = DynamicBatcher(max_batch=64, max_wait_s=0.08)
    stop = threading.Event()

    def trickle():
        while not stop.is_set():
            b.submit({"x": np.float32(0)})
            time.sleep(0.005)

    t = threading.Thread(target=trickle, daemon=True)
    b.submit({"x": np.float32(-1)})
    t0 = time.perf_counter()
    t.start()
    try:
        got = b.next_batch()
    finally:
        stop.set()
        t.join()
    dt = time.perf_counter() - t0
    assert got[0].payload["x"] == -1
    assert dt < 1.0, f"trickle starved the head of the queue for {dt:.2f}s"
    b.close()


def test_batcher_wake_on_submit_no_poll_quantum():
    """Wake-on-submit: a consumer parked with a FAR wake_at alarm is
    woken by submit immediately — a lone request's wait is bounded by
    max_wait_s + scheduling jitter, with no poll-interval quantum."""
    b = DynamicBatcher(max_batch=8, max_wait_s=0.005)
    got, lat = [], []

    def consume():
        t0 = time.perf_counter()
        got.append(b.next_batch(wake_at=t0 + 30.0))  # alarm way out
        lat.append(time.perf_counter() - t0)

    t = threading.Thread(target=consume, daemon=True)
    t.start()
    time.sleep(0.1)  # consumer is parked in the condition wait
    t0 = time.perf_counter()
    b.submit({"x": np.float32(7)})
    t.join(timeout=5.0)
    assert not t.is_alive()
    dt = time.perf_counter() - t0
    assert got[0][0].payload["x"] == 7
    # bound: max_wait (5 ms) + generous scheduling jitter, FAR below the
    # old 50 ms poll quantum this replaced
    assert dt < 0.045, f"lone request waited {dt * 1e3:.1f} ms"
    b.close()


def test_batcher_sheds_expired_deadlines_before_forming():
    """A queued request whose client deadline passed is shed at batch
    formation (DeadlineExpiredError + shed counter), never returned in
    a batch; requests without deadlines are unaffected."""
    from sparknet_tpu.obs import MetricsRegistry

    reg = MetricsRegistry()
    b = DynamicBatcher(max_batch=8, max_wait_s=0.01, registry=reg,
                       model="m")
    doomed = b.submit({"x": np.float32(1)}, deadline_s=0.005)
    alive = b.submit({"x": np.float32(2)})
    time.sleep(0.05)  # doomed expires while queued
    got = b.next_batch()
    assert [r.payload["x"] for r in got] == [2]
    with pytest.raises(DeadlineExpiredError):
        doomed.result(timeout=1.0)
    assert b.shed == 1
    c = reg.counter("sparknet_serve_shed_total",
                    labels=("model", "reason"))
    assert c.value(model="m", reason="deadline") == 1
    # an ALREADY-expired deadline never touches the queue
    pre = b.submit({"x": np.float32(3)}, deadline_s=0.0)
    with pytest.raises(DeadlineExpiredError):
        pre.result(timeout=1.0)
    assert b.depth() == 0 and b.shed == 2
    # sanity: the un-deadlined request was actually served
    assert alive  # future returned; group serving is the server's job
    b.close()


def test_batcher_closes_batch_early_for_client_deadline():
    """Deadline-aware formation: a request whose client deadline lands
    BEFORE the oldest-request max_wait close resolves at ~its deadline —
    served early (the formation loop closes 1 ms ahead of the deadline),
    or, if a contended host loses that scheduling margin, shed AT it.
    Either way the client is answered around its deadline, never held
    to the 0.5 s batch deadline."""
    b = DynamicBatcher(max_batch=64, max_wait_s=0.5)
    t0 = time.perf_counter()
    f = b.submit({"x": np.float32(1)}, deadline_s=0.05)
    got = b.next_batch()
    dt = time.perf_counter() - t0
    assert dt < 0.3, (f"batch held {dt:.2f}s past the client deadline "
                      f"instead of closing early")
    if got:  # the common, uncontended outcome: served before expiry
        assert got[0].payload["x"] == 1
    else:    # margin lost to scheduling: shed AT the deadline, answered
        with pytest.raises(DeadlineExpiredError):
            f.result(timeout=1.0)
    b.close()


def test_server_infer_timeout_is_a_deadline(net):
    """infer(timeout=) threads the deadline into batch formation: an
    expired request is shed with DeadlineExpiredError instead of riding
    a bucket slot (and instead of a bare concurrent.futures timeout)."""
    cfg = ServeConfig(max_batch=4, max_wait_ms=2.0, outputs=("prob",),
                      metrics_every_batches=0)

    class SlowNet:
        """Facade: forwards take long enough that a queued request's
        deadline expires while an earlier batch is still running."""

        def __init__(self, inner, delay_s):
            self._inner, self._delay = inner, delay_s

        def __getattr__(self, k):
            return getattr(self._inner, k)

        def forward(self, *a, **kw):
            time.sleep(self._delay)
            return self._inner.forward(*a, **kw)

    slow = SlowNet(net, 0.25)
    with InferenceServer(slow, cfg) as srv:
        srv.infer(_example(0))  # compile + warm
        # first request occupies the worker; the second's 100 ms deadline
        # expires during that forward -> shed at ITS batch formation
        first = srv.submit(_example(1))
        time.sleep(0.05)  # first's batch is IN the slow forward now
        t0 = time.perf_counter()
        with pytest.raises(DeadlineExpiredError):
            srv.infer(_example(2), timeout=0.1)
        dt = time.perf_counter() - t0
        assert dt < 2.0, f"shed took {dt:.2f}s (shed-not-hang violated)"
        first.result(timeout=30.0)
        assert srv.batcher.shed >= 1
        assert srv.status()["requests_shed"] >= 1


def test_server_lone_request_latency_bounded(net):
    """The wake-on-submit pin at server level: a warmed, idle server
    answers a lone request within max_wait + a few forwards — the old
    50 ms idle-poll quantum is gone from the path."""
    cfg = ServeConfig(max_batch=4, max_wait_ms=5.0, outputs=("prob",),
                      metrics_every_batches=0)
    with InferenceServer(net, cfg) as srv:
        srv.infer(_example(0))  # compile bucket 1
        # estimate one forward
        t0 = time.perf_counter()
        srv.infer(_example(1))
        fwd_s = max(time.perf_counter() - t0 - 0.005, 0.002)
        time.sleep(0.3)  # worker fully parked (mid-poll, in the old code)
        lats = []
        for i in range(15):
            t0 = time.perf_counter()
            srv.infer(_example(2 + i))
            lats.append(time.perf_counter() - t0)
            time.sleep(0.01)
        lats.sort()
        p99 = lats[-1]
        bound = 0.005 + 6 * fwd_s + 0.015  # deadline + forwards + jitter
        assert p99 < max(bound, 0.045), (
            f"lone p99 {p99 * 1e3:.1f} ms vs bound "
            f"{max(bound, 0.045) * 1e3:.1f} ms — is an idle-poll quantum "
            f"back in the path?")


def test_batcher_backpressure_and_close():
    b = DynamicBatcher(max_batch=2, max_wait_s=60.0, max_queue=3)
    futs = [b.submit({"x": np.float32(i)}) for i in range(3)]
    with pytest.raises(QueueFullError):
        b.submit({"x": np.float32(9)})
    b.close()
    with pytest.raises(RuntimeError):
        b.submit({"x": np.float32(9)})
    for f in futs:  # queued-but-unserved requests must not hang clients
        with pytest.raises(RuntimeError, match="shut down"):
            f.result(timeout=1.0)


# -- serving: concurrency + bucket discipline --------------------------------

def test_concurrent_clients_every_request_answered_exactly_once(server,
                                                                net):
    """8 client threads x 12 requests: every future resolves exactly once,
    with the answer belonging to ITS request (matched against a direct
    forward of the same example), and every formed batch fits a bucket."""
    n_clients, per = 8, 12
    results: dict = {}
    errs = []

    def client(c):
        try:
            futs = [(i, server.submit(_example(c * per + i)))
                    for i in range(per)]
            for i, f in futs:
                results[(c, i)] = f.result(timeout=30.0)
        except Exception as e:  # pragma: no cover - failure detail
            errs.append(e)

    ts = [threading.Thread(target=client, args=(c,))
          for c in range(n_clients)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert not errs, errs
    assert len(results) == n_clients * per  # exactly once, none dropped
    st = server.status()
    assert st["requests_ok"] == n_clients * per
    assert st["requests_failed"] == 0
    # responses match their own request: direct forward of example k
    # (cross-bucket tolerance — the response may have run in any bucket;
    # see test_cross_bucket_outputs_allclose for why not bitwise)
    for (c, i), resp in results.items():
        k = c * per + i
        direct = net.forward({**zeros_batch(net, 1), **{
            "data": _example(k)["data"][None]}}, blob_names=["fc2"])
        np.testing.assert_allclose(resp["fc2"], direct["fc2"][0],
                                   rtol=1e-4, atol=1e-4)
    # bucket discipline: n <= bucket, bucket is a configured bucket
    assert server.batch_log, "no batches recorded"
    for n, bucket in server.batch_log:
        assert bucket in server.buckets
        assert 1 <= n <= bucket <= server.cfg.max_batch


def test_mis_shaped_request_rejected_at_the_door(server):
    """A mis-shaped request is a TYPED ValueError at submit() — the
    frontends' 400 ladder — never a batch-mate poisoner. It used to
    survive to the pre-sized pad path, where `np.stack(rows,
    out=buf[:n])` blew up the WHOLE signature group with an opaque
    "Output array is the wrong shape" server-side 500."""
    good = [server.submit(_example(i)) for i in range(2)]
    with pytest.raises(ValueError, match=r"\(7, 7, 1\)"):
        server.submit({"data": np.zeros((7, 7, 1), np.float32)})
    with pytest.raises(ValueError, match="not a net input"):
        server.submit({"dta": _example(0)["data"]})
    # co-batched good requests are untouched, and the bad one never
    # entered the pipeline: no server-side failure is recorded
    for f in good:
        assert np.isfinite(f.result(timeout=30.0)["prob"]).all()
    assert server.status()["requests_failed"] == 0


# -- parity ------------------------------------------------------------------

def test_padded_batch_bitwise_matches_unpadded_rows(net):
    """Padding is lossless WITHIN a compiled bucket: rows of a 2-real/
    2-pad forward are bitwise-identical to the same rows of a full-4
    forward (every layer is row-independent across the batch)."""
    data = np.stack([_example(i)["data"] for i in range(4)])
    full = net.forward({**zeros_batch(net, 4), "data": data},
                       blob_names=["fc2", "prob"])
    padded_in = np.concatenate([data[:2], np.zeros_like(data[:2])])
    padded = net.forward({**zeros_batch(net, 4), "data": padded_in},
                         blob_names=["fc2", "prob"])
    for k in ("fc2", "prob"):
        np.testing.assert_array_equal(padded[k][:2], full[k][:2])


def test_server_single_bucket_bitwise_parity(net):
    """With ONE bucket, a lone request and a full concurrent batch run
    the SAME compiled forward — server answers are bitwise-identical to
    direct single-request forwards padded to that bucket."""
    cfg = ServeConfig(max_batch=4, max_wait_ms=5.0, buckets=(4,),
                      outputs=("fc2",))
    with InferenceServer(net, cfg) as srv:
        lone = srv.infer(_example(0))  # padded 1 -> 4 by the server
        futs = [srv.submit(_example(i)) for i in range(4)]
        batched = [f.result(timeout=30.0) for f in futs]
        assert all(b == 4 for _, b in srv.batch_log)
    direct_in = np.stack([_example(i)["data"] for i in range(4)])
    direct = net.forward({**zeros_batch(net, 4), "data": direct_in},
                         blob_names=["fc2"])
    # the lone request and its batched twin took different-fill batches
    # of the SAME bucket: bitwise equal, and equal to the direct forward
    np.testing.assert_array_equal(lone["fc2"], batched[0]["fc2"])
    for i in range(4):
        np.testing.assert_array_equal(batched[i]["fc2"], direct["fc2"][i])


def test_cross_bucket_outputs_allclose(server, net):
    """Across DIFFERENT compiled buckets XLA may re-associate reductions:
    the contract is allclose, not bitwise (measured ~3e-5 max drift on
    f32 lenet logits) — pinned so a real numerical regression (layout
    bug, wrong padding) still fails loudly."""
    lone = server.infer(_example(3))  # bucket 1
    futs = [server.submit(_example(i)) for i in range(3, 7)]  # bucket 4
    batched = futs[0].result(timeout=30.0)
    for f in futs[1:]:
        f.result(timeout=30.0)
    np.testing.assert_allclose(lone["fc2"], batched["fc2"],
                               rtol=1e-4, atol=1e-4)


# -- checkpoint hot-reload ---------------------------------------------------

def _save_trainstate_like(net, d, step, scale=1.0, anomalous=False):
    """A TrainState-shaped checkpoint (params/<l>/<p> with a leading
    replica axis) holding this net's weights scaled by `scale`."""
    flat = {}
    for lname, lp in net.params.items():
        for pname, w in lp.items():
            flat[f"params/{lname}/{pname}"] = np.asarray(w)[None] * scale
    extra = {"anomalous": True} if anomalous else None
    return ckpt.save(str(d), flat, step=step, extra=extra)


def test_manager_initial_load_and_flat_extraction(net, tmp_path):
    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=3, scale=0.5)
    m = ModelManager(net, checkpoint_dir=str(d))
    assert m.load_initial() == 3
    assert m.step == 3
    # and the extraction helper round-trips shapes exactly
    flat, _, _ = ckpt.restore_flat(str(d))
    params = params_from_checkpoint_flat(flat, net.params)
    for lname, lp in net.params.items():
        for pname, w in lp.items():
            assert params[lname][pname].shape == w.shape


def test_manager_rejects_missing_leaves(net, tmp_path):
    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=1)
    flat, _, _ = ckpt.restore_flat(str(d))
    with pytest.raises(ServeModelError, match="conv1"):
        params_from_checkpoint_flat(
            {k: v for k, v in flat.items() if "conv1" not in k},
            net.params)
    # a claimed-tp checkpoint whose shards do NOT reassemble to the net's
    # shapes still fails loudly with the leaf path
    bad = dict(flat)
    bad["params/fc1/w"] = bad["params/fc1/w"][:, :, :100]
    with pytest.raises(ServeModelError, match="fc1"):
        params_from_checkpoint_flat(bad, net.params, tp=2)


def _tp2_trainer_checkpoint(cls, d, step):
    """A REAL tp=2 training checkpoint of the serve net's architecture,
    written exactly as the train loop persists it (fetch_global ->
    flatten, topology in extra)."""
    import jax

    from sparknet_tpu import CompiledNet
    from sparknet_tpu.parallel import make_mesh
    from sparknet_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                            fetch_global)
    from sparknet_tpu.solver import SolverConfig
    from sparknet_tpu.zoo import lenet as lenet_spec

    cnet = CompiledNet.compile(lenet_spec(batch=4))
    mesh = make_mesh(4, axis_names=(DATA_AXIS, MODEL_AXIS), shape=(2, 2))
    t = cls(cnet, SolverConfig(base_lr=0.01, momentum=0.9,
                               lr_policy="fixed"), mesh, tau=1)
    state = t.init_state(jax.random.PRNGKey(5))
    flat = ckpt._flatten(fetch_global(state))
    extra = {"n_devices": 4, "tp": 2}
    if getattr(t, "state_layout", "replica") != "replica":
        extra["layout"] = t.state_layout
        extra["state_sharding"] = t.state_sharding
    ckpt.save(str(d), flat, step=step, extra=extra)
    return {l: {p: np.asarray(x) for p, x in lp.items()}
            for l, lp in t.averaged_params(state).items()}


def test_manager_serves_tp2_checkpoints_both_layouts(net, tmp_path):
    """r7: tp=2 checkpoints are servable. The replica layout's per-device
    column shards reassemble inside params_from_checkpoint_flat; the
    NamedSharding layout stores full logical weights and needs no
    reassembly. Either way the installed params equal the trainer's own
    averaged_params BITWISE and the manager reports a healthy swap."""
    from sparknet_tpu.parallel import ParallelTrainer, ShardedTrainer

    for sub, cls in (("replica", ParallelTrainer),
                     ("logical", ShardedTrainer)):
        d = tmp_path / f"ck_{sub}"
        want = _tp2_trainer_checkpoint(cls, d, step=2)
        m = ModelManager(net, checkpoint_dir=str(d), poll_interval_s=0.0)
        assert m.load_initial() == 2, sub
        assert m.swap_failures == 0, sub
        for lname, lp in want.items():
            for pname, w in lp.items():
                got = np.asarray(net.params[lname][pname])
                assert got.shape == w.shape, (sub, lname, pname)
                assert np.array_equal(got, w), (sub, lname, pname)
        # and the served net actually answers from the TP weights
        out = net.forward(zeros_batch(net, 4), blob_names=["prob"])
        assert np.all(np.isfinite(np.asarray(out["prob"])))


@pytest.mark.chaos
def test_hot_swap_mid_traffic_chaos(net, tmp_path):
    """The acceptance chaos: continuous client traffic while (1) a GOOD
    new checkpoint hot-swaps in, (2) a CORRUPT newer one is rejected,
    (3) a NONFINITE-but-digest-valid one is rolled back by the canary.
    Zero dropped responses, zero corrupted (all finite, right shape),
    and the swap/rejection counters tell the story."""
    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=1)
    hb_path = str(tmp_path / "hb.json")
    cfg = ServeConfig(max_batch=4, max_wait_ms=2.0, outputs=("prob",),
                      checkpoint_dir=str(d), poll_interval_s=0.05,
                      heartbeat_path=hb_path, heartbeat_every_s=0.01)
    answered, bad = [], []
    stop = threading.Event()

    def client():
        i = 0
        while not stop.is_set():
            try:
                out = srv.infer(_example(i), timeout=30.0)
                p = out["prob"]
                if p.shape != (10,) or not np.isfinite(p).all() or \
                        abs(float(p.sum()) - 1.0) > 1e-3:
                    bad.append((i, p))
                answered.append(i)
            except Exception as e:
                bad.append((i, e))
            i += 1

    with InferenceServer(net, cfg) as srv:
        assert srv.manager.step == 1
        threads = [threading.Thread(target=client) for _ in range(4)]
        for t in threads:
            t.start()
        try:
            # (1) good swap lands without a hiccup
            _save_trainstate_like(net, d, step=2, scale=0.9)
            _wait(lambda: srv.manager.step == 2)
            # (2) corrupt snapshot: digest verify must reject it. Stage
            # the save OUTSIDE the watched dir and corrupt it there —
            # corrupting in place races the 50 ms poll, which can install
            # the still-clean step 3 before the byte flips (observed
            # flake). The rename publishes step 3 already-corrupt.
            stage = tmp_path / "stage"
            path = _save_trainstate_like(net, stage, step=3)
            npz = os.path.join(path, "state.npz")
            raw = bytearray(open(npz, "rb").read())
            raw[-32] ^= 0x01
            open(npz, "wb").write(bytes(raw))
            os.rename(path, os.path.join(str(d), os.path.basename(path)))
            fails = srv.manager.swap_failures
            _wait(lambda: srv.manager.swap_failures > fails)
            assert srv.manager.step == 2  # still on the good one
            assert "corrupt" in srv.manager.last_error
            # (3) digest-valid but poisoned weights: canary rolls back
            _save_trainstate_like(net, d, step=4, scale=np.nan)
            fails = srv.manager.swap_failures
            _wait(lambda: srv.manager.swap_failures > fails)
            assert srv.manager.step == 2
            assert "canary" in srv.manager.last_error
        finally:
            stop.set()
            for t in threads:
                t.join()
        assert not bad, bad[:3]
        assert len(answered) > 20  # real traffic flowed throughout
        assert srv.manager.swaps == 1
        assert srv.manager.swap_failures == 2
        st = srv.status()
        assert st["requests_failed"] == 0
        assert st["requests_ok"] >= len(answered)
    hb = read_heartbeat(hb_path)
    assert hb is not None and hb["role"] == "serve"
    assert hb["step"] == 2 and hb["rollbacks"] == 2


def _wait(cond, timeout=20.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, "condition never held"
        time.sleep(0.02)


# -- status surfaces ---------------------------------------------------------

def test_healthz_and_metrics_http(net):
    cfg = ServeConfig(max_batch=4, max_wait_ms=2.0, outputs=("prob",),
                      status_port=0)  # ephemeral port
    with InferenceServer(net, cfg) as srv:
        srv.infer(_example(0))
        host, port = srv.status_address
        h = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=10).read())
        assert h["status"] == "ok"
        # /metrics is now the Prometheus text exposition rendered from
        # the shared obs registry (same name schema as the train side);
        # the JSON vitals moved to /status
        resp = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10)
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode()
        # serve families carry the model label (multi-model routers share
        # one registry; a single-model server labels its sole lane)
        assert ('sparknet_serve_requests_total{model="default",'
                'outcome="ok"} 1') in text
        assert 'sparknet_serve_batch_fill_ratio{model="default"} 1' in text
        assert "sparknet_build_info{" in text
        s = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/status", timeout=10).read())
        assert s["requests_ok"] == 1
        assert s["batch_fill_ratio"] == 1.0  # one request, bucket 1
        assert s["p50_ms"] is not None
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=10)


def test_serve_cli_demo(tmp_path, capsys):
    """The `sparknet-serve` entry point end to end in --demo mode."""
    from sparknet_tpu.serve.app import main
    main(["--model", "lenet", "--outputs", "prob", "--max-batch", "4",
          "--demo", "12", "--workdir", str(tmp_path),
          "--heartbeat", str(tmp_path / "hb.json")])
    status = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert status["requests_ok"] == 12 and status["requests_failed"] == 0
    assert read_heartbeat(str(tmp_path / "hb.json"))["status"] == "done"


def test_future_type(server):
    assert isinstance(server.submit(_example(0)), Future)


def test_status_and_jsonl_carry_batch_size_hist(net, tmp_path):
    """The formed-batch size histogram (the bucket-ladder derivation
    input) lands in /status and — cumulative, with the model name — in
    the metrics JSONL at the metrics cadence."""
    from sparknet_tpu.serve import size_hist_from_jsonl
    from sparknet_tpu.utils.logger import Logger

    jsonl = str(tmp_path / "serve.jsonl")
    log = Logger(str(tmp_path / "l.txt"), echo=False, jsonl_path=jsonl)
    cfg = ServeConfig(max_batch=4, max_wait_ms=5.0, buckets=(1, 4),
                      outputs=("prob",), metrics_every_batches=1)
    with InferenceServer(net, cfg, logger=log) as srv:
        srv.infer(_example(0))                    # one size-1 batch
        for f in [srv.submit(_example(i)) for i in range(4)]:
            f.result(timeout=30.0)                # one size-4 batch
        st = srv.status()
        hist = st["batch_size_hist"]
        assert hist.get("1", 0) >= 1          # the lone first request
        # every real row is accounted for (burst formation may split)
        assert sum(int(k) * v for k, v in hist.items()) == 5
        assert sum(int(v) for v in hist.values()) == st["batches"]
        # the live meter agrees with the status copy
        assert srv.fill.size_hist() == {int(k): v
                                        for k, v in hist.items()}
    log.close()
    hists = size_hist_from_jsonl([jsonl])
    assert hists["default"] == {int(k): v for k, v in hist.items()}


def test_manager_loads_sharded_manifest_checkpoints(net, tmp_path):
    """r8: serve hot-swap reads SHARD-MANIFEST checkpoints — the layout
    training writes by default now — through the same restore_flat path,
    installing params bitwise equal to a monolithic save of the same
    state. (The manager never sees the layout: restore reassembles the
    exact flat map.)"""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sparknet_tpu.parallel.mesh import (fetch_state_shards, make_mesh)

    mesh = make_mesh(4)
    want = {lname: {pname: np.asarray(w) * 0.5 for pname, w in lp.items()}
            for lname, lp in net.params.items()}
    tree = {"params": {
        lname: {pname: jax.device_put(w[None],
                                      NamedSharding(mesh, P()))
                for pname, w in lp.items()}
        for lname, lp in want.items()}}
    d = tmp_path / "ck"
    ckpt.save_sharded(str(d), fetch_state_shards(tree, mesh), step=7)
    meta = json.load(open(d / "step-7" / "meta.json"))
    assert "shards" in meta  # really the manifest layout
    m = ModelManager(net, checkpoint_dir=str(d))
    assert m.load_initial() == 7
    for lname, lp in want.items():
        for pname, w in lp.items():
            np.testing.assert_array_equal(
                np.asarray(m.net.params[lname][pname]), w,
                err_msg=f"{lname}/{pname}")


# -- r12 freshness-era poll behavior -----------------------------------------

def test_manager_store_outage_is_store_error_not_corrupt(net, tmp_path,
                                                         monkeypatch):
    """A store that stops answering mid-poll is TRANSIENT trouble: it
    lands under swaps_total{outcome="store_error"}, cools down NO step
    (the checkpoint is probably fine), raises no swap_failures (a fleet
    rollout must not read an outage as a rejection), and reschedules the
    poll with full-jitter backoff inside one interval."""
    from fake_stores import bucket_store, stop_serving

    from sparknet_tpu.data import gcs
    from sparknet_tpu.obs import MetricsRegistry
    reg = MetricsRegistry()
    with bucket_store("gs") as (url, srv):
        d = f"{url}/ck"
        _save_trainstate_like(net, d, step=1)
        m = ModelManager(net, checkpoint_dir=d, poll_interval_s=5.0,
                         registry=reg)
        assert m.load_initial() == 1
        _save_trainstate_like(net, d, step=2)
        stop_serving(srv)
        # the stopped server still holds its port, so every attempt waits
        # out the client's timeout: at the production 60 s x 5 attempts
        # this one test slept 300 s of tier-1's 870. Same outage, same
        # retry loop, a clock that fits (this endpoint's client only).
        monkeypatch.setattr(gcs._shared_client(), "timeout", 0.5)
        monkeypatch.setattr(gcs, "BACKOFF_S", 0.01)
        t0 = time.monotonic()
        assert m.poll(now=t0) is False
    assert m.step == 1
    assert m.swap_failures == 0          # an outage is NOT a rejection
    assert m._bad == {}                  # and NO step went on cooldown
    assert 'outcome="store_error"} 1' in reg.render_prometheus()
    assert 'outcome="rejected"' not in reg.render_prometheus()
    # full-jitter: retry lands uniformly within ONE poll interval, not at
    # the bad_step_retry_s corruption cadence
    assert t0 <= m._next_poll <= t0 + 5.0


def test_manager_transient_load_error_then_same_step_installs(
        net, tmp_path, monkeypatch):
    """Store trouble during the checkpoint FETCH (listing worked) is
    classified the same way — and once the store answers again the very
    same step installs, because it was never cooled down."""
    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=1)
    m = ModelManager(net, checkpoint_dir=str(d), poll_interval_s=2.0)
    assert m.load_initial() == 1
    _save_trainstate_like(net, d, step=2)
    real, tries = ckpt.restore_flat, []

    def flaky(*a, **kw):
        if not tries:
            tries.append(1)
            raise TimeoutError("store busy")
        return real(*a, **kw)

    monkeypatch.setattr(ckpt, "restore_flat", flaky)
    t0 = time.monotonic()
    assert m.poll(now=t0) is False
    assert m.step == 1 and m.swap_failures == 0 and m._bad == {}
    assert "store" in m.last_error or "busy" in m.last_error
    assert t0 <= m._next_poll <= t0 + 2.0
    assert m.poll(now=m._next_poll + 1e-3) is True
    assert m.step == 2                   # no cooldown stood in the way


def test_poll_jitter_desynchronizes_replicas(net, tmp_path):
    """N replicas watching one store must not list it in lockstep: with
    poll_jitter set, one shared poll instant schedules N DISTINCT next
    polls, all within ±jitter of the interval. jitter=0 keeps the exact
    legacy cadence (back-compat default for ModelManager)."""
    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=1)
    mgrs = [ModelManager(net, checkpoint_dir=str(d), poll_interval_s=10.0,
                         poll_jitter=0.4) for _ in range(8)]
    for m in mgrs:
        m.poll(now=100.0)
    nexts = [m._next_poll for m in mgrs]
    assert all(106.0 <= t <= 114.0 for t in nexts)
    assert len(set(nexts)) >= 7          # spread, not lockstep
    legacy = ModelManager(net, checkpoint_dir=str(d), poll_interval_s=10.0)
    legacy.poll(now=100.0)
    assert legacy._next_poll == 110.0
    with pytest.raises(ValueError, match="poll_jitter"):
        ModelManager(net, checkpoint_dir=str(d), poll_jitter=1.0)


def test_poll_skips_torn_sharded_write_until_meta_commits(net, tmp_path):
    """Serve-side torn-checkpoint safety: a poll landing in the middle of
    a SHARDED save (array shards on disk, meta.json not yet) must treat
    the step as not-a-checkpoint — no install, no rejection, no cooldown.
    The moment the meta.json commit marker lands, the same poll path
    installs it whole."""
    import shutil

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from sparknet_tpu.parallel.mesh import fetch_state_shards, make_mesh

    d = tmp_path / "ck"
    _save_trainstate_like(net, d, step=1)
    m = ModelManager(net, checkpoint_dir=str(d), poll_interval_s=0.0)
    assert m.load_initial() == 1
    want = {ln: {pn: np.asarray(w) * 0.25 for pn, w in lp.items()}
            for ln, lp in net.params.items()}
    mesh = make_mesh(4)
    tree = {"params": {
        ln: {pn: jax.device_put(w[None], NamedSharding(mesh, P()))
             for pn, w in lp.items()}
        for ln, lp in want.items()}}
    stage = tmp_path / "stage"
    ckpt.save_sharded(str(stage), fetch_state_shards(tree, mesh), step=9)
    src, dst = stage / "step-9", d / "step-9"
    os.makedirs(dst)
    for f in os.listdir(src):
        if f != "meta.json":             # the commit marker stays out
            shutil.copy(src / f, dst / f)
    with pytest.warns(RuntimeWarning, match="meta.json"):
        assert m.poll() is False
    assert m.step == 1 and m.swap_failures == 0 and m._bad == {}
    shutil.copy(src / "meta.json", dst / "meta.json")
    assert m.poll() is True and m.step == 9
    for ln, lp in want.items():
        for pn, w in lp.items():
            np.testing.assert_array_equal(
                np.asarray(m.net.params[ln][pn]), w,
                err_msg=f"{ln}/{pn}")
