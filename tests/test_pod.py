"""Pod-scope observability (sparknet_tpu.obs.pod + obs.device): exposition
parse/merge (counter sums, gauge max/min, histogram pod sums), straggler
attribution over fake workers (http and heartbeat-file modes), the
/pod/status endpoint, the train loop's pod wiring, device telemetry, and
the compile counters (CompiledNet + serve bucket forwards)."""
import json
import os
import time
import urllib.request

import numpy as np
import pytest

from sparknet_tpu.obs import MetricsRegistry, StatusServer
from sparknet_tpu.obs.pod import (PodAggregator, flag_stragglers,
                                  format_pod_table, merge_expositions,
                                  parse_exposition, render_exposition,
                                  worker_heartbeat_path)
from sparknet_tpu.utils.health import mad_classify
from sparknet_tpu.utils.heartbeat import HeartbeatWriter


# -- exposition parse / merge / render ---------------------------------------

def _registry(rounds: int, round_s: float, lat=(0.05,)) -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("sparknet_train_rounds_total", "rounds").inc(rounds)
    reg.gauge("sparknet_train_round_seconds", "round").set(round_s)
    h = reg.histogram("sparknet_serve_request_latency_seconds", "lat",
                      buckets=(0.1, 1.0))
    for v in lat:
        h.observe(v)
    reg.counter("sparknet_health_rounds_total", "cls",
                labels=("cls",)).inc(rounds, cls="ok")
    return reg


def test_parse_roundtrip_scalars_and_histograms():
    reg = _registry(7, 0.25, lat=(0.05, 0.5, 5.0))
    fams = parse_exposition(reg.render_prometheus())
    assert fams["sparknet_train_rounds_total"].kind == "counter"
    assert fams["sparknet_train_rounds_total"].samples[()] == 7
    assert fams["sparknet_health_rounds_total"].samples[
        (("cls", "ok"),)] == 7
    h = fams["sparknet_serve_request_latency_seconds"].hists[()]
    assert h["count"] == 3 and h["sum"] == pytest.approx(5.55)
    assert h["le"]["0.1"] == 1 and h["le"]["1"] == 2 and h["le"]["+Inf"] == 3


def test_parse_escaped_labels():
    reg = MetricsRegistry()
    reg.gauge("g", labels=("path",)).set(1, path='a"b\\c\nd')
    fams = parse_exposition(reg.render_prometheus())
    assert fams["g"].samples[(("path", 'a"b\\c\nd'),)] == 1


def test_merge_counter_sums_gauge_minmax_hist_podsum():
    per = {"0": parse_exposition(_registry(10, 0.1).render_prometheus()),
           "1": parse_exposition(_registry(6, 0.4).render_prometheus())}
    merged = merge_expositions(per)
    text = render_exposition(merged)
    # counters: per-worker children + worker="pod" sum
    assert 'sparknet_train_rounds_total{worker="0"} 10' in text
    assert 'sparknet_train_rounds_total{worker="1"} 6' in text
    assert 'sparknet_train_rounds_total{worker="pod"} 16' in text
    assert 'sparknet_health_rounds_total{cls="ok",worker="pod"} 16' in text
    # gauges: max/min envelope labels
    assert 'sparknet_train_round_seconds{worker="max"} 0.4' in text
    assert 'sparknet_train_round_seconds{worker="min"} 0.1' in text
    # histograms: pod-summed cumulative buckets
    assert ('sparknet_serve_request_latency_seconds_count{worker="pod"} 2'
            in text)
    # the merged text is itself parseable (round trip)
    again = parse_exposition(text)
    assert again["sparknet_train_rounds_total"].samples[
        (("worker", "pod"),)] == 16


def test_merge_kind_conflict_degrades_family_not_scrape():
    a = MetricsRegistry()
    a.counter("m").inc(3)
    b = MetricsRegistry()
    b.gauge("m").set(9)
    merged = merge_expositions(
        {"0": parse_exposition(a.render_prometheus()),
         "1": parse_exposition(b.render_prometheus())})
    # first-seen kind (worker 0's counter) wins; worker 1's sample skipped
    assert merged["m"].kind == "counter"
    assert merged["m"].samples[(("worker", "pod"),)] == 3
    assert (("worker", "1"),) not in merged["m"].samples


# -- straggler classification ------------------------------------------------

def test_mad_classify_flags_and_floor():
    med, sigma, flags = mad_classify([1.0, 1.0, 1.0, 10.0])
    assert flags == [False, False, False, True]
    assert med == 1.0 and sigma > 0  # floored despite MAD == 0
    # equal values: nothing flagged, ever
    assert mad_classify([2.0] * 8)[2] == [False] * 8
    # n < 3 never flags (MAD is degenerate)
    assert mad_classify([1.0, 100.0])[2] == [False, False]


def test_flag_stragglers_two_worker_ratio_rule():
    # 2 workers: MAD cannot fire; the ratio rule names the slower one
    med, skew, flagged = flag_stragglers({"0": 0.1, "1": 1.0})
    assert flagged == {"1"}
    assert skew == pytest.approx(1.0 - med)
    # clean 2-worker pod: nothing flagged
    assert flag_stragglers({"0": 0.1, "1": 0.11})[2] == set()
    # 3+ workers use median+MAD
    assert flag_stragglers({"0": 1.0, "1": 1.0, "2": 10.0})[2] == {"2"}
    assert flag_stragglers({"0": 1.0, "1": 1.0, "2": 1.0})[2] == set()


# -- the aggregator: http mode -----------------------------------------------

@pytest.fixture
def two_workers():
    """Two in-process fake workers behind real StatusServers; worker 1 is
    a 10x straggler. Yields (urls, vitals) with servers torn down after."""
    vitals = [{"role": "train", "round": 10, "status": "ok", "loss": 1.0,
               "round_s": 0.1, "data_wait_s": 0.001, "rollbacks": 0},
              {"role": "train", "round": 9, "status": "ok", "loss": 1.2,
               "round_s": 1.0, "data_wait_s": 0.6, "rollbacks": 0}]
    regs = [_registry(10, 0.1), _registry(9, 1.0)]
    servers = [StatusServer(0, reg, status=(lambda v=v: dict(v)))
               for reg, v in zip(regs, vitals)]
    urls = {str(i): f"http://{s.address[0]}:{s.address[1]}"
            for i, s in enumerate(servers)}
    try:
        yield urls, vitals
    finally:
        for s in servers:
            s.stop()


def test_aggregator_http_merge_and_straggler(two_workers):
    urls, vitals = two_workers
    agg = PodAggregator(workers=urls, min_refresh_s=0.0)
    status = agg.pod_status()
    assert status["n_workers"] == 2 and status["n_alive"] == 2
    assert status["stragglers"] == ["1"]
    assert status["straggler_rounds"] == {"1": 1}
    assert status["max_round"] == 10 and status["min_round"] == 9
    assert status["round_skew_s"] == pytest.approx(1.0 - 0.55)
    text = agg.render()
    assert 'sparknet_train_rounds_total{worker="pod"} 19' in text
    assert 'sparknet_train_round_seconds{worker="max"} 1' in text
    assert "sparknet_pod_round_skew_seconds" in text
    assert 'sparknet_pod_straggler_rounds_total{worker="1"} 1' in text
    assert 'sparknet_pod_worker_up{worker="1"} 1' in text
    # same reported round again -> no double count
    agg.collect(force=True)
    assert agg.registry.counter(
        "sparknet_pod_straggler_rounds_total",
        labels=("worker",)).value(worker="1") == 1
    # round advances, still slow -> counts again
    vitals[1]["round"] = 10
    agg.collect(force=True)
    assert agg.registry.counter(
        "sparknet_pod_straggler_rounds_total",
        labels=("worker",)).value(worker="1") == 2
    # the audit trail names the worker and the magnitude
    log = agg.pod_status()["straggler_log"]
    assert log and log[-1]["worker"] == "1"
    assert "STRAGGLER" in format_pod_table(agg.pod_status())


def test_aggregator_clean_two_worker_run_reports_zero(two_workers):
    urls, vitals = two_workers
    vitals[1]["round_s"] = 0.1  # same speed
    agg = PodAggregator(workers=urls, min_refresh_s=0.0)
    status = agg.pod_status()
    assert status["stragglers"] == []
    assert status["straggler_rounds"] == {}
    assert status["straggler_log"] == []
    assert agg.registry.counter(
        "sparknet_pod_straggler_rounds_total",
        labels=("worker",)).value(worker="1") is None


def test_aggregator_dead_worker_degrades(two_workers):
    urls, _ = two_workers
    urls = dict(urls, **{"2": "http://127.0.0.1:1/"})  # nothing listening
    agg = PodAggregator(workers=urls, min_refresh_s=0.0, timeout_s=0.5)
    status = agg.pod_status()
    assert status["n_workers"] == 3 and status["n_alive"] == 2
    dead = [w for w in status["workers"] if w["worker"] == "2"][0]
    assert not dead["alive"] and dead["error"]
    assert 'sparknet_pod_worker_up{worker="2"} 0' in agg.render()


def test_aggregator_http_hung_loop_reads_stale(two_workers):
    """http mode freshness comes from the worker LOOP's beat_ts stamp:
    a hung round loop whose HTTP daemon thread still answers must be
    reported stale, not alive (the file mode already had this via the
    heartbeat's t)."""
    urls, vitals = two_workers
    vitals[1]["beat_ts"] = time.time() - 3600  # loop last flushed 1h ago
    vitals[0]["beat_ts"] = time.time()
    agg = PodAggregator(workers=urls, stale_after_s=60.0,
                        min_refresh_s=0.0)
    status = agg.pod_status()
    assert status["n_alive"] == 1
    hung = [w for w in status["workers"] if w["worker"] == "1"][0]
    assert not hung["alive"] and "stale" in hung["error"]
    # and a stale worker's round time is excluded from attribution
    assert status["stragglers"] == []


def test_heartbeat_bucket_roundtrip_and_flush(monkeypatch):
    """gs:// heartbeats: the beat is a non-blocking handoff to a writer
    thread; flush() bounds the wait and the aggregator reads the record
    back through the same native store client."""
    import sys
    sys.path.insert(0, os.path.dirname(__file__))
    from fake_stores import serve_gcs, stop_serving
    from sparknet_tpu.utils.heartbeat import read_heartbeat

    srv, endpoint = serve_gcs()
    monkeypatch.setenv("STORAGE_EMULATOR_HOST", endpoint)
    monkeypatch.setenv("no_proxy", "*")
    try:
        path = worker_heartbeat_path("gs://bkt/pod", 1)
        hb = HeartbeatWriter(path, interval_s=0.0)
        assert hb.beat(4, status="ok", worker=1, round_s=0.2)
        hb.flush()
        rec = read_heartbeat(path)
        assert rec and rec["step"] == 4 and rec["round_s"] == 0.2
        agg = PodAggregator(pod_dir="gs://bkt/pod", min_refresh_s=0.0)
        status = agg.pod_status()
        assert status["n_workers"] == 1
        assert status["workers"][0]["worker"] == "1"
        assert status["workers"][0]["round_s"] == 0.2
    finally:
        stop_serving(srv)


def test_pod_status_server_endpoints(two_workers):
    urls, _ = two_workers
    agg = PodAggregator(workers=urls, min_refresh_s=0.0)
    srv = agg.serve(0)
    try:
        host, port = srv.address
        s = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/pod/status", timeout=10).read())
        assert s["role"] == "pod" and s["stragglers"] == ["1"]
        m = urllib.request.urlopen(
            f"http://{host}:{port}/metrics", timeout=10)
        assert m.headers["Content-Type"].startswith("text/plain")
        text = m.read().decode()
        assert 'sparknet_train_rounds_total{worker="pod"} 19' in text
        hz = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/healthz", timeout=10).read())
        assert hz["status"] == "ok" and hz["stragglers"] == ["1"]
    finally:
        agg.stop()


# -- the aggregator: heartbeat-file mode -------------------------------------

def test_aggregator_file_mode_flags_injected_straggler(tmp_path):
    pod_dir = str(tmp_path / "pod")
    times = [0.1, 0.1, 1.5]  # worker 2 injected slow
    for i, round_s in enumerate(times):
        hb = HeartbeatWriter(worker_heartbeat_path(pod_dir, i))
        hb.beat(5, status="ok", worker=i, round_s=round_s,
                data_wait_s=0.001, last_loss=1.0)
    agg = PodAggregator(pod_dir=pod_dir, min_refresh_s=0.0)
    status = agg.pod_status()
    assert status["n_workers"] == 3 and status["n_alive"] == 3
    assert status["stragglers"] == ["2"]
    assert status["straggler_rounds"] == {"2": 1}
    assert [w["round"] for w in status["workers"]] == [5, 5, 5]
    # file mode still renders a pod exposition (aggregator registry)
    text = agg.render()
    assert "sparknet_pod_workers 3" in text
    assert 'sparknet_pod_worker_round_seconds{worker="2"} 1.5' in text


def test_aggregator_surfaces_per_model_serve_rows(tmp_path):
    """A serve-role heartbeat's per-model vitals rows ride through the
    aggregator into /pod/status worker rows and the podview table —
    multi-model straggler attribution reads per model, not just per
    process."""
    pod_dir = str(tmp_path / "pod")
    HeartbeatWriter(worker_heartbeat_path(pod_dir, 0)).beat(
        7, status="ok", round_s=0.1)
    HeartbeatWriter(worker_heartbeat_path(pod_dir, 1), role="serve").beat(
        42, status="ok",
        models={"mnist": {"step": 42, "freshness_s": 3.25, "step_lag": 1,
                          "queue_depth": 3, "p99_ms": 8.5,
                          "requests_ok": 100, "requests_shed": 2,
                          "swaps": 1},
                "cifar": {"step": 9, "queue_depth": 0, "p99_ms": 30.1,
                          "requests_ok": 10}})
    agg = PodAggregator(pod_dir=pod_dir, min_refresh_s=0.0)
    status = agg.pod_status()
    serve = [w for w in status["workers"] if w["worker"] == "1"][0]
    assert serve["role"] == "serve"
    assert set(serve["models"]) == {"mnist", "cifar"}
    assert serve["models"]["mnist"]["p99_ms"] == 8.5
    # r12: checkpoint freshness and step lag ride the heartbeat row, so
    # podview shows per-replica staleness WITHOUT scraping /metrics
    assert serve["models"]["mnist"]["freshness_s"] == 3.25
    assert serve["models"]["mnist"]["step_lag"] == 1
    train = [w for w in status["workers"] if w["worker"] == "0"][0]
    assert "models" not in train  # train rows stay exactly as before
    table = format_pod_table(status)
    assert "model=mnist" in table and "p99=8.5ms" in table
    assert "fresh=3.25s" in table and "lag=1" in table
    assert "model=cifar" in table and "shed=2" in table
    cifar = [ln for ln in table.splitlines() if "model=cifar" in ln][0]
    assert "fresh=" not in cifar      # no freshness reported = omitted


def test_aggregator_file_mode_stale_worker_named(tmp_path):
    pod_dir = str(tmp_path / "pod")
    for i in range(2):
        HeartbeatWriter(worker_heartbeat_path(pod_dir, i)).beat(
            3, status="ok", round_s=0.1)
    # age worker 1's beat far past the staleness bound
    p1 = worker_heartbeat_path(pod_dir, 1)
    rec = json.load(open(p1))
    rec["t"] = time.time() - 3600
    json.dump(rec, open(p1, "w"))
    agg = PodAggregator(pod_dir=pod_dir, stale_after_s=60.0,
                        min_refresh_s=0.0)
    status = agg.pod_status()
    assert status["n_alive"] == 1
    stale = [w for w in status["workers"] if w["worker"] == "1"][0]
    assert not stale["alive"] and "stale" in stale["error"]


# -- train-loop wiring (single process = 1-worker pod) -----------------------

@pytest.fixture(scope="module")
def pod_trained(tmp_path_factory):
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet

    root = str(tmp_path_factory.mktemp("pod_train"))
    r = np.random.default_rng(0)
    ds = ArrayDataset({
        "data": r.standard_normal((128, 1, 28, 28)).astype(np.float32),
        "label": r.integers(0, 10, (128, 1)).astype(np.int32)})
    cfg = RunConfig(model="lenet", n_devices=1, local_batch=16, tau=2,
                    max_rounds=3, eval_every=0, workdir=root,
                    status_port=0, pod_dir=os.path.join(root, "pod"),
                    pod_port=0, heartbeat_every_s=0.0)
    scraped = {}

    def hook(rnd, state):
        if rnd == 2:
            host, port = cfg.status_address
            scraped["metrics"] = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10).read().decode()
            host, port = cfg.pod_address
            # the loop hands a round's record (and with it the beat) to its
            # collector a round late and off this thread: ask until the
            # first beat is on disk (an empty view is never cached)
            deadline = time.monotonic() + 30.0
            while True:
                scraped["pod"] = json.loads(urllib.request.urlopen(
                    f"http://{host}:{port}/pod/status", timeout=10).read())
                if scraped["pod"]["n_workers"] or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            scraped["pod_metrics"] = urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10).read().decode()

    log = Logger(os.path.join(root, "l.txt"), echo=False,
                 jsonl_path=os.path.join(root, "m.jsonl"))
    train(cfg, lenet(batch=16), ds, None, logger=log, round_hook=hook)
    log.close()
    return {"cfg": cfg, "root": root, "scraped": scraped}


def test_train_worker_exports_straggler_inputs(pod_trained):
    text = pod_trained["scraped"]["metrics"]
    for name in ("sparknet_train_round_seconds",
                 "sparknet_train_data_wait_seconds",
                 "sparknet_train_round_compiled_variants",
                 "sparknet_device_live_arrays",
                 # the r9 cache_hit label rides every compile event
                 'sparknet_compile_events_total{what="net",cache_hit='):
        assert name in text, f"missing {name} in worker /metrics"


def test_train_pod_endpoint_sees_worker(pod_trained):
    pod = pod_trained["scraped"]["pod"]
    assert pod["n_workers"] == 1 and pod["n_alive"] == 1
    w = pod["workers"][0]
    assert w["worker"] == "0" and w["round_s"] is not None
    assert w["data_wait_s"] is not None
    assert pod["stragglers"] == []  # 1 worker: nothing to attribute
    assert "sparknet_pod_workers 1" in pod_trained["scraped"]["pod_metrics"]


def test_train_pod_heartbeat_file_schema(pod_trained):
    hb = json.load(open(worker_heartbeat_path(
        pod_trained["cfg"].pod_dir, 0)))
    assert hb["role"] == "train" and hb["worker"] == 0
    assert hb["status"] == "done"  # final forced beat
    assert hb["round_s"] is not None and hb["data_wait_s"] is not None


# -- device telemetry + compile counters -------------------------------------

def test_device_telemetry_samples_without_accelerator_stats():
    from sparknet_tpu.obs.device import DeviceTelemetry

    reg = MetricsRegistry()
    tel = DeviceTelemetry(reg)
    tel.sample()  # CPU: memory_stats() is None -> only live arrays
    assert reg.gauge("sparknet_device_live_arrays").value() is not None
    # a device whose memory_stats raises must not break the sample
    class Boom:
        platform, id = "boom", 0

        def memory_stats(self):
            raise RuntimeError("no stats")
    DeviceTelemetry(reg, devices=[Boom()]).sample()


def test_device_telemetry_memory_gauges_from_stats():
    from sparknet_tpu.obs.device import DeviceTelemetry

    class Fake:
        platform, id = "tpu", 3

        def memory_stats(self):
            return {"bytes_in_use": 1024, "peak_bytes_in_use": 4096,
                    "bytes_limit": 1 << 30}
    reg = MetricsRegistry()
    DeviceTelemetry(reg, devices=[Fake()]).sample()
    text = reg.render_prometheus()
    assert 'sparknet_device_hbm_bytes_in_use{device="tpu:3"} 1024' in text
    assert 'sparknet_device_hbm_peak_bytes{device="tpu:3"} 4096' in text


def _compile_event_count(reg, what):
    snap = reg.snapshot()["sparknet_compile_events_total"]
    return sum(v for key, v in snap["values"].items() if key[0] == what)


def test_compile_events_replayed_into_late_registry():
    from sparknet_tpu.model.net import CompiledNet
    from sparknet_tpu.obs.device import (attach_compile_metrics,
                                         compile_stats)
    from sparknet_tpu.zoo import lenet

    CompiledNet.compile(lenet(batch=2))  # happens BEFORE the registry
    reg = MetricsRegistry()
    attach_compile_metrics(reg)
    before = _compile_event_count(reg, "net")
    assert before >= 1  # the history replayed
    CompiledNet.compile(lenet(batch=2))  # and live events keep flowing
    assert _compile_event_count(reg, "net") == before + 1
    # the seconds histogram carries REAL compile cost only: memo/cache
    # hits count events but never dilute the duration percentiles
    snap = reg.snapshot()["sparknet_compile_seconds"]
    stats = compile_stats()["net"]
    assert snap["values"][("net",)]["count"] == \
        stats["events"] - stats["cache_hits"]


def test_compile_events_cache_hit_labeling():
    """The r9 cache_hit label end to end: a region doing FRESH XLA work
    records cache_hit="false" (a cold compile — with no persistent cache
    there is nothing to hit), an identical spec recompile records
    cache_hit="true" (the CompiledNet memo: zero fresh work), and the
    Prometheus exposition carries both label values."""
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.model.net import CompiledNet
    from sparknet_tpu.obs.device import (attach_compile_metrics,
                                         compile_stats, timed_compile)
    from sparknet_tpu.zoo import lenet

    what = f"test_site_{time.time_ns()}"  # unique event site
    salt = time.time_ns() % 89
    f = jax.jit(lambda x: x * 3 + salt)   # a jit nobody compiled before
    with timed_compile(what):
        f(jnp.ones((2,)))                 # cold: fresh XLA compile
    assert compile_stats()[what]["cache_misses"] == 1
    with timed_compile(what):
        f(jnp.ones((2,)))                 # cached executable: no work
    assert compile_stats()[what]["cache_hits"] == 1
    # identical spec recompile -> memo hit recorded as a hit
    CompiledNet.compile(lenet(batch=2))
    before = compile_stats()["net"]["cache_hits"]
    CompiledNet.compile(lenet(batch=2))
    assert compile_stats()["net"]["cache_hits"] == before + 1
    # the exposition carries the label, both values
    reg = MetricsRegistry()
    attach_compile_metrics(reg)
    text = reg.render_prometheus()
    assert (f'sparknet_compile_events_total{{what="{what}",'
            f'cache_hit="false"}} 1') in text
    assert (f'sparknet_compile_events_total{{what="{what}",'
            f'cache_hit="true"}} 1') in text


def test_serve_bucket_recompile_counter_steady_state():
    """The serve recompile counter equals len(buckets) once every bucket
    has been exercised, and STAYS there — steady state means zero compile
    churn, and churn past len(buckets) is the metric's alarm condition."""
    from sparknet_tpu.net_api import JaxNet
    from sparknet_tpu.serve import InferenceServer, ServeConfig
    from sparknet_tpu.zoo import lenet

    net = JaxNet(lenet(batch=4))
    cfg = ServeConfig(max_batch=4, max_wait_ms=1.0, buckets=(1, 2, 4),
                      outputs=("prob",), metrics_every_batches=0)
    x = {"data": np.zeros((28, 28, 1), np.float32)}
    with InferenceServer(net, cfg) as srv:
        c = srv.registry.counter("sparknet_serve_bucket_compiles_total",
                                 labels=("model",))
        srv.infer(x)                       # bucket 1
        futs = [srv.submit(x) for _ in range(4)]
        for f in futs:
            f.result(timeout=30)           # bucket 4 (and maybe others)
        futs = [srv.submit(x) for _ in range(2)]
        for f in futs:
            f.result(timeout=30)
        # drive until all three buckets have been seen at least once
        deadline = time.monotonic() + 30
        while len(srv._compiled_buckets) < 3 and \
                time.monotonic() < deadline:
            n = min(b for b in (1, 2, 4)
                    if b not in srv._compiled_buckets)
            for f in [srv.submit(x) for _ in range(n)]:
                f.result(timeout=30)
        assert srv._compiled_buckets == {1, 2, 4}
        assert c.value(model="default") == 3  # == len(buckets)
        # steady state: more traffic adds NO compile events
        for f in [srv.submit(x) for _ in range(4)]:
            f.result(timeout=30)
        srv.infer(x)
        assert c.value(model="default") == 3
        assert srv.status()["bucket_compiles"] == 3


# -- podview CLI -------------------------------------------------------------

def test_podview_selfcheck():
    from sparknet_tpu.obs.pod import main
    assert main(["--selfcheck"]) == 0


def test_podview_file_mode_cli(tmp_path, capsys):
    pod_dir = str(tmp_path / "pod")
    for i, rs in enumerate((0.1, 0.1, 2.0)):
        HeartbeatWriter(worker_heartbeat_path(pod_dir, i)).beat(
            7, status="ok", round_s=rs, last_loss=0.5)
    from sparknet_tpu.obs.pod import main
    assert main(["--pod-dir", pod_dir, "--json"]) == 0
    s = json.loads(capsys.readouterr().out)
    assert s["n_workers"] == 3 and s["stragglers"] == ["2"]


def test_aggregator_file_mode_vanished_worker_surfaced(tmp_path):
    """Mid-run membership change: a worker whose heartbeat FILE vanishes
    between scrapes must surface as worker_up=0 / candidate-dead, not
    silently drop out of the pod view and the straggler population."""
    pod_dir = str(tmp_path / "pod")
    for i in range(3):
        HeartbeatWriter(worker_heartbeat_path(pod_dir, i)).beat(
            4, status="ok", round_s=0.1)
    agg = PodAggregator(pod_dir=pod_dir, min_refresh_s=0.0)
    assert agg.pod_status()["n_alive"] == 3
    os.remove(worker_heartbeat_path(pod_dir, 1))  # vanishes, not stale
    status = agg.pod_status()
    assert status["n_workers"] == 3  # sticky: still in the population
    assert status["n_alive"] == 2
    assert status["candidate_dead"] == ["1"]
    gone = [w for w in status["workers"] if w["worker"] == "1"][0]
    assert not gone["alive"] and "unreadable" in gone["error"]
    assert 'sparknet_pod_worker_up{worker="1"} 0' in agg.render()
    # the survivors' straggler stats still work over the live population
    assert status["stragglers"] == []


def test_aggregator_surfaces_membership_epoch(tmp_path):
    """Elastic runs stamp membership_epoch on their beats; /pod/status
    reports the newest epoch any worker saw (resizes visible on a
    scrape, no JSONL required)."""
    pod_dir = str(tmp_path / "pod")
    HeartbeatWriter(worker_heartbeat_path(pod_dir, 0)).beat(
        7, status="ok", round_s=0.1, membership_epoch=2, n_members=3)
    HeartbeatWriter(worker_heartbeat_path(pod_dir, 1)).beat(
        6, status="ok", round_s=0.1, membership_epoch=1, n_members=4)
    status = PodAggregator(pod_dir=pod_dir,
                           min_refresh_s=0.0).pod_status()
    assert status["membership_epoch"] == 2
    by_id = {w["worker"]: w for w in status["workers"]}
    assert by_id["0"]["membership_epoch"] == 2
    assert by_id["1"]["membership_epoch"] == 1
