"""Start-up's account of itself: the compile log (`utils/compile_cache.py`:
one entry an executable built or fetched, stage by stage, with the persistent
cache's verdict and the step the program stamps on it), the kept start-up spans
(`obs.trace.startup_span`) and what puts them side by side
(`obs.device.startup_report`, the train loop's `start-up:` line and `/status`
`startup`). All on the CPU: counts, verdicts and host seconds, never a device
time.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sparknet_tpu.obs import device as obs_device
from sparknet_tpu.obs import trace as obs_trace
from sparknet_tpu.obs.registry import MetricsRegistry
from sparknet_tpu.utils import compile_cache
from sparknet_tpu.utils.compile_cache import compile_log, track_compiles

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a tiny trainer's first round, then a second batch shape two rounds later,
#: in a process of its own: what it recorded, as one JSON line
CHILD = r"""
import json, sys
import jax, numpy as np
from sparknet_tpu.utils.compile_cache import init_compile_cache, compile_log
init_compile_cache()
from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
from sparknet_tpu.obs import device, trace
from sparknet_tpu.obs.registry import MetricsRegistry
from sparknet_tpu.utils.config import RunConfig
cfg = RunConfig.from_dict({"model": "lenet", "tau": 2, "local_batch": 4,
                           "n_devices": 1, "precision": "float32"})
trainer = build_trainer(cfg, resolve_spec(cfg))
state = trainer.init_state(jax.random.PRNGKey(0))
r = np.random.default_rng(0)
def batch(n):
    return {"data": r.standard_normal((2, n, 28, 28, 1)).astype(np.float32),
            "label": r.integers(0, 10, (2, n, 1)).astype(np.int32)}
for n in (4, 4, 8):
    state, loss = trainer.train_round(state, batch(n), jax.random.PRNGKey(1))
    float(loss)
registry = MetricsRegistry()
device.attach_compile_metrics(registry)
events = registry.snapshot()["sparknet_compile_events_total"]["values"]
print("RECORD " + json.dumps({
    "log": compile_log(), "spans": trace.startup_spans(),
    "import_t0": trace.import_stamp(), "tracer": trace.active_tracer() is None,
    "stats": device.compile_stats()["train_round"],
    "events": sorted("|".join(k) for k in events if k[0] == "train_round"),
    "line": device.startup_line(device.startup_report())}))
"""


def _child(cache_dir) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    # every byte is read: a cache hit makes XLA's loader print kilobytes
    out = subprocess.run([sys.executable, "-c", CHILD], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RECORD ")]
    return json.loads(line[-1][len("RECORD "):])


@pytest.fixture(scope="module")
def cold_then_warm(tmp_path_factory):
    cache = tmp_path_factory.mktemp("startup-cache")
    return _child(cache), _child(cache)


def _rounds(record):
    return [e for e in record["log"] if e["what"] == "train_round"]


def test_the_rounds_compile_is_one_entry_with_its_three_stages(cold_then_warm):
    cold, _ = cold_then_warm
    first = _rounds(cold)[0]
    assert first["trace_s"] > 0 and first["lower_s"] > 0 and first["backend_s"] > 0
    assert first["t1"] - first["t0"] >= first["backend_s"]
    assert first["thread"] == "MainThread"
    # nested jitted functions report stages of their own inside the round's:
    # the entry holds the round's, which is the longest of them
    nested = [e for e in cold["log"] if e["t0"] >= first["t0"]
              and e["t1"] <= first["t1"] and e is not first]
    assert not nested, "nothing else closes inside the round's own compile"
    assert first["trace_s"] + first["lower_s"] + first["backend_s"] \
        <= (first["t1"] - first["t0"]) * 1.05 + 0.05


def test_cold_is_a_miss_and_warm_a_hit_with_its_retrieval(cold_then_warm):
    cold, warm = cold_then_warm
    assert [e["cache"] for e in _rounds(cold)] == ["miss", "miss"]
    assert [e["cache"] for e in _rounds(warm)] == ["hit", "hit"]
    assert all(e["retrieval_s"] is None and e["saved_s"] is None
               for e in _rounds(cold))
    assert all(e["retrieval_s"] > 0 and e["saved_s"] is not None
               for e in _rounds(warm))
    # tracing and lowering are paid again on a hit: no cache saves them
    assert all(e["trace_s"] > 0 and e["lower_s"] > 0 for e in _rounds(warm))
    # warm, nothing misses: every executable of the process was fetched
    assert {e["cache"] for e in warm["log"]} == {"hit"}


def test_a_second_input_shape_is_an_entry_with_its_step(cold_then_warm):
    for record in cold_then_warm:
        assert [e["step"] for e in _rounds(record)] == [0, 2]
        assert all("step" not in e for e in record["log"]
                   if e["what"] != "train_round")


def test_compile_stats_and_a_late_registry_show_the_round(cold_then_warm):
    cold, warm = cold_then_warm
    assert cold["stats"]["events"] == 2 and cold["stats"]["cache_misses"] == 2
    assert warm["stats"]["cache_hits"] == 2
    for record in cold_then_warm:
        sums = {k: sum(e[k] for e in _rounds(record))
                for k in ("trace_s", "lower_s", "backend_s")}
        assert {k: record["stats"][k] for k in sums} == pytest.approx(sums)
        assert record["stats"]["seconds"] == pytest.approx(sum(sums.values()))
    assert cold["events"] == ["train_round|false"]
    assert warm["events"] == ["train_round|true"]


def test_kept_spans_exist_with_no_tracer_on_and_lie_before_the_round(cold_then_warm):
    cold, _ = cold_then_warm
    assert cold["tracer"], "no tracer was on"
    by = {s["name"]: s for s in cold["spans"]}
    assert {"resolve_spec", "build_trainer", "compile_net", "trainer_init",
            "state_from_params"} <= set(by)
    assert by["compile_net"]["parent"] == by["build_trainer"]["id"]
    assert by["trainer_init"]["parent"] == by["build_trainer"]["id"]
    assert by["build_trainer"]["parent"] is None
    first = _rounds(cold)[0]
    assert cold["import_t0"] < by["resolve_spec"]["t0"] < by["build_trainer"]["t0"]
    assert by["build_trainer"]["t1"] <= by["state_from_params"]["t0"]
    assert by["state_from_params"]["t1"] <= first["t0"] < first["t1"]
    assert cold["line"].startswith("start-up: import ")
    assert "train_round compile" in cold["line"] and "cache miss" in cold["line"]
    assert "cache hit" in cold_then_warm[1]["line"]


# -- in this process ---------------------------------------------------------

def _fresh_jit():
    salt = time.time_ns() % 1_000_003  # a program nobody compiled before
    fn = lambda x: jnp.tanh(x * 5 - salt) + salt  # the salt is in the program
    fn.__name__ = fn.__qualname__ = "salted"
    return jax.jit(fn)


def test_track_compiles_is_a_view_of_this_threads_entries():
    f, g = _fresh_jit(), _fresh_jit()
    other = threading.Thread(target=lambda: g(jnp.ones((41,))), name="other")
    with track_compiles() as region:
        f(jnp.ones((37,)))
        other.start()
        other.join(timeout=120)
    assert not other.is_alive()
    mine = [e for e in region.entries if e["what"] == "salted"]
    assert len(mine) == 1 and mine[0]["thread"] == threading.current_thread().name
    assert region.xla_compiles == len(region.entries) >= 1
    assert region.cache_hit is False and region.cache_misses >= 1
    theirs = [e for e in compile_log() if e["thread"] == "other"]
    assert theirs and all(e not in region.entries for e in theirs)
    with track_compiles() as again:
        f(jnp.ones((37,)))
    assert again.xla_compiles == 0 and again.entries == () and again.cache_hit


def test_the_log_keeps_the_first_counts_the_rest_and_holds_the_newest(monkeypatch):
    import collections
    monkeypatch.setattr(compile_cache, "_log", [])
    monkeypatch.setattr(compile_cache, "_newest", collections.deque(maxlen=2))
    monkeypatch.setattr(compile_cache, "MAX_ENTRIES", 2)
    before = compile_cache.compile_log_dropped()
    with track_compiles() as region:
        for n in range(43, 48):
            _fresh_jit()(jnp.ones((n,)))
        closed = compile_cache._closed - region._mark
    kept = compile_log()
    first = kept[0]["seq"]
    # a start-up's entries stay: the first two, then past the gap the newest two
    assert [e["seq"] for e in kept] == [first, first + 1, first + closed - 2,
                                        first + closed - 1]
    assert compile_cache.compile_log_dropped() == before + closed - 4 >= before + 1
    # the region saw the newest entries, as many as are held
    assert region.xla_compiles == 2 and region.cache_hit is False
    # and the operator's count of later compiles does not stop at the log's size
    report = obs_device.startup_report(until=kept[1]["t1"] + 1e-9)
    assert len(report["compiles"]) == 2
    assert report["later_compiles"] == 2 + report["dropped_compiles"]
    assert report["newest_compile"]["what"] == kept[-1]["what"]


class _Clock:
    """`time.perf_counter()` for the listeners alone: a test says when."""

    def __init__(self, monkeypatch):
        self.now = 1000.0
        fake = type(sys)("time")
        fake.perf_counter = lambda: self.now
        monkeypatch.setattr(compile_cache, "time", fake)

    def stage(self, event, seconds, name, gap=0.01):
        self.now += gap + seconds  # jax reports a stage when it has ended
        compile_cache._on_duration(event, seconds, fun_name=name)


TRACE, LOWER, BACKEND = (compile_cache._TRACE_EVENT, compile_cache._LOWER_EVENT,
                         compile_cache._BACKEND_COMPILE_EVENT)


def test_an_entry_takes_its_own_stages_and_never_the_nested_ones(monkeypatch):
    """The listener alone, fed what jax reports for an outer function with
    two jitted ones traced inside it."""
    clock = _Clock(monkeypatch)
    t0 = clock.now + 0.01
    clock.stage(TRACE, 0.25, "inner_a", gap=0.5)   # both inside outer_fn's trace
    clock.stage(TRACE, 0.5, "inner_b", gap=0.5)
    clock.stage(TRACE, 2.0, "outer_fn", gap=-1.74)
    clock.stage(LOWER, 1.0, "jit(outer_fn)")
    compile_cache._on_event(compile_cache._CACHE_USED_EVENT)
    compile_cache._on_event(compile_cache._CACHE_HIT_EVENT)
    compile_cache._on_duration(compile_cache._SAVED_EVENT, 30.0)
    compile_cache._on_duration(compile_cache._RETRIEVAL_EVENT, 0.125)
    clock.stage(BACKEND, 0.75, "jit(outer_fn)")
    entry = compile_log()[-1]
    assert (entry["what"], entry["trace_s"], entry["lower_s"], entry["backend_s"]) \
        == ("outer_fn", 2.0, 1.0, 0.75)
    assert (entry["cache"], entry["retrieval_s"], entry["saved_s"]) == ("hit", 0.125, 30.0)
    assert (entry["t0"], entry["t1"]) == pytest.approx((t0, clock.now))
    # the nested names' stages are gone with the entry that enclosed them, and
    # the next executable on this thread starts clean: not consulted is "off"
    assert not {"inner_a", "inner_b", "outer_fn"} & set(compile_cache._gathering().stages)
    clock.stage(BACKEND, 0.5, "jit(inner_a)")
    after = compile_log()[-1]
    assert (after["what"], after["trace_s"], after["cache"], after["retrieval_s"]) \
        == ("inner_a", 0.0, "off", None)


@pytest.mark.parametrize("left_behind", ["lowered", "traced"])
def test_a_stage_no_compile_followed_is_dropped_not_summed(monkeypatch, left_behind):
    """`.lower()` in `program_report`, an `eval_shape`: a name traced (and
    lowered) and never compiled. A compile of that name long after, its trace
    found cached, is an entry of its own seconds that begins where it began."""
    clock = _Clock(monkeypatch)
    clock.stage(TRACE, 3.0, "left_fn")
    if left_behind == "lowered":
        clock.stage(LOWER, 2.0, "jit(left_fn)")
    bystander = clock.now + 50.0
    clock.stage(TRACE, 0.5, "bystander_fn", gap=50.0)  # traced, not yet compiled
    if left_behind == "traced":  # the lowering is made anew, the trace is cached
        clock.stage(LOWER, 2.0, "jit(left_fn)", gap=100.0)
    clock.stage(BACKEND, 4.0, "jit(left_fn)",
                gap=0.01 if left_behind == "traced" else 100.0)
    entry = compile_log()[-1]
    assert entry["what"] == "left_fn" and entry["trace_s"] == 0.0
    assert entry["lower_s"] == (2.0 if left_behind == "traced" else 0.0)
    began = clock.now - 4.0 - (2.01 if left_behind == "traced" else 0.0)
    assert entry["t0"] == pytest.approx(began)
    # what was pending before this compile began is another function's, kept
    assert bystander < entry["t0"]
    assert "bystander_fn" in compile_cache._gathering().stages
    compile_cache._gathering().stages.clear()


def test_a_verdict_no_compile_followed_is_not_the_next_entrys(monkeypatch):
    clock = _Clock(monkeypatch)
    compile_cache._on_event(compile_cache._CACHE_USED_EVENT)
    compile_cache._on_event(compile_cache._CACHE_HIT_EVENT)  # then jax raised
    compile_cache._on_duration(compile_cache._RETRIEVAL_EVENT, 0.5)
    compile_cache._on_event(compile_cache._CACHE_USED_EVENT)
    compile_cache._on_event(compile_cache._CACHE_MISS_EVENT)
    clock.stage(BACKEND, 1.5, "jit(verdict_fn)")
    entry = compile_log()[-1]
    assert (entry["what"], entry["cache"], entry["retrieval_s"]) \
        == ("verdict_fn", "miss", None)


def test_an_executable_nobody_named_counts_under_other_and_the_record_is_sums():
    """The log keeps every executable under its own name; the metrics' `what`
    stays a handful of values, and the process holds sums, not events."""
    before = obs_device.compile_stats().get("other", {"events": 0})["events"]
    early = MetricsRegistry()
    obs_device.attach_compile_metrics(early)
    with track_compiles() as region:
        _fresh_jit()(jnp.ones((53,)))
    assert "salted" in {e["what"] for e in region.entries}
    stats = obs_device.compile_stats()
    assert "salted" not in stats and stats["other"]["events"] >= before + 1
    assert not hasattr(obs_device, "_events")
    late = MetricsRegistry()  # made after the compile: takes the record over
    obs_device.attach_compile_metrics(late)
    snaps = [r.snapshot() for r in (early, late)]
    for snap in snaps:
        events = snap["sparknet_compile_events_total"]["values"]
        assert "salted" not in {k[0] for k in events}
        other = stats["other"]
        assert sum(v for k, v in events.items() if k[0] == "other") == other["events"]
        assert events[("other", "false")] == other["cache_misses"]
        hist = snap["sparknet_compile_seconds"]["values"][("other",)]
        # fresh work only: hits are counted and not timed
        assert hist["count"] == other["cache_misses"]
    assert snaps[0]["sparknet_compile_seconds"]["values"] \
        == snaps[1]["sparknet_compile_seconds"]["values"]


def test_a_program_stamps_its_entries_and_a_failing_stamp_costs_nothing():
    step = {"n": 41}
    obs_device.register_program("stamped_fn", lambda: None, stamp=lambda: {"step": step["n"]})
    compile_cache._on_duration(compile_cache._BACKEND_COMPILE_EVENT, 0.1,
                               fun_name="jit(stamped_fn)")
    assert compile_log()[-1]["step"] == 41
    obs_device.register_program("stamped_fn", lambda: None, stamp=lambda: 1 / 0)
    compile_cache._on_duration(compile_cache._BACKEND_COMPILE_EVENT, 0.1,
                               fun_name="jit(stamped_fn)")
    assert "step" not in compile_log()[-1]
    obs_device.register_program("stamped_fn", lambda: None)
    assert obs_device.compile_stamp("stamped_fn") == {}
    stats = obs_device.compile_stats()["stamped_fn"]
    assert stats["events"] >= 2 and stats["backend_s"] == pytest.approx(0.1 * stats["events"])


def test_kept_spans_are_bounded_nest_and_hold_no_array(monkeypatch):
    record = obs_trace.Tracer(max_events=4)
    monkeypatch.setattr(obs_trace, "_startup", record)
    assert obs_trace.active_tracer() is None
    with obs_trace.startup_span("outer", model="lenet", n=3):
        with obs_trace.startup_span("inner"):
            pass

    @obs_trace.startup_span("decorated")
    def build():
        return np.ones((4,))

    build()
    build()
    build()  # the fifth span: counted and dropped
    spans = obs_trace.startup_spans()
    assert [s["name"] for s in spans] == ["inner", "outer", "decorated", "decorated"]
    assert record.dropped == 1
    by = {s["name"]: s for s in spans}
    assert by["inner"]["parent"] == by["outer"]["id"] and by["outer"]["parent"] is None
    assert by["outer"]["args"] == {"model": "lenet", "n": 3}
    assert by["outer"]["t0"] <= by["inner"]["t0"] <= by["inner"]["t1"] <= by["outer"]["t1"]
    flat = json.dumps(spans)  # plain numbers and strings: no array, no tree
    assert "array" not in flat.lower()
    assert obs_trace.MAX_STARTUP_SPANS <= 1024


def test_kept_spans_are_ordinary_spans_too_while_a_tracer_is_on(monkeypatch):
    monkeypatch.setattr(obs_trace, "_startup", obs_trace.Tracer(max_events=8))
    with obs_trace.tracing() as tracer:
        with obs_trace.startup_span("resolve_spec"):
            pass
    names = [e["name"] for e in tracer.events() if e["ph"] == "X"]
    assert names == ["resolve_spec"]
    assert [s["name"] for s in obs_trace.startup_spans()] == ["resolve_spec"]


def _span(name, t0, t1, sid, parent=None):
    return {"name": name, "t0": t0, "t1": t1, "id": sid, "parent": parent,
            "thread": "MainThread", "args": {}}


def _entry(what, t0, t1, stages, cache, **kw):
    return {"what": what, "thread": "MainThread", "tid": 1, "seq": 0, "t0": t0,
            "t1": t1, "trace_s": stages[0], "lower_s": stages[1],
            "backend_s": stages[2], "cache": cache, "retrieval_s": None,
            "saved_s": None, **kw}


def test_startup_report_cuts_at_the_first_round_and_the_line_sums_it(monkeypatch):
    spans = [_span("resolve_spec", 14.2, 14.7, 1), _span("compile_net", 15.0, 15.5, 3, 2),
             _span("trainer_init", 15.5, 17.6, 4, 2), _span("build_trainer", 15.0, 17.6, 2),
             _span("state_from_params", 18.0, 19.9, 5),
             _span("state_from_params", 90.0, 91.0, 6)]  # a later rebuild
    log = [_entry("broadcast_in_dim", 18.1, 19.4, (0.1, 0.2, 1.0), "hit"),
           _entry("make_stack", 20.0, 22.0, (0.5, 0.5, 1.0), "miss"),
           _entry("train_round", 22.0, 63.0, (9.1, 6.3, 25.6), "miss", step=0),
           _entry("train_round", 300.0, 340.0, (9.0, 6.0, 25.0), "hit", step=5000)]
    monkeypatch.setattr(obs_trace, "startup_spans", lambda: spans)
    monkeypatch.setattr(obs_trace, "import_stamp", lambda: 0.0)
    monkeypatch.setattr(obs_device, "compile_log", lambda: log)
    monkeypatch.setattr(obs_device, "compile_log_dropped", lambda: 7)
    report = obs_device.startup_report(until=70.0)
    assert [s["id"] for s in report["spans"]] == [1, 3, 4, 2, 5]
    assert [e["what"] for e in report["compiles"]] == \
        ["broadcast_in_dim", "make_stack", "train_round"]
    # one the log holds and seven it counted and let go
    assert (report["later_compiles"], report["dropped_compiles"]) == (8, 7)
    assert report["newest_compile"] == {"what": "train_round", "step": 5000,
                                        "seconds": 40.0, "cache": "hit"}
    assert obs_device.startup_line(report) == (
        "start-up: import 14.2 s, build 3.1, restore 0.0, state 1.9, "
        "train_round compile 41.0 (trace 9.1, lower 6.3, backend 25.6, cache miss), "
        "2 other programs 3.3")
    json.dumps(report)  # what /status serves
    # before any round has completed everything so far is start-up
    everything = obs_device.startup_report()
    assert len(everything["compiles"]) == 4 and everything["later_compiles"] == 0
    assert everything["newest_compile"] is None


def test_run_loop_logs_the_line_and_serves_the_block(tmp_path):
    """The operator's view: one `start-up:` line when the first round
    completes, and `/status` `startup` with the spans and the compiles up to
    it."""
    import urllib.request

    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet

    r = np.random.default_rng(0)
    ds = ArrayDataset({"data": r.standard_normal((64, 1, 28, 28)).astype(np.float32),
                       "label": r.integers(0, 10, (64, 1)).astype(np.int32)})
    cfg = RunConfig.from_dict({"model": "lenet", "tau": 2, "local_batch": 4,
                               "n_devices": 2, "max_rounds": 3, "status_port": 0,
                               "workdir": str(tmp_path), "precision": "float32"})
    seen = {}

    def scrape(rnd, state):
        # round 0's loss is fetched a round late, on the collector's thread:
        # ask until that has happened
        deadline = time.monotonic() + 60
        while rnd == 2 and time.monotonic() < deadline:
            host, port = cfg.status_address
            with urllib.request.urlopen(f"http://{host}:{port}/status", timeout=30) as f:
                seen.update(json.load(f))
            if seen["startup"]["until"] is not None:
                break
            time.sleep(0.05)

    log = Logger(str(tmp_path / "log.txt"), echo=False)
    train(cfg, lenet(batch=4), ds, logger=log, round_hook=scrape)
    text = open(tmp_path / "log.txt").read()
    lines = [l for l in text.splitlines() if "start-up: import" in l]
    assert len(lines) == 1 and "train_round compile" in lines[0]
    startup = seen["startup"]
    assert startup["until"] is not None
    assert {"build_trainer", "state_from_params"} <= {s["name"] for s in startup["spans"]}
    rounds = [e for e in startup["compiles"] if e["what"] == "train_round"]
    assert rounds and rounds[-1]["step"] == 0
    assert all(e["t1"] < startup["until"] for e in startup["compiles"])
