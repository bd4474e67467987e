"""The seven `setup_*` metrics (PR 38): `startup_account.py`'s arithmetic by hand
on a made-up run, every reader on it and with the program's records taken away
(as on the parent commit), the entries of `BENCHMARK.json` found by name, and
once against a real tiny trainer. All on the CPU: counts, verdicts and host
seconds of a made-up clock, never a device time.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
import time
import types

import pytest

from sparknet_tpu.obs.device import startup_line, startup_sums

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")


def _run_py():
    spec = importlib.util.spec_from_file_location(
        "bench_run_" + re.sub(r"\W", "_", ROOT), os.path.join(BENCH, "run.py"))
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = ["caffenet-train-round", "caffenet-avg4-round", "glm47-flash-train-round"]
#: metric -> (unit, source, layer), as ISSUE 38 tables them
NEW = {"setup_import_s": ("s", "program_span", "apps / start-up"),
       "setup_build_s": ("s", "program_span", "apps / start-up"),
       "setup_state_s": ("s", "program_span", "trainer"),
       "setup_round_trace_s": ("s", "program_counter", "compile"),
       "setup_round_backend_s": ("s", "program_counter", "compile"),
       "setup_other_compile_s": ("s", "program_counter", "compile"),
       "setup_cache_misses": ("count", "program_counter", "compile")}

# -- a made-up set-up on a made-up clock --------------------------------------
# process start at 1000.0; the window opens 100 s later. Before it: imports
# to 1014.0; resolve_spec 0.5; build_trainer 3.0 with compile_net 0.5 and
# trainer_init 2.0 inside; the benchmark's stack program; the check round's
# state (2.0, a placement program's compile inside it), the round's compile
# (40 s), the norms program; the window's state (1.0); three warm-up rounds.
T0, SETUP = 1000.0, 100.0


def _span(name, t0, t1, sid, parent=None):
    return {"name": name, "t0": T0 + t0, "t1": T0 + t1, "id": sid,
            "parent": parent, "thread": "MainThread", "args": {}}


def _entry(what, t0, t1, stages, cache, **kw):
    return {"what": what, "thread": "MainThread", "tid": 1, "seq": 0,
            "t0": T0 + t0, "t1": T0 + t1, "trace_s": stages[0],
            "lower_s": stages[1], "backend_s": stages[2], "cache": cache,
            "retrieval_s": 0.25 if cache == "hit" else None,
            "saved_s": 30.0 if cache == "hit" else None, **kw}


SPANS = [_span("stale", -50.0, -49.0, 90),              # an earlier run's
         _span("resolve_spec", 14.0, 14.5, 1),
         _span("compile_net", 15.0, 15.5, 3, 2),
         _span("trainer_init", 15.5, 17.5, 4, 2),
         _span("build_trainer", 15.0, 18.0, 2),
         _span("state_from_params", 21.0, 23.0, 5),
         _span("state_from_params", 70.0, 71.0, 6),
         _span("state_from_params", 130.0, 131.0, 7)]    # after the window opened
LOG = [_entry("old_program", -10.0, -9.0, (0.1, 0.1, 0.5), "miss"),
       _entry("make_stack", 19.0, 21.0, (0.5, 0.5, 1.0), "miss"),
       _entry("broadcast_in_dim", 21.5, 22.5, (0.125, 0.125, 0.75), "hit"),
       _entry("train_round", 23.0, 63.0, (9.0, 6.0, 25.0), "miss", step=0),
       _entry("norms", 63.0, 65.0, (0.5, 0.5, 1.0), "off"),
       _entry("train_round", 140.0, 150.0, (1.0, 1.0, 8.0), "hit", step=9)]
PHASES = [("build", 18.5), ("check_round", 69.0), ("warmup", 100.002),
          ("reference", 160.0)]


def _fake_run(phases=PHASES):
    ctx = types.SimpleNamespace(load=load, t0=T0, phases=list(phases),
                                traffic={"warmup_rounds": 3})
    # the window: completions 4.0 s apart from the opening stamp on
    return types.SimpleNamespace(ctx=ctx, setup_s=SETUP, trace=None, notes={},
                                 round_done_s=[T0 + SETUP + 4.0 * i for i in range(6)])


@pytest.fixture
def with_records(monkeypatch):
    import sparknet_tpu.obs.trace as trace
    import sparknet_tpu.utils.compile_cache as cc
    monkeypatch.setattr(trace, "startup_spans", lambda: list(SPANS))
    monkeypatch.setattr(cc, "compile_log", lambda: list(LOG))


# -- the arithmetic, by hand ---------------------------------------------------

def test_account_by_hand():
    sa = load("startup_account.py")
    out = sa.account(startup_sums, SPANS, LOG, T0, SETUP, PHASES, rounds_before=4,
                     round_s=4.0)
    assert out["setup_import_s"] == pytest.approx(14.0)
    # build_trainer holds compile_net and trainer_init: counted once
    assert out["setup_build_s"] == pytest.approx(0.5 + 3.0)
    assert out["setup_state_s"] == pytest.approx(2.0 + 1.0)
    assert out["setup_round_trace_s"] == pytest.approx(15.0)
    assert out["setup_round_backend_s"] == pytest.approx(25.0)
    assert out["setup_other_compile_s"] == pytest.approx(2.0 + 1.0 + 2.0)
    assert out["setup_cache_misses"] == 3, "miss, miss, and not consulted"
    # what the record covers, as a union: imports 14, resolve_spec 0.5,
    # build_trainer 3, make_stack 2, the check round's state 2 (the placement
    # program's compile inside it counts once), the round 40, norms 2, state 1
    assert out["recorded_s"] == pytest.approx(14 + 0.5 + 3 + 2 + 2 + 40 + 2 + 1)
    assert out["rounds_s"] == pytest.approx(16.0)
    assert out["unaccounted_s"] == pytest.approx(100 - 64.5 - 16.0)
    by = {p["name"]: p for p in out["phases"]}
    assert list(by) == ["build", "check_round", "warmup"], "the reference is no set-up"
    assert by["build"]["seconds"] == pytest.approx(18.5)
    assert by["build"]["recorded_s"] == pytest.approx(14 + 0.5 + 3)
    assert by["check_round"]["recorded_s"] == pytest.approx(2 + 2 + 40 + 2)
    assert by["check_round"]["rest_s"] == pytest.approx(50.5 - 46)
    assert by["warmup"]["seconds"] == pytest.approx(31.0), "cut at the stamp"
    assert by["warmup"]["rest_s"] == pytest.approx(30.0)
    assert sum(p["seconds"] for p in out["phases"]) == pytest.approx(SETUP)
    assert [s["name"] for s in out["spans"]] == [
        "resolve_spec", "build_trainer", "compile_net", "trainer_init",
        "state_from_params", "state_from_params"]
    assert [c["what"] for c in out["compiles"]] == [
        "make_stack", "broadcast_in_dim", "train_round", "norms"]
    assert out["compiles"][2]["step"] == 0 and out["compiles"][2]["at_s"] == pytest.approx(23.0)
    json.dumps(out)  # the note line


def test_sibling_spans_are_each_counted_and_an_empty_record_reads_zero():
    sa = load("startup_account.py")
    spans = [_span("resolve_spec", 1.0, 2.0, 1), _span("build_trainer", 2.0, 4.0, 2),
             _span("restore", 5.0, 8.0, 3), _span("state_from_params", 6.0, 7.0, 4, 3)]
    out = sa.account(startup_sums, spans, [], T0, 10.0, [], 0, None)
    assert out["setup_build_s"] == pytest.approx(3.0)
    assert out["setup_state_s"] == pytest.approx(3.0), "a resume's state is restore's"
    assert out["rounds_s"] is None and out["unaccounted_s"] == pytest.approx(10 - 1 - 3 - 3)
    empty = sa.account(startup_sums, [], [], T0, 10.0, [], 0, None)
    assert empty["setup_import_s"] is None and empty["setup_cache_misses"] == 0
    assert empty["setup_round_backend_s"] == 0 and empty["unaccounted_s"] == 10.0
    assert sa.covered([(0, 4), (1, 2), (3, 6), (8, 12)], 0.5, 10) == pytest.approx(7.5)


def test_the_metrics_are_the_sums_the_programs_own_line_tells(with_records):
    """One arithmetic: `obs.device.startup_sums` under both the `start-up:`
    line a train loop logs and the seven metrics."""
    sa = load("startup_account.py")
    out = sa.of_run(_fake_run())
    cut = [s for s in SPANS if T0 <= s["t0"] < T0 + SETUP]
    log = [e for e in LOG if T0 <= e["t1"] < T0 + SETUP]
    line = startup_line({"spans": cut, "compiles": log, "import_t0": T0})
    assert line == (
        f"start-up: import {out['setup_import_s']:.1f} s, build {out['setup_build_s']:.1f}"
        f", restore 0.0, state {out['setup_state_s']:.1f}, train_round compile 40.0 "
        f"(trace 9.0, lower 6.0, backend {out['setup_round_backend_s']:.1f}, cache miss), "
        f"3 other programs {out['setup_other_compile_s']:.1f}")


# -- every reader: a value from the made-up run, None without the records ------

@pytest.mark.parametrize("metric,value", [
    ("setup_import_s", 14.0), ("setup_build_s", 3.5), ("setup_state_s", 3.0),
    ("setup_round_trace_s", 15.0), ("setup_round_backend_s", 25.0),
    ("setup_other_compile_s", 5.0), ("setup_cache_misses", 3.0)])
def test_reader_on_a_made_up_run(metric, value, with_records):
    reader = load(os.path.join("readers", metric + ".py"))
    assert float(reader.read(_fake_run())) == pytest.approx(value)


def test_one_note_line_a_run(with_records, capsys):
    run = _fake_run()
    for metric in NEW:
        load(os.path.join("readers", metric + ".py")).read(run)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if '"startup"' in l]
    assert len(notes) == 1 and notes[0]["note"] == "startup"
    assert notes[0]["rounds_before"] == 4, "the check round and three warm-up rounds"
    assert notes[0]["rounds_s"] == pytest.approx(16.0)
    assert notes[0]["unaccounted_s"] == pytest.approx(19.5)
    assert run.notes == {"startup_unaccounted_s": pytest.approx(19.5)}, \
        "the mark that the line is out, in the run's own note"


@pytest.mark.parametrize("gone", ["startup_spans", "compile_log", "startup_sums"])
def test_readers_return_none_for_a_program_with_no_record_of_its_start_up(
        gone, monkeypatch):
    """As on the parent commit: `startup_spans` and `compile_log` do not exist
    there, and the readers leave their metrics out without raising."""
    import sparknet_tpu.obs.trace as trace
    import sparknet_tpu.utils.compile_cache as cc
    import sparknet_tpu.obs.device as device
    monkeypatch.delattr(*{"startup_spans": (trace, "startup_spans"),
                          "compile_log": (cc, "compile_log"),
                          "startup_sums": (device, "startup_sums")}[gone])
    for metric in NEW:
        assert load(os.path.join("readers", metric + ".py")).read(_fake_run()) is None


# -- the entries, by name ------------------------------------------------------

@pytest.mark.parametrize("metric", list(NEW))
def test_the_entry_is_there_by_name_with_its_three_cells(metric):
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    unit, source, layer = NEW[metric]
    assert by[metric] == {"name": metric, "unit": unit, "better": "lower",
                          "source": source, "layer": layer, "moves": "setup_s",
                          "workloads": CELLS}
    assert os.path.exists(os.path.join(BENCH, "readers", metric + ".py"))
    assert metric in [m["name"] for m in RUN.cell_metrics(BENCHMARK, CELLS[2], True)]
    assert metric not in [m["name"] for m in RUN.cell_metrics(BENCHMARK, CELLS[2], False)]


def test_setup_s_has_metrics_under_it_now():
    moved = [m["name"] for m in BENCHMARK["per_layer"] if m["moves"] == "setup_s"]
    assert sorted(moved) == sorted(NEW)
    every_cell = {m["name"]: m for m in BENCHMARK["end_to_end"]}["setup_s"]
    assert "workloads" not in every_cell, "every cell reports setup_s"
    layers = {m["layer"] for m in BENCHMARK["per_layer"]}
    assert {"apps / start-up", "trainer", "compile"} <= layers


# -- against the real program, at a tiny size ----------------------------------

def test_readers_against_a_real_tiny_trainer():
    """A lenet set-up as a driver makes one (spec, trainer, state, a first
    round, a second), with no tracer and no profiler on: the seven readers
    read the program's own records of it."""
    import jax
    import numpy as np
    from sparknet_tpu.apps.train_loop import build_trainer, resolve_spec
    from sparknet_tpu.obs import trace
    from sparknet_tpu.utils.config import RunConfig

    assert trace.active_tracer() is None
    t0 = time.perf_counter()
    cfg = RunConfig.from_dict({"model": "lenet", "tau": 2, "local_batch": 6,
                               "n_devices": 2, "precision": "float32",
                               "seed": time.time_ns() % 1000})
    trainer = build_trainer(cfg, resolve_spec(cfg))
    phases = [("build", time.perf_counter() - t0)]
    r = np.random.default_rng(0)
    stamps = []
    for i in range(3):
        state = trainer.init_state(jax.random.PRNGKey(i)) if i < 2 else state
        state, loss = trainer.train_round(state, {
            "data": r.standard_normal((2, 12, 28, 28, 1)).astype(np.float32),
            "label": r.integers(0, 10, (2, 12, 1)).astype(np.int32)},
            jax.random.PRNGKey(7))
        float(loss)
        stamps.append(time.perf_counter())
        if i == 0:
            phases.append(("check_round", stamps[-1] - t0))
    ctx = types.SimpleNamespace(load=load, t0=t0, phases=phases,
                                traffic={"warmup_rounds": 1})
    run = types.SimpleNamespace(ctx=ctx, setup_s=stamps[1] - t0, trace=None, notes={},
                                round_done_s=stamps[1:])
    got = {m: load(os.path.join("readers", m + ".py")).read(run) for m in NEW}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["setup_build_s"] > 0 and got["setup_state_s"] > 0
    assert got["setup_round_trace_s"] > 0 and got["setup_round_backend_s"] > 0
    out = load("startup_account.py").of_run(run)
    rounds = [c for c in out["compiles"] if c["what"] == "train_round"]
    assert len(rounds) == 1 and rounds[0]["step"] == 0
    assert sum(s["name"] == "state_from_params" for s in out["spans"]) == 2
    assert out["recorded_s"] <= out["setup_s"] + 1e-6
