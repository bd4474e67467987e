"""The benchmark's own CPU tests: every cell resolves by name, the seeded data
repeats, the metric arithmetic and the trace reduction give hand-checked
numbers, the harness fails off the chip, a throw-away cell / configuration /
metric is added as new files only, `correct` comes out false on a broken timed
path, and the lower-precision control fails the comparison (at a size a test
run can hold; the chip readings at the cell's own size are in PERF.md).
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _run_py(root=ROOT):
    spec = importlib.util.spec_from_file_location(
        "bench_run_" + re.sub(r"\W", "_", root), os.path.join(root, "benchmark", "run.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


# -- every name resolves to a file -------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve(cell):
    _, w, entry = RUN.resolve(ROOT, cell)
    config = RUN.load_json(os.path.join(ROOT, entry["file"]))
    assert config["name"] == entry["name"] and config["reduced"] == entry["reduced"]
    reference = RUN.load_module(os.path.join(ROOT, config["reference"]))
    assert os.path.dirname(config["reference"]) == os.path.dirname(entry["file"]), \
        "a configuration's plain reference sits beside its file"
    for attr in ("LAYERS", "LIMITS", "CONTROL_PRECISION", "init_params",
                 "round_reference"):
        assert hasattr(reference, attr), attr
    traffic = RUN.load_json(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    driver = RUN.load_module(os.path.join(BENCH, "drivers", traffic["driver"] + ".py"))
    assert callable(driver.run)
    for trace in (False, True):
        names = [m["name"] for m in RUN.cell_metrics(BENCHMARK, cell, trace)]
        assert names, "every cell reports something in both kinds of run"
        for name in names:
            reader = RUN.load_module(os.path.join(BENCH, "readers", name + ".py"))
            assert callable(reader.read)
    e2e = [m["name"] for m in RUN.cell_metrics(BENCHMARK, cell, False)]
    assert "setup_s" in e2e and len(e2e) >= 2


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_metric_entry_well_formed(metric):
    m = {x["name"]: x for x in METRICS}[metric]
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter",
                           "host_clock")
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    if "moves" in m:  # a per-layer metric: its cells report what it moves
        moved = {x["name"]: x for x in BENCHMARK["end_to_end"]}[m["moves"]]
        assert set(m.get("workloads", CELLS)) <= set(moved.get("workloads", CELLS))
        assert "\n" not in m["layer"] and 0 < len(m["layer"]) <= 200
    else:
        assert 0 < m["bound"] <= 0.1


def test_benchmark_json_shape():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads") for x in BENCHMARK[k]]
    names += [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in BENCHMARK["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BENCHMARK["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4), "one four-chip cell of three"
    assert {w["config"] for w in BENCHMARK["workloads"]} == \
        {c["name"] for c in BENCHMARK["configs"]}
    assert all(len(w["why"]) <= 200 for w in BENCHMARK["workloads"])
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10
    for path in BENCHMARK["paths"]:
        for d, _, files in os.walk(os.path.join(ROOT, path)):
            if "__pycache__" not in d:
                assert all(re.match(r"^[A-Za-z0-9_.\-]+$", f) for f in files), d


# -- the same seed gives the same rows ---------------------------------------

def test_seeded_stacks_and_corpus_repeat():
    import jax.numpy as jnp
    seeded = load("seeded.py")
    kw = dict(global_batch=8, tau=3, crop=9, n_classes=16, dtype=jnp.bfloat16)
    seed = 3_000_000_019  # the driver's seeds pass 2**31
    a = seeded.stack_slice(seed, 2, 0, 3, 0, 8, **kw)
    b = seeded.stack_slice(seed, 2, 0, 3, 0, 8, **kw)
    assert all(np.array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
               for x, y in zip(a, b))
    # any slice alone equals that part of the whole stack
    part = seeded.stack_slice(seed, 2, 1, 1, 4, 4, **kw)
    assert np.array_equal(np.asarray(part[0], np.float32),
                          np.asarray(a[0], np.float32)[1:2, 4:8])
    assert np.array_equal(np.asarray(part[1]), np.asarray(a[1])[1:2, 4:8])
    rows = np.asarray(a[0], np.float32).reshape(24, -1)
    assert len({r.tobytes() for r in rows}) == 24, "rows all differ"
    other = seeded.stack_slice(seed + 1, 2, 0, 3, 0, 8, **kw)
    assert not np.array_equal(np.asarray(other[0], np.float32),
                              np.asarray(a[0], np.float32))
    assert abs(float(np.mean(rows))) < 6 and 64 < float(np.std(rows)) < 84
    im1, lb1 = seeded.corpus(seed, 37, 16, 10, threads=4)
    im2, lb2 = seeded.corpus(seed, 37, 16, 10, threads=4)
    assert im1.shape == (37, 3, 16, 16) and im1.dtype == np.uint8
    assert np.array_equal(im1, im2) and np.array_equal(lb1, lb2)
    assert not np.array_equal(im1, seeded.corpus(seed + 1, 37, 16, 10, threads=4)[0])


# -- metric arithmetic on hand-made inputs -----------------------------------

def test_metric_arithmetic():
    m, flops = load("metric_math.py"), load("flops.py")
    ref = RUN.load_module(os.path.join(BENCH, "configs", "caffenet-tau50.reference.py"))
    stamps = [10.0, 10.5, 11.0, 12.0, 12.5]
    assert m.window_rate(stamps, 100.0) == pytest.approx(4 * 100.0 / 2.5)
    assert m.window_rate([1.0], 100.0) is None
    assert m.intervals(stamps) == [0.5, 0.5, 1.0, 0.5]
    assert m.median(m.intervals(stamps)) == 0.5
    assert m.percentile([5, 1, 4, 2, 3, 9, 8, 7, 6, 10], 90) == 9
    assert m.percentile([3.0], 90) == 3.0 and m.percentile([], 90) is None
    run = types.SimpleNamespace(
        round_done_s=stamps, spans={"h2d": [(9.0, 10.2), (11.0, 11.3), (12.4, 13.0)]},
        loop_rows=[{"t_data_ms": 4.0, "t_ckpt_fetch_ms": 0.0},
                   {"t_data_ms": 6.0, "t_ckpt_fetch_ms": 30.0}],
        trace={"busy_s": 0.9, "window_s": 1.2,
               "fullest": {"rounds": 3, "kernel_s": 0.06}})
    assert m.span_ms_per_round(run, "h2d") == pytest.approx(1e3 * (0.2 + 0.3 + 0.1) / 4)
    assert m.span_ms_per_round(run, "absent") is None
    assert m.row_mean(run, "t_data_ms") == 5.0
    assert m.row_mean(run, "t_ckpt_fetch_ms", only_positive=True) == 30.0
    assert m.idle_share(run) == pytest.approx(25.0)
    assert m.traced_rounds_ms(run, "kernel_s") == pytest.approx(20.0)
    # CaffeNet by hand: 724,406,816 MACs forward
    macs = (55 * 55 * 11 * 11 * 3 * 96 + 27 * 27 * 5 * 5 * 48 * 256
            + 13 * 13 * 9 * 256 * 384 + 13 * 13 * 9 * 192 * 384
            + 13 * 13 * 9 * 192 * 256 + 9216 * 4096 + 4096 * 4096 + 4096 * 1000)
    assert macs == 724_406_816
    assert flops.forward_flops_per_image(ref.LAYERS, 227, 1000) == 2.0 * macs
    assert flops.train_flops_per_image(ref.LAYERS, 227, 1000) == 6.0 * macs
    cost = flops.lrn_step_cost(ref.LAYERS, 227, 256, 2)
    elems = 256 * (27 * 27 * 96 + 13 * 13 * 256)
    assert cost["bytes"] == 5 * 2 * elems and cost["ops"] == 30 * elems
    peak = flops.peaks("TPU v5 lite")
    share, bound = flops.roofline_share(1e9, 819e6, 2e-3, peak)
    assert bound == "bytes" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share(197e9, 1.0, 4e-3, peak)
    assert bound == "ops" and share == pytest.approx(25.0)
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_compare_norm_gap():
    compare = load("compare.py")
    ref = {"a": 10.0, "b": 1.0, "c": 1e-9}
    gap, leaf = compare.norm_gap({"a": 10.5, "b": 1.2, "c": 2e-9}, ref)
    assert leaf == "b" and gap == pytest.approx(0.2)  # c is held to the median
    assert compare.norm_gap({"a": float("nan"), "b": 1.0, "c": 0.0}, ref)[1] == "a"
    checks = compare.first_round_checks(
        {"loss": 6.9, "update_norms": ref, "momentum_norms": [ref, ref],
         "probe": [np.ones(4), np.array([3.0, 4.0])]},
        {"loss": 6.9005, "update_norms": ref, "momentum_norms": [ref, dict(ref, a=12.0)],
         "probe": [np.ones(4), np.array([3.0, 4.5])]},
        {"loss_gap": 0.001, "update_gap": 0.1, "momentum_gap": 0.1, "probe_diff": 0.2})
    by = {c["name"]: c for c in checks}
    assert by["loss_gap"]["ok"] and by["update_gap"]["ok"]
    assert by["probe_diff"]["worker"] == 1 and by["probe_diff"]["ok"]
    assert by["probe_diff"]["value"] == pytest.approx(0.5 / np.hypot(3.0, 4.5))
    assert not by["momentum_gap"]["ok"] and by["momentum_gap"]["worker"] == 1
    assert not compare.judged({"value": float("nan"), "limit": 1.0})["ok"]
    assert compare.exact("x", 0)["ok"] and not compare.exact("x", 1e-30)["ok"]


# -- the trace reduction on a small recorded trace ---------------------------

def test_trace_reduce_on_recorded_trace():
    """`fixtures/small.xplane.pb`: a v5e trace of six runs of a small program
    holding two Pallas kernels, with `bench:sleep` spans between runs."""
    tr = load("trace_reduce.py")
    trace = tr.read(os.path.join(BENCH, "fixtures", "small.xplane.pb"))
    assert list(trace["devices"]) == [0] and len(trace["spans"]) == 19
    r = tr.reduce(trace)
    d = r["fullest"]
    # six runs: the first period (the profiler's start) is left out
    assert d["round_module"].startswith("jit_body(") and r["rounds"] == 4
    assert r["window_s"] == pytest.approx(0.086508791, rel=1e-6)
    assert r["busy_s"] == pytest.approx(2.05121e-4, rel=1e-4)
    assert d["kernel_calls"] == 8
    assert d["kernel_s"] == pytest.approx(1.59919e-4, rel=1e-4)
    assert d["collective_s"] == 0.0 and d["collective_exposed_s"] == 0.0
    assert [k for k, _ in r["device_ops"][:2]] == ["%transpose_jvp___.1", "%body.1"]
    assert r["device_ops"][0][1] == pytest.approx(1.03613e-4, rel=1e-4)
    assert r["idle_gaps"][0][0] == "bench:sleep"
    assert r["idle_gaps"][0][1] == pytest.approx(0.08630367, rel=1e-5)
    # the union and the collective arithmetic, by hand
    s, e = tr.union(np.array([0.0, 1.0, 1.5, 5.0]), np.array([2.0, 1.2, 3.0, 9.0]), 0.5, 6.0)
    assert s.tolist() == [0.5, 5.0] and e.tolist() == [3.0, 6.0]
    assert tr.covered(np.array([0.0, 4.0]), np.array([1.0, 5.0]), 0.0, 10.0) == 2.0
    assert tr._parse("%all-reduce-start.3 = (f32[4]{0}, f32[4]{0}) all-reduce-start(f32[4]{0} %x)") \
        == ("%all-reduce-start.3", "all-reduce-start")
    assert tr._parse("%body.1 = bf16[169,64,128]{2,1,0:T(8,128)(2,1)S(1)} custom-call(bf16[1]{0} %a)")[1] \
        == "custom-call"


# -- off the chip the harness measures nothing -------------------------------

def test_run_py_exits_nonzero_off_the_chip(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = tmp_path / "out.txt"
    with open(out, "w") as f:
        rc = subprocess.run(
            [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
             "--seed", "1", "--seconds", "1", "--trace", "0"],
            stdout=f, stderr=subprocess.STDOUT, env=env, cwd=ROOT, timeout=300).returncode
    text = out.read_text()
    assert rc != 0 and "nothing was measured" in text
    assert '"correct"' not in text, "no result line off the chip"


# -- a tiny cell end to end, added as new files only -------------------------

TINY = {"crop": 67, "n_classes": 16, "local_batch": 8, "tau": 2}
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: (seeds 11-15): the program's probe_diff read 0.0031-0.0039 and the fp8
#: control's 0.036-0.045. Its learning rate is 0.001 / 64 in the check round,
#: so the parameters' change sits near float32's rounding of the weights and
#: update_gap is loose (0.06 read); it is held against 1.0, an unchanged state.
TINY_LIMITS = {"loss_gap": 0.01, "update_gap": 0.3, "momentum_gap": 0.05, "probe_diff": 0.007}


@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    """A throw-away checkout: the benchmark's files as they are, plus a new
    configuration, a new traffic mix, a new per-layer metric and two new
    cells -- new files and new entries of BENCHMARK.json, no file edited."""
    root = str(tmp_path_factory.mktemp("tiny-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for p in _files(root)}
    cfg = dict(RUN.load_json(os.path.join(BENCH, "configs", "caffenet-tau50.json")),
               name="tiny", reference="benchmark/configs/tiny.reference.py",
               reduced=sorted(TINY), **TINY)
    cfg["solver"] = dict(cfg["solver"], base_lr=0.001)  # batch 8 diverges at 0.01
    _write(root, "benchmark/configs/tiny.json", json.dumps(cfg))
    _write(root, "benchmark/configs/tiny.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_ref_base', os.path.join("
        "os.path.dirname(os.path.abspath(__file__)), 'caffenet-tau50.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "LAYERS, init_params, round_reference = _m.LAYERS, _m.init_params, _m.round_reference\n"
        "CONTROL_PRECISION, PROBE_LEAF = _m.CONTROL_PRECISION, _m.PROBE_LEAF\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    _write(root, "benchmark/traffic/tiny-round.json", json.dumps(
        {"driver": "device-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    _write(root, "benchmark/traffic/tiny-loop.json", json.dumps(
        {"driver": "cached-loop", "corpus_images": 96, "image_size": 80,
         "checkpoint_every": 2, "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2, "crop_check_rows": 6}))
    _write(root, "benchmark/readers/last_round_loss.py",
           "def read(run):\n    return run.losses[-1] if run.losses else None\n")
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny", "source": cfg["source"],
                             "file": "benchmark/configs/tiny.json",
                             "reduced": sorted(TINY), "why": "a test's own"})
    new_cells = [{"name": "tiny-round", "config": "tiny", "traffic": "tiny-round",
                  "chips": 1, "why": "a test's own"},
                 {"name": "tiny-loop", "config": "tiny", "traffic": "tiny-loop",
                  "chips": 1, "why": "a test's own"},
                 {"name": "tiny-avg4", "config": "tiny4", "traffic": "tiny-round",
                  "chips": 4, "why": "a test's own"}]
    _write(root, "benchmark/configs/tiny4.json", json.dumps(dict(cfg, name="tiny4")))
    bench["configs"].append({"name": "tiny4", "source": cfg["source"],
                             "file": "benchmark/configs/tiny4.json",
                             "reduced": sorted(TINY), "why": "a test's own"})
    bench["workloads"] += new_cells
    for m in bench["end_to_end"]:
        if m["name"] == "train_round_rate":
            m["workloads"] += ["tiny-round", "tiny-avg4"]
    # the cached-loop traffic has no cell of its own yet (PERF.md section 7):
    # its driver and readers are kept working by this throw-away one
    bench["end_to_end"].append({"name": "train_loop_rate", "unit": "samples/s/chip",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock", "workloads": ["tiny-loop"]})
    bench["end_to_end"].append({"name": "last_round_loss", "unit": "nats",
                                "better": "lower", "bound": 0.1, "source": "host_clock",
                                "workloads": ["tiny-round", "tiny-loop", "tiny-avg4"]})
    for name, unit in (("loop_data_wait_ms", "ms"), ("loop_round_p50_ms", "ms"),
                       ("loop_round_p90_ms", "ms"), ("loop_preprocess_ms", "ms"),
                       ("loop_h2d_ms", "ms"), ("loop_ckpt_stall_ms", "ms"),
                       ("loop_idle_share", "%")):
        bench["per_layer"].append({"name": name, "unit": unit, "better": "lower",
                                   "source": "program_span", "layer": "apps / loop",
                                   "moves": "train_loop_rate", "workloads": ["tiny-loop"]})
    _write(root, "BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items()), \
        "adding a cell edited a file that was there"
    return root


def _files(root):
    return [os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs]


def _write(root, rel, text):
    path = os.path.join(root, rel)
    assert not os.path.exists(path) or rel == "BENCHMARK.json"
    with open(path, "w") as f:
        f.write(text)


def _run_tiny(root, cell, seed=11, seconds=6.0, trace=False):
    # seconds: a tiny round takes ~0.5 s alone and several times that beside
    # five other test workers; the window has to hold one all the same
    import time
    run = _run_py(root)
    return run.run_cell(root, cell, seed, seconds, trace, time.perf_counter())


def test_tiny_round_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, "tiny-round")
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    checks = {n["name"]: n for n in notes if n.get("note") == "check"}
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff"}
    assert all("limit" in c and "value" in c for c in checks.values())
    assert out["correct"] is True, checks
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s", "last_round_loss"}
    assert out["metrics"]["train_round_rate"]["value"] > 0
    assert out["device"]["count"] == 1 and "memory_peak_bytes" in out["device"]
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}


def test_tiny_four_worker_cell_is_correct_and_replicas_agree(tiny_tree, capsys):
    """Four virtual CPU devices stand for the four chips: the averaged round
    against four reference workers' rounds, one on each device."""
    out = _run_tiny(tiny_tree, "tiny-avg4", seed=15, seconds=1.0)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    checks = {n["name"]: n for n in notes if n.get("note") == "check"}
    assert checks["replica_spread"]["value"] == 0 and checks["replica_spread"]["limit"] == 0
    assert out["correct"] is True, checks
    assert out["device"]["count"] == 4


def test_tiny_loop_cell_is_correct_and_its_rows_are_crops(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, "tiny-loop", seed=12)
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    checks = {n["name"]: n for n in notes if n.get("note") == "check"}
    assert "crop_mismatch" in checks and checks["crop_mismatch"]["value"] == 0
    assert checks["loop_round0_drift"]["value"] == 0
    assert out["correct"] is True, checks
    assert set(out["metrics"]) == {"train_loop_rate", "setup_s", "last_round_loss"}


def test_tiny_loop_traced_run_reads_the_loop_layers(tiny_tree):
    """A `--trace 1` run of the loop cell off the chip: the readers that take
    their numbers from the loop's own rows and the benchmark's spans report;
    the one that needs a device trace finds nothing to read and is left out."""
    out = _run_tiny(tiny_tree, "tiny-loop", seed=16, trace=True)
    assert {"loop_data_wait_ms", "loop_round_p50_ms", "loop_round_p90_ms",
            "loop_preprocess_ms", "loop_h2d_ms"} <= set(out["metrics"])
    # (loop_ckpt_stall_ms reports when a saving round's stall fell in the window)
    assert "loop_idle_share" not in out["metrics"] and "breakdown" not in out
    assert out["metrics"]["loop_preprocess_ms"]["value"] > 0
    assert out["metrics"]["loop_round_p90_ms"]["value"] >= out["metrics"]["loop_round_p50_ms"]["value"]


def test_correct_is_false_when_the_round_returns_its_state_unchanged(tiny_tree, monkeypatch):
    """The rest of a run, with the timed path broken underneath."""
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    real = ParallelTrainer.train_round

    def lazy_round(self, state, batches, rng, **kw):
        import jax
        _, loss = real(self, jax.tree.map(lambda x: x.copy(), state), batches, rng, **kw)
        return state, loss

    monkeypatch.setattr(ParallelTrainer, "train_round", lazy_round)
    out = _run_tiny(tiny_tree, "tiny-round", seed=13, seconds=1.0)
    assert out["correct"] is False


def test_correct_is_false_when_a_prepared_row_is_altered(tiny_tree, monkeypatch):
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    real = ImagePreprocessor.convert_batch

    def shifted(self, batch, **kw):
        out = real(self, batch, **kw)
        out["data"] = np.roll(np.asarray(out["data"]), 1, axis=2)  # one pixel right
        return out

    monkeypatch.setattr(ImagePreprocessor, "convert_batch", shifted)
    out = _run_tiny(tiny_tree, "tiny-loop", seed=14, seconds=1.0)
    assert out["correct"] is False


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_lower_precision_control_fails_the_comparison(tiny_tree, seed):
    """The control: the reference put in the program's place, computed in the
    precision below the configuration's (CONTROL_PRECISION). It has to fail a
    limit the sound reference passes with room."""
    import jax
    import jax.numpy as jnp
    run = _run_py(tiny_tree)
    ref = run.load_module(os.path.join(tiny_tree, "benchmark/configs/tiny.reference.py"))
    seeded = run.load_module(os.path.join(tiny_tree, "benchmark/seeded.py"))
    compare = run.load_module(os.path.join(tiny_tree, "benchmark/compare.py"))
    cfg = run.load_json(os.path.join(tiny_tree, "benchmark/configs/tiny.json"))
    kw = dict(global_batch=cfg["local_batch"], tau=cfg["tau"], crop=cfg["crop"],
              n_classes=cfg["n_classes"], dtype=jnp.bfloat16)
    rows = lambda t, w: jax.tree.map(lambda x: x[0], seeded.stack_slice(
        seed, 0, t, 1, 0, cfg["local_batch"], **kw))
    params0 = ref.init_params(seed, cfg["crop"], cfg["n_classes"])
    args = dict(tau=cfg["tau"], solver=cfg["solver"])
    sound = ref.round_reference(params0, rows, seeded.round_key(seed, 0), **args)
    control = ref.round_reference(params0, rows, seeded.round_key(seed, 0),
                                  precision=ref.CONTROL_PRECISION, **args)
    checks = compare.first_round_checks(control, sound, ref.LIMITS)
    assert not all(c["ok"] for c in checks), checks
