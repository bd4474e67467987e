"""The per-layer metrics that read the program's own account of itself (PR 25):
`scope_math.py` (the traced window's device ops joined with
`program_report("train_round")`), `program_spans.py` (the program's host spans
of the traced stretch) and the eleven readers, by hand on made-up inputs and
once against a real tiny trainer. All on the CPU: counts and arithmetic, never
a device time.
"""
from __future__ import annotations

import importlib.util
import os
import re
import sys
import types

import numpy as np
import pytest

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
CELLS = ["caffenet-train-round", "caffenet-avg4-round"]
NEW = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
       "conv_fc_device_ms", "pool_device_ms", "elementwise_device_ms",
       "lrn_layer_device_ms", "round_outside_step_ms", "round_host_call_ms",
       "round_host_keys_ms", "round_temp_bytes"]


def _op(phase, layer_type=None, layer=None, scope=""):
    return {"scope": scope, "phase": phase, "layer_type": layer_type,
            "layer": layer, "opcode": "fusion"}


#: a made-up report: two conv fusions, a pool op, a ReLU, an LRN kernel and
#: its layout pass, the solver's update, the peeled step's copy
OPS = {
    "%fusion.1": _op("forward", "Convolution", "conv1", "tau_step/jvp(Convolution/conv1)"),
    "%fusion.2": _op("backward", "InnerProduct", "fc6", "tau_step/transpose(jvp(InnerProduct/fc6))"),
    "%select-and-scatter.1": _op("backward", "Pooling", "pool1"),
    "%maximum_fusion.1": _op("forward", "ReLU", "relu7"),
    "%rng_fusion.1": _op("forward", "Dropout", "drop7"),
    "%lrn_fwd.1": _op("forward", "LRN", "norm1"),
    "%convert_bitcast_fusion.22": _op("backward", "LRN", "norm1"),
    "%multiply_fusion.3": _op("optimizer", None, None, "tau_step/solver_update"),
    "%copy.9": _op("outside_step"),
    "%all-reduce.1": _op("outside_step", None, None, "tau_boundary"),
}
#: seconds over TWO traced rounds, mean over the chips, as trace_reduce's
#: `device_ops` holds them; `%fusion` is the benchmark's own stack-making
DEVICE_OPS = [("%fusion.1", 0.200), ("%fusion.2", 0.300),
              ("%select-and-scatter.1", 0.050), ("%maximum_fusion.1", 0.020),
              ("%rng_fusion.1", 0.010), ("%lrn_fwd.1", 0.060),
              ("%convert_bitcast_fusion.22", 0.040),
              ("%multiply_fusion.3", 0.008), ("%copy.9", 0.020),
              ("%all-reduce.1", 0.004), ("%fusion", 0.008)]
REPORT = {"memory": {"argument": 4532463104, "output": 487816704,
                     "alias": 487815680, "temp": 4788398592}, "ops": OPS}


def _fake_run(device_ops=DEVICE_OPS, trace=True):
    ctx = types.SimpleNamespace(load=load)
    return types.SimpleNamespace(
        ctx=ctx, trace={"rounds": 2, "device_ops": list(device_ops)}
        if trace else None)


@pytest.fixture
def with_report(monkeypatch):
    """scope_math with the made-up report in the place of the program's."""
    sm = load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: (REPORT, 2.5)})
    monkeypatch.setattr(sm, "_joined", {})
    return sm


# -- the arithmetic, by hand ---------------------------------------------------

def test_join_by_hand_and_the_three_percent_rule():
    sm = load("scope_math.py")
    j = sm.join(DEVICE_OPS, OPS, rounds=2)
    assert [n for n, _ in j["unmatched"]] == ["%fusion"]
    assert j["unmatched"][0][1] == pytest.approx(4.0)  # ms a round
    total_ms = 1e3 * sum(s for _, s in DEVICE_OPS) / 2
    assert total_ms == pytest.approx(360.0)
    assert j["unmatched_share"] == pytest.approx(4.0 / 360.0) and j["ok"]
    by = {n: ms for n, ms, _ in j["matched"]}
    assert by["%fusion.2"] == pytest.approx(150.0) and len(by) == 10
    # the other way: 3.5 % of the window's op time under names the program
    # does not hold is too much to call the rest a partition
    more = DEVICE_OPS[:-1] + [("%fusion", 0.0256)]
    j2 = sm.join(more, OPS, rounds=2)
    assert j2["unmatched_share"] == pytest.approx(12.8 / 368.8) and not j2["ok"]
    edge = sm.join([("%fusion.1", 0.97), ("%x", 0.03)], OPS, rounds=1)
    assert edge["ok"], "3 % itself is within the rule"
    assert sm.join([], OPS, rounds=2) == {
        "matched": [], "unmatched": [], "unmatched_share": 1.0, "ok": False}
    layers = sm.by_layer(j)
    assert layers[0] == ["backward", "InnerProduct/fc6", pytest.approx(150.0)]
    assert ["optimizer", "solver_update", pytest.approx(4.0)] in layers
    assert ["outside_step", "tau_boundary", pytest.approx(2.0)] in layers
    assert ["outside_step", "-", pytest.approx(10.0)] in layers


def test_sums_by_phase_and_by_layer_type(with_report, capsys):
    sm, run = with_report, _fake_run()
    assert sm.phase_ms(run, "forward") == pytest.approx(100 + 10 + 5 + 30)
    assert sm.phase_ms(run, "backward") == pytest.approx(150 + 25 + 20)
    assert sm.phase_ms(run, "optimizer") == pytest.approx(4.0)
    assert sm.phase_ms(run, "outside_step") == pytest.approx(12.0)
    # the four phases partition what was matched
    assert sum(sm.phase_ms(run, p) for p in
               ("forward", "backward", "optimizer", "outside_step")) \
        == pytest.approx(356.0)
    assert sm.layer_type_ms(run, "Convolution", "InnerProduct") == pytest.approx(250.0)
    assert sm.layer_type_ms(run, "Pooling") == pytest.approx(25.0)
    assert sm.layer_type_ms(run, "ReLU", "Dropout") == pytest.approx(15.0)
    assert sm.layer_type_ms(run, "LRN") == pytest.approx(50.0), \
        "the kernel and the layout pass around it"
    assert sm.memory_bytes(run, "temp") == 4788398592
    notes = [l for l in capsys.readouterr().out.splitlines() if "scope_join" in l]
    assert len(notes) == 1, "one note line a run"
    import json
    note = json.loads(notes[0])
    assert note["unmatched_ms_per_round"] == [["%fusion", pytest.approx(4.0)]]
    assert note["program_report_s"] == 2.5 and note["matched_ops"] == 10


def test_too_much_unmatched_leaves_the_metrics_out(with_report, capsys):
    run = _fake_run(DEVICE_OPS[:-1] + [("%fusion", 0.0256)])
    assert with_report.phase_ms(run, "forward") is None
    assert with_report.layer_type_ms(run, "LRN") is None
    assert '"%fusion"' in capsys.readouterr().out, "the names are shown"
    assert with_report.memory_bytes(run, "temp") == 4788398592


def test_span_per_round_by_hand():
    ps = load("program_spans.py")
    s = lambda name, step, t0, t1: {"name": name, "args": {"step": step},
                                    "t0": t0, "t1": t1}
    spans = [s("train_round", 7, 0.0, 0.050),       # the profiler's start
             s("train_round", 8, 1.0, 1.002), s("round_keys", 8, 1.0, 1.0005),
             s("train_round", 9, 2.0, 2.004), s("round_keys", 9, 2.0, 2.0015),
             s("round_keys", 9, 2.002, 2.0025),      # twice in one round: summed
             {"name": "train_round", "args": {}, "t0": 5.0, "t1": 9.0}]
    assert ps.per_round(spans, "train_round") == pytest.approx(3.0)
    assert ps.per_round(spans, "round_keys") == pytest.approx((0.5 + 2.0) / 2)
    assert ps.per_round(spans[:1], "train_round") is None, "one round: dropped"
    assert ps.per_round(spans, "absent") is None


# -- every new reader: a value from a traced run, None without a trace ---------

@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric, with_report):
    reader = load(os.path.join("readers", metric + ".py"))
    assert reader.read(_fake_run(trace=False)) is None


@pytest.mark.parametrize("metric,value", [
    ("step_forward_ms", 145.0), ("step_backward_ms", 195.0),
    ("step_optimizer_ms", 4.0), ("conv_fc_device_ms", 250.0),
    ("pool_device_ms", 25.0), ("elementwise_device_ms", 15.0),
    ("lrn_layer_device_ms", 50.0), ("round_outside_step_ms", 12.0),
    ("round_temp_bytes", 4788398592.0)])
def test_new_reader_on_a_made_up_traced_run(metric, value, with_report):
    reader = load(os.path.join("readers", metric + ".py"))
    assert float(reader.read(_fake_run())) == pytest.approx(value)


def test_readers_return_none_for_a_program_with_no_account_of_itself(monkeypatch):
    """As on the parent commit: `program_report` and `session_spans` do not
    exist there, and the readers leave their metrics out without raising."""
    import sparknet_tpu.obs.device as device
    import sparknet_tpu.obs.trace as trace
    sm = load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {})
    monkeypatch.setattr(sm, "_joined", {})
    monkeypatch.delattr(device, "program_report")
    monkeypatch.delattr(trace, "session_spans")
    for metric in NEW:
        assert load(os.path.join("readers", metric + ".py")).read(_fake_run()) is None


def test_the_eleven_entries_are_appended_for_both_cells():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert names[-11:] == NEW, "new entries go at the end of the list"
    for m in BENCHMARK["per_layer"][-11:]:
        assert m["workloads"] == CELLS and m["better"] == "lower"
        assert m["moves"] == "train_round_rate"
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
    by = {m["name"]: m for m in BENCHMARK["per_layer"]}
    assert by["round_host_call_ms"]["source"] == "program_span"
    assert by["round_temp_bytes"]["source"] == "program_counter"
    assert by["lrn_layer_device_ms"]["layer"] == by["lrn_kernel_ms"]["layer"]
    assert by["step_forward_ms"]["layer"] == by["train_mfu"]["layer"]
    assert by["round_outside_step_ms"]["layer"] == by["round_device_ms"]["layer"]


# -- against the real program, at a tiny size ----------------------------------

def test_readers_against_a_real_tiny_trainer(monkeypatch, tmp_path):
    """A lenet round on two virtual devices inside a profiler session: the
    span readers read the session's record, and a window made of the
    report's own names (a CPU trace has no device plane) joins whole."""
    import jax
    from sparknet_tpu import CompiledNet
    from sparknet_tpu.parallel import ParallelTrainer, make_mesh
    from sparknet_tpu.solver import SolverConfig
    from sparknet_tpu.zoo import lenet

    trainer = ParallelTrainer(
        CompiledNet.compile(lenet(batch=8)),
        SolverConfig(base_lr=0.01, momentum=0.9, lr_policy="fixed"),
        make_mesh(2), tau=2, fused_boundary=True)
    r = np.random.default_rng(0)
    batches = {"data": r.standard_normal((2, 16, 28, 28, 1)).astype(np.float32),
               "label": r.integers(0, 10, (2, 16, 1)).astype(np.int32)}
    state = trainer.init_state(jax.random.PRNGKey(0))
    state, _ = trainer.train_round(state, batches, jax.random.PRNGKey(0))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(3):
            state, loss = trainer.train_round(state, batches, jax.random.PRNGKey(i))
        float(loss)
    finally:
        jax.profiler.stop_trace()
    sm = load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {})
    monkeypatch.setattr(sm, "_joined", {})
    report, seconds = sm.report()
    assert report is trainer.program_report() and seconds > 0
    names = [n for n, op in report["ops"].items()
             if op["opcode"] not in ("while", "call", "conditional")]
    run = _fake_run([(n, 1e-3) for n in names] + [("%not_the_rounds", 1e-3)])
    values = {m: load(os.path.join("readers", m + ".py")).read(run) for m in NEW}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(0.5 * len(names))  # 1 ms over 2 rounds
    assert values["conv_fc_device_ms"] > 0 and values["pool_device_ms"] > 0
    assert values["lrn_layer_device_ms"] == 0.0, "lenet has no LRN"
    assert values["round_temp_bytes"] == report["memory"]["temp"]
    # three rounds in the session, the first dropped
    assert 0 < values["round_host_keys_ms"] < values["round_host_call_ms"]
