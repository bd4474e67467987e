"""The program's own tracing (PR 25): `obs.trace.span` with its two sinks
(the Chrome-trace tracer and a live `jax.profiler` session), the spans of
`train_round` and of the loop's round preparation, the `named_scope` of every
layer, and the round program's account of itself
(`obs.device.program_report`). All on the CPU.
"""
from __future__ import annotations

import functools
import glob
import os
import re
import threading

import jax
import numpy as np
import pytest

from sparknet_tpu import CompiledNet
from sparknet_tpu.model.seq_layers import KEPT_NAMES
from sparknet_tpu.obs import device as obs_device
from sparknet_tpu.obs import trace as obs_trace
from sparknet_tpu.parallel import ParallelTrainer, ShardedTrainer, make_mesh
from sparknet_tpu.solver import SolverConfig
from sparknet_tpu.utils.metrics import PhaseTimers
from sparknet_tpu.zoo import caffenet, lenet

ROUND_SPANS = {"train_round", "round_keys", "h2d", "dispatch"}


def _profiler_options():
    """As the benchmark's traced run starts the profiler: device events and
    annotations, no Python tracer."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    return options


def _host_annotations(trace_dir) -> set:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no xplane file"
    return {e.name for f in files for plane in ProfileData.from_file(f).planes
            if plane.name.startswith("/host:") for line in plane.lines
            for e in line.events}


def _lenet_trainer(cls=ParallelTrainer, n=2, tau=3, **kw):
    net = CompiledNet.compile(lenet(batch=8))
    trainer = cls(net, SolverConfig(base_lr=0.01, momentum=0.9,
                                    lr_policy="fixed"),
                  make_mesh(n), tau=tau, fused_boundary=True, **kw)
    r = np.random.default_rng(0)
    batches = {"data": r.standard_normal((tau, 8 * n, 28, 28, 1)
                                         ).astype(np.float32),
               "label": r.integers(0, 10, (tau, 8 * n, 1)).astype(np.int32)}
    return trainer, batches


# -- span(): two sinks, one clock ---------------------------------------------

def test_span_in_profiler_session_is_recorded_and_annotated(tmp_path):
    import time
    assert obs_trace.active_tracer() is None
    before = time.perf_counter()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_profiler_options())
    try:
        with obs_trace.span("outer", round=7):
            with obs_trace.span("inner", round=7):
                time.sleep(0.002)

        def worker():
            with obs_trace.span("elsewhere"):
                pass
        th = threading.Thread(target=worker, name="lane-two")
        th.start()
        th.join()
        live = obs_trace.session_spans()  # readable while the session runs
    finally:
        jax.profiler.stop_trace()
    after = time.perf_counter()
    spans = {s["name"]: s for s in obs_trace.session_spans()}
    assert set(spans) == {"outer", "inner", "elsewhere"} == {s["name"] for s in live}
    outer, inner = spans["outer"], spans["inner"]
    assert inner["parent"] == outer["id"] and outer["parent"] is None
    assert spans["elsewhere"]["parent"] is None, "the stack is the thread's own"
    assert outer["thread"] == "MainThread" and spans["elsewhere"]["thread"] == "lane-two"
    assert outer["args"] == {"round": 7}
    # time.perf_counter(), the clock of the benchmark's completion stamps
    assert before < outer["t0"] <= inner["t0"] < inner["t1"] <= outer["t1"] < after
    assert inner["t1"] - inner["t0"] >= 0.002
    names = _host_annotations(tmp_path)
    assert {"sparknet:outer", "sparknet:inner", "sparknet:elsewhere"} <= names
    # the record of the last session stays readable; a span after the
    # session is not added to it
    with obs_trace.span("late"):
        pass
    assert {s["name"] for s in obs_trace.session_spans()} == set(spans)


def test_span_records_nothing_when_off():
    """No tracer and no profiler session: nothing is kept anywhere
    (`test_span_noop_when_off`'s guarantee, now for both sinks)."""
    assert obs_trace.active_tracer() is None
    assert not jax.profiler.TraceAnnotation.is_enabled()
    before = obs_trace.session_spans()
    with obs_trace.span("nothing", round=1):
        pass
    assert obs_trace.session_spans() == before
    assert obs_trace.active_tracer() is None


def test_span_feeds_the_chrome_tracer_and_the_session_together(tmp_path):
    jax.profiler.start_trace(str(tmp_path / "prof"),
                             profiler_options=_profiler_options())
    try:
        with obs_trace.tracing() as tr:
            with obs_trace.span("both", step=3):
                pass
    finally:
        jax.profiler.stop_trace()
    chrome = [e for e in tr.events() if e["ph"] == "X"]
    assert [e["name"] for e in chrome] == ["both"]
    assert set(chrome[0]) == {"name", "ph", "cat", "ts", "dur", "pid", "tid", "args"}
    assert [s["name"] for s in obs_trace.session_spans()] == ["both"]


# -- train_round's spans --------------------------------------------------------

@pytest.mark.parametrize("with_timers", [False, True])
def test_train_round_emits_the_same_spans_with_and_without_timers(with_timers):
    trainer, batches = _lenet_trainer()
    if with_timers:
        trainer.phase_timers = PhaseTimers()
    state = trainer.init_state(jax.random.PRNGKey(0))
    with obs_trace.tracing() as tr:
        for i in range(2):
            state, _ = trainer.train_round(state, batches, jax.random.PRNGKey(i))
    events = [e for e in tr.events() if e["ph"] == "X"]
    assert {e["name"] for e in events} == ROUND_SPANS
    # the trainer's own count of dispatched rounds rides on every span
    assert {e["name"] for e in events if e["args"] == {"step": 1}} == ROUND_SPANS
    if with_timers:
        assert set(trainer.phase_timers.count) == ROUND_SPANS - {"train_round"}
        assert trainer.phase_timers.count["dispatch"] == 2


# -- the round program's account of itself ------------------------------------

@pytest.fixture(scope="module")
def lenet_report():
    trainer, batches = _lenet_trainer()
    assert trainer.program_report() is None, "nothing dispatched yet"
    state = trainer.init_state(jax.random.PRNGKey(0))
    trainer.train_round(state, batches, jax.random.PRNGKey(1))
    report = obs_device.program_report("train_round")
    text = trainer._round.lower(*trainer._round_avals).compile().as_text()
    return trainer, report, text


def _own_instructions(text: str) -> set:
    """Names of the instructions of the entry computation and of every
    `while` body: the ops the device runs one by one."""
    names, keep = set(), False
    for line in text.splitlines():
        head = line.split("(", 1)[0]
        if line.startswith("ENTRY") or re.match(r"^%?[\w.\-]*(body|cond)[\w.\-]* ", head):
            keep = True
        elif line.startswith("}"):
            keep = False
        elif keep:
            m = re.match(r"^\s+(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(", line)
            if m and m.group(2) != "parameter":
                names.add("%" + m.group(1).lstrip("%"))
    return names


def test_program_report_maps_the_whole_round(lenet_report):
    trainer, report, text = lenet_report
    assert text.startswith("HloModule jit_train_round")
    ops = report["ops"]
    own = _own_instructions(text)
    assert len(own) > 50 and own <= set(ops), sorted(own - set(ops))[:5]
    assert all(op["phase"] in obs_device.PHASES for op in ops.values())
    assert not any(op["opcode"] == "parameter" for op in ops.values())
    # `prob` and `accuracy` are off the loss's path: compiled away
    layers = {(l.type, l.name) for l in trainer.net.spec.layers_for_phase("TRAIN")
              if l.type not in ("Softmax", "Accuracy")}
    seen = {p: {(op["layer_type"], op["layer"]) for op in ops.values()
                if op["phase"] == p and op["layer"]}
            for p in ("forward", "backward")}
    assert seen["forward"] == layers, "every layer is named, type and name"
    assert {l for l in layers if l[0] in ("Convolution", "InnerProduct", "Pooling")} \
        <= seen["backward"]
    optimizer = [op for op in ops.values() if op["phase"] == "optimizer"]
    assert optimizer and all("solver_update" in op["scope"] for op in optimizer)
    outside = [op for op in ops.values() if op["phase"] == "outside_step"]
    assert any("tau_boundary" in op["scope"] for op in outside)
    assert not any("tau_step" in op["scope"].split("/") for op in outside)
    assert report["recompute"] == {}, "no block, nothing kept"
    assert set(report["memory"]) == {"argument", "output", "alias", "temp"}
    assert all(type(v) is int and v >= 0 for v in report["memory"].values())
    assert report["memory"]["temp"] > 0


def test_program_report_publishes_memory_gauges_when_it_has_run(lenet_report):
    from sparknet_tpu.obs import MetricsRegistry
    _, report, _ = lenet_report
    registry = MetricsRegistry()
    obs_device.attach_program_gauges(registry)  # replays what has been read
    for key in ("temp", "argument", "output"):
        assert registry.gauge(f"sparknet_train_round_{key}_bytes").value() \
            == float(report["memory"][key])
    assert "sparknet_train_round_temp_bytes" in registry.render_prometheus()
    assert obs_device.program_part("memory")["train_round"] == report["memory"]
    assert obs_device.program_report("no_such_program") is None


def test_sharded_trainer_shares_the_names():
    trainer, batches = _lenet_trainer(ShardedTrainer)
    state = trainer.init_state(jax.random.PRNGKey(0))
    trainer.train_round(state, batches, jax.random.PRNGKey(1))
    text = trainer._round.lower(*trainer._round_avals).compile().as_text()
    assert text.startswith("HloModule jit_train_round")
    report = obs_device.program_report("train_round")  # the newest trainer's
    assert report is trainer.program_report()
    assert {op["phase"] for op in report["ops"].values()} == set(obs_device.PHASES)
    ev = trainer._eval.lower(state.params, {
        k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype) for k, v in batches.items()})
    assert "jit_eval_round" in ev.as_text()[:200]


HAND_HLO = """HloModule jit_train_round, is_scheduled=true

%fused_computation.7 (p0: f32[8,4], p1: f32[4,2], p2: f32[8,2]) -> f32[8,2] {
  %p0 = f32[8,4]{1,0} parameter(0)
  %p1 = f32[4,2]{1,0} parameter(1)
  %p2 = f32[8,2]{1,0} parameter(2)
  %dot.3 = f32[8,2]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_round)/while/body/tau_step/transpose(jvp(InnerProduct/fc6))/dot_general" stack_frame_id=4}
  ROOT %subtract.9 = f32[8,2]{1,0} subtract(%p2, %dot.3), metadata={op_name="jit(train_round)/while/body/tau_step/solver_update/sub"}
}

%fused_computation.8 (p0: f32[8,2]) -> f32[8,2] {
  %p0 = f32[8,2]{1,0} parameter(0)
  %c = f32[] constant(0)
  %b = f32[8,2]{1,0} broadcast(%c), dimensions={}
  ROOT %maximum.1 = f32[8,2]{1,0} maximum(%p0, %b), metadata={op_name="jit(train_round)/while/body/tau_step/jvp(ReLU/relu6)/max"}
}

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.0 = f32[] add(%a, %b), metadata={op_name="tau_boundary/psum"}
}

%body.2 (arg: (f32[8,4], f32[4,2], f32[8,2])) -> (f32[8,4], f32[4,2], f32[8,2]) {
  %arg = (f32[8,4]{1,0}, f32[4,2]{1,0}, f32[8,2]{1,0}) parameter(0)
  %get-tuple-element.1 = f32[8,4]{1,0} get-tuple-element(%arg), index=0
  %get-tuple-element.2 = f32[4,2]{1,0} get-tuple-element(%arg), index=1
  %get-tuple-element.3 = f32[8,2]{1,0} get-tuple-element(%arg), index=2
  %copy.5 = f32[8,4]{0,1} copy(%get-tuple-element.1)
  %fusion.769 = f32[8,2]{1,0} fusion(%copy.5, %get-tuple-element.2, %get-tuple-element.3), kind=kOutput, calls=%fused_computation.7, metadata={op_name="jit(train_round)/while/body/tau_step/solver_update/sub"}
  %maximum_fusion.2 = f32[8,2]{1,0} fusion(%fusion.769), kind=kLoop, calls=%fused_computation.8
  %lrn_fwd.4 = f32[8,2]{1,0} custom-call(%maximum_fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_round)/while/body/tau_step/jvp(LRN/norm1)/lrn_fwd"}
  %copy.6 = f32[8,2]{0,1} copy(%lrn_fwd.4)
  ROOT %tuple.1 = (f32[8,4]{1,0}, f32[4,2]{1,0}, f32[8,2]{1,0}) tuple(%get-tuple-element.1, %get-tuple-element.2, %copy.6)
}

%cond.3 (arg: (f32[8,4], f32[4,2], f32[8,2])) -> pred[] {
  %arg = (f32[8,4]{1,0}, f32[4,2]{1,0}, f32[8,2]{1,0}) parameter(0)
  ROOT %constant.9 = pred[] constant(true)
}

ENTRY %main.4 (x: f32[8,4], w: f32[4,2], y: f32[8,2]) -> f32[8,2] {
  %x = f32[8,4]{1,0} parameter(0)
  %w = f32[4,2]{1,0} parameter(1)
  %y = f32[8,2]{1,0} parameter(2)
  %tuple.0 = (f32[8,4]{1,0}, f32[4,2]{1,0}, f32[8,2]{1,0}) tuple(%x, %w, %y)
  %while.1 = (f32[8,4]{1,0}, f32[4,2]{1,0}, f32[8,2]{1,0}) while(%tuple.0), condition=%cond.3, body=%body.2, metadata={op_name="jit(train_round)/while"}
  %get-tuple-element.9 = f32[8,2]{1,0} get-tuple-element(%while.1), index=2
  ROOT %all-reduce.1 = f32[8,2]{1,0} all-reduce(%get-tuple-element.9), replica_groups={}, to_apply=%region_0.1, metadata={op_name="jit(train_round)/tau_boundary/psum"}
}
"""


def test_attribution_rule_by_hand():
    ops = obs_device.parse_hlo_ops(HAND_HLO)
    # the insides of fusions and a reduction's adder are not device ops
    assert not {"%dot.3", "%subtract.9", "%maximum.1", "%add.0", "%arg", "%x"} & set(ops)
    # a fusion that holds a dot is the dot's layer's, whatever XLA fused
    # behind it -- here the solver's subtract, which is the fusion's root
    f = ops["%fusion.769"]
    assert (f["phase"], f["layer_type"], f["layer"]) == ("backward", "InnerProduct", "fc6")
    assert f["scope"] == "while/body/tau_step/transpose(jvp(InnerProduct/fc6))"
    # any other fusion: its root's (it carries no op_name of its own here)
    m = ops["%maximum_fusion.2"]
    assert (m["phase"], m["layer_type"], m["layer"]) == ("forward", "ReLU", "relu6")
    k = ops["%lrn_fwd.4"]
    assert (k["phase"], k["layer"], k["opcode"]) == ("forward", "norm1", "custom-call")
    # the compiler's own copies: the maker of the operand, else the user
    assert ops["%copy.6"]["layer"] == "norm1"
    assert ops["%copy.5"]["layer"] == "fc6", "no operand has a name: its user's"
    assert ops["%all-reduce.1"]["phase"] == "outside_step"
    assert ops["%all-reduce.1"]["scope"] == "tau_boundary"
    assert ops["%while.1"]["opcode"] in obs_device.CONTAINERS
    assert obs_device.scope_of(
        "jit(train_round)/while/body/closed_call/tau_step/solver_update/mul")["phase"] == "optimizer"
    assert obs_device.scope_of("jit(train_round)/dynamic_slice") == {
        "scope": "", "phase": "outside_step", "recomputed": False,
        "layer_type": None, "layer": None}
    assert not any(op["recomputed"] for op in ops.values()), "no block made anything again"


@pytest.fixture(scope="module")
def caffenet_report():
    """(spec, the report of its compiled round) of a tiny CaffeNet."""
    spec = caffenet(batch=2, crop=67, n_classes=16)
    net = CompiledNet.compile(spec)
    trainer = ParallelTrainer(net, SolverConfig(base_lr=0.001, momentum=0.9),
                              make_mesh(1), tau=2, fused_boundary=True,
                              compute_health=False)
    state = trainer.init_state(jax.random.PRNGKey(0))
    r = np.random.default_rng(0)
    batches = {"data": r.standard_normal((2, 2, 67, 67, 3)).astype(np.float32),
               "label": r.integers(0, 16, (2, 2, 1)).astype(np.int32)}
    trainer.train_round(state, batches, jax.random.PRNGKey(1))
    return spec, trainer.program_report()


def test_compiled_round_names_every_caffenet_layer(caffenet_report):
    """A tiny CaffeNet round's compiled text carries every layer's
    `<Type>/<name>` scope (the published size is compiled for a described
    v5e by tests/test_chip_compile.py)."""
    spec, report = caffenet_report
    named = {(op["layer_type"], op["layer"]) for op in report["ops"].values()}
    want = {(l.type, l.name) for l in spec.layers_for_phase("TRAIN")
            if l.type not in ("Softmax", "Accuracy")}  # off the loss's path
    assert want <= named, want - named
    assert {"Convolution", "LRN", "Pooling", "ReLU", "InnerProduct", "Dropout"} \
        <= {t for t, _ in want}


# -- a profile_dir capture of one loop round holds the host spans -------------

def test_profile_dir_capture_holds_the_loops_spans(tmp_path):
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger

    r = np.random.default_rng(0)
    ds = ArrayDataset({
        "data": r.standard_normal((128, 1, 28, 28)).astype(np.float32),
        "label": r.integers(0, 10, (128, 1)).astype(np.int32)})
    cfg = RunConfig(model="lenet", n_devices=1, local_batch=16, tau=2,
                    max_rounds=4, eval_every=0, workdir=str(tmp_path),
                    profile_dir=str(tmp_path / "prof"),
                    trace_out=str(tmp_path / "trace.json"))
    log_path = tmp_path / "l.txt"
    log = Logger(str(log_path), echo=False)
    train(cfg, lenet(batch=16), ds, None, logger=log)
    log.close()
    names = _host_annotations(tmp_path / "prof")
    assert {"sparknet:train_round", "sparknet:dispatch", "sparknet:round_prep",
            "sparknet:round_keys", "sparknet:h2d"} <= names
    session = {s["name"] for s in obs_trace.session_spans()}
    assert {"train_round", "dispatch", "round_keys", "h2d"} <= session
    # the prefetch thread's phases, in the Chrome file with no profiler
    import json
    chrome = {e["name"] for e in json.load(open(cfg.trace_out))["traceEvents"]}
    assert {"round_prep", "sample", "cast", "train_round", "dispatch"} <= chrome
    # the profiled run is the one place the loop asks for the round's memory
    assert "train_round program, bytes per device: argument " in log_path.read_text()


def test_the_window_part_of_a_compiled_text():
    """`obs.device.window` on a made-up compiled text: every layer's kernel
    calls by phase under its own core scope, in the step body that has most;
    the blocks a layer's mask visits of those a causal one would, passed
    through and summed; a layer with no window reports none; a net none of
    whose layers has one reports nothing at all."""
    from sparknet_tpu.model import seq_layers as sl
    call = ('custom-call(%p), custom_call_target="tpu_custom_call", '
            'metadata={op_name="jit(train_round)/tau_step/')
    text = "\n".join([
        "ENTRY %main.1 (p: f32[4]) -> f32[4] {",
        "  %p = f32[4]{0} parameter(0)",
        f"  %fwd.1 = f32[4]{{0}} {call}jvp(GQAttention/l0_attn)/core/splash_mha_fwd/pallas_call\"}}",
        f"  %fwd.2 = f32[4]{{0}} {call}jvp(GQAttention/l1_attn)/core/splash_mha_fwd/pallas_call\"}}",
        f"  %bwd.2 = f32[4]{{0}} {call}transpose(jvp(GQAttention/l1_attn))/core/splash_mha_dkv/pallas_call\"}}",
        f"  %gmm.1 = f32[4]{{0}} {call}jvp(MoE/l1_moe)/experts/gmm/pallas_call\"}}",
        '  %sum.1 = f32[4]{0} add(%fwd.1, %fwd.2), metadata={op_name="jit(train_round)/tau_step/jvp(GQAttention/l0_attn)/core/add"}',
        "  ROOT %out = f32[4]{0} add(%sum.1, %bwd.2)",
        "}"])
    ops = obs_device.parse_hlo_ops(text)
    layers = {"l0_attn": {"window": None, "blocks_visited": 272, "blocks_causal": 272},
              "l1_attn": {"window": 4096, "blocks_visited": 140, "blocks_causal": 272}}
    got = obs_device.window(ops, sl.WINDOW_SCOPES, layers)
    assert got == {
        "layers": {"l0_attn": {**layers["l0_attn"], "core_forward_calls": 1,
                               "core_backward_calls": 0},
                   "l1_attn": {**layers["l1_attn"], "core_forward_calls": 1,
                               "core_backward_calls": 1}},
        "windowed_layers": 1, "blocks_visited": 412, "blocks_causal": 544}
    assert obs_device.window(ops, {}, {}) == {}
    assert "window" in obs_device.REPORT_PARTS


# -- `recomputed`: a block's forward made again for the backward it serves ----

def test_the_flag_tells_a_kernel_made_again_from_its_backward_neighbour():
    """The made-up round of `tests/test_seq_model.py`: of five forward
    kernels one runs under `rematted_computation`; the backward kernel beside
    it, on the same `transpose(` path under the same block, is backward
    proper."""
    from test_seq_model import RECOMPUTE_HLO
    ops = obs_device.parse_hlo_ops(RECOMPUTE_HLO)
    assert {n for n, op in ops.items() if op["recomputed"]} == {
        "%splash_mha_fwd_residuals.3"}
    again, beside = ops["%splash_mha_fwd_residuals.3"], ops["%splash_mha_dkv_no_residuals.1"]
    assert again["phase"] == beside["phase"] == "backward", "no fifth phase"
    assert (again["layer_type"], again["layer"]) == ("MTP", "mtp") == (
        beside["layer_type"], beside["layer"])


_REMAT = "jit(train_round)/while/body/tau_step/transpose(jvp(tau_step))/jvp()/checkpoint/"
REMAT_HLO = f"""HloModule jit_train_round

%fused_proper (p0: f32[8,4], p1: f32[4,2]) -> f32[8,2] {{
  %p0 = f32[8,4]{{1,0}} parameter(0)
  %p1 = f32[4,2]{{1,0}} parameter(1)
  %dot.1 = f32[8,2]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{_REMAT}GatedMLP/l0_mlp/dot_general"}}
  ROOT %mul.1 = f32[8,2]{{1,0}} multiply(%dot.1, %dot.1), metadata={{op_name="{_REMAT}rematted_computation/GatedMLP/l0_mlp/jit(silu)/mul"}}
}}

%fused_again (p0: f32[8,4], p1: f32[4,2]) -> f32[8,2] {{
  %p0 = f32[8,4]{{1,0}} parameter(0)
  %p1 = f32[4,2]{{1,0}} parameter(1)
  %dot.2 = f32[8,2]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{_REMAT}rematted_computation/GatedMLP/l0_mlp/mlp_pre/dot_general"}}
  ROOT %mul.2 = f32[8,2]{{1,0}} multiply(%dot.2, %dot.2), metadata={{op_name="{_REMAT}GatedMLP/l0_mlp/mul"}}
}}

%fused_norm (p0: f32[8,4]) -> f32[8,4] {{
  %p0 = f32[8,4]{{1,0}} parameter(0)
  ROOT %mul.3 = f32[8,4]{{1,0}} multiply(%p0, %p0), metadata={{op_name="{_REMAT}rematted_computation/RMSNorm/l0_mlp_norm/mul"}}
}}

ENTRY %main.1 (x: f32[8,4], w: f32[4,2]) -> f32[8,2] {{
  %x = f32[8,4]{{1,0}} parameter(0)
  %w = f32[4,2]{{1,0}} parameter(1)
  %norm.1 = f32[8,4]{{1,0}} fusion(%x), kind=kLoop, calls=%fused_norm
  %copy.1 = f32[8,4]{{0,1}} copy(%norm.1)
  %again.1 = f32[8,2]{{1,0}} fusion(%copy.1, %w), kind=kOutput, calls=%fused_again
  %copy.2 = f32[8,4]{{0,1}} copy(%x)
  %proper.1 = f32[8,2]{{1,0}} fusion(%copy.2, %w), kind=kOutput, calls=%fused_proper
  %outside.1 = f32[8,2]{{1,0}} add(%again.1, %proper.1), metadata={{op_name="jit(train_round)/rematted_computation/add"}}
  ROOT %update.1 = f32[8,2]{{1,0}} add(%outside.1, %proper.1), metadata={{op_name="jit(train_round)/tau_step/solver_update/rematted_computation/add"}}
}}
"""


@pytest.mark.parametrize("name,phase,again,why", [
    ("%norm.1", "backward", True, "a fusion without a product: its root's path"),
    ("%again.1", "backward", True, "a fusion with a product made again is the "
     "product's, whatever backward arithmetic was fused behind it"),
    ("%proper.1", "backward", False, "a product of the backward pass proper with "
     "a block's recomputed elementwise work fused behind it is the product's"),
    ("%copy.1", "backward", True, "a nameless copy of what a recomputed op made: "
     "its maker's"),
    ("%copy.2", "backward", False, "a nameless copy with no named maker: its "
     "user's, here backward proper"),
    ("%outside.1", "outside_step", False, "outside the step nothing is a block's"),
    ("%update.1", "optimizer", False, "nor under the optimizer"),
])
def test_the_flag_follows_the_attribution_rule(name, phase, again, why):
    op = obs_device.parse_hlo_ops(REMAT_HLO)[name]
    assert op["phase"] == phase
    assert op["recomputed"] is again, why


def test_a_net_without_blocks_flags_nothing(caffenet_report):
    _, report = caffenet_report
    assert {op["phase"] for op in report["ops"].values()} == set(obs_device.PHASES)
    assert not any(op["recomputed"] for op in report["ops"].values())


@functools.cache
def _two_block_ops(model: str, bare: bool):
    """(net, `parse_hlo_ops` of its compiled gradient under the step's
    scope) of a sequence model's tiny file cut to ONE decoder layer and its
    head: two recomputation blocks. `bare`: every block under the bare
    `jax.checkpoint` (what a block was before its layers named anything)."""
    import jax.numpy as jnp
    from model_cases import POS, ROWS, case
    from sparknet_tpu.model import net as net_mod
    net = CompiledNet.compile(case(model).spec(
        num_hidden_layers=1, num_nextn_predict_layers=0,
        **({"hybrid_override_pattern": "M"} if model == "nemotron_h" else {})))
    assert {l.block for l in net.spec.layers} == {None, "l0", "head"}
    loss = net.loss_fn("loss")

    def grad(p, ids):  # (a fresh function a trace: no policy in jax's key)
        with jax.named_scope(obs_device.STEP_SCOPE):
            return jax.value_and_grad(
                lambda p: loss(p, {"tokens": ids}, None)[0])(p)
    with pytest.MonkeyPatch.context() as patch:
        if bare:
            patch.setattr(net_mod, "_kept_names", lambda layers: ())
        text = jax.jit(grad).lower(
            jax.eval_shape(net.init_params, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct((ROWS, POS), jnp.int32)).compile().as_text()
    return net, obs_device.parse_hlo_ops(text)


@pytest.mark.parametrize("kind,model,scope,kept", [
    ("GatedMLP", "glm4_moe_lite", "mlp_pre", "mlp_pre"),
    ("MLAttention", "glm4_moe_lite", "core", "attn_core"),
    ("Mamba2", "nemotron_h", "in_proj", None),
    ("InnerProduct", "glm4_moe_lite", "ip_out", "ip_out")])
def test_a_blocks_products_are_flagged_where_it_makes_them_again(
        kind, model, scope, kept):
    """Under the bare `jax.checkpoint` every product a layer makes under
    `scope` on the forward path is there a second time, flagged; with the
    layer's `KEPT_NAMES` entry no flagged product lies under the maker's
    scope (off the chip the attention core's maker, a kernel, does not run:
    there the kept output spares the core one of its two products). A layer
    type that names nothing (`Mamba2`) reads as under the bare checkpoint."""
    def products(ops, under, again):
        return sum(op.get("matmuls", 1) for op in ops.values()
                   if op["layer_type"] == kind and op["matmul"]
                   and any(part.startswith(under) for part in op["scope"].split("/"))
                   and (op["recomputed"] if again else op["phase"] == "forward"))
    net, built = _two_block_ops(model, bare=False)
    _, bare = _two_block_ops(model, bare=True)
    for ops in (built, bare):
        flagged = [op for op in ops.values() if op["recomputed"]]
        assert flagged and {op["phase"] for op in flagged} == {"backward"}
        assert any(not op["matmul"] for op in flagged), "the norms, made again"
    assert products(bare, scope, True) == products(bare, scope, False) > 0
    if kept is None:
        assert kind not in KEPT_NAMES
        assert products(built, scope, True) == products(built, scope, False) > 0
    else:
        assert kept in KEPT_NAMES[kind]
        assert products(built, net.kept_makers()[kept], True) == 0
        assert products(built, scope, True) < products(bare, scope, True)
