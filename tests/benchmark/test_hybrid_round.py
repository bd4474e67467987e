"""The hybrid sequence-model configuration's files (`lfm2-8b-a1b-ep4-tau4`:
gated short convolutions among grouped-query attention, experts without a
shared one, a tied head) through the `token-round` traffic, on the CPU at a
tiny size: a throw-away cell added as new files is `correct`; it is not when
the round returns its state unchanged, when the convolution loses a tap, or
under the fp8 control; `hybrid_lm_flops.py` gives hand-worked numbers; the
round's ops are attributed to the new scopes and the five new readers return
numbers. Counts and arithmetic only, never a device time.
"""
from __future__ import annotations

import json
import os
import shutil
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL, NAME = "lfm2-8b-a1b-train-round", "lfm2-8b-a1b-ep4-tau4"
NEW = ["shortconv_device_ms", "shortconv_mix_roofline", "gqa_device_ms",
       "gqa_core_roofline", "hybrid_lm_train_mfu"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "moe_experts_device_ms",
          "moe_experts_roofline", "moe_route_device_ms", "moe_dropped_slots",
          "moe_load_max_over_min", "lm_head_loss_device_ms"]


# the token cells' shared helpers: a checkout's run.py as a module, a run's
# check notes, a made-up traced run
from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))

#: the tiny configuration: every mechanism of the published one (a leading
#: dense layer, then attention among convolutions, expert layers holding 2 of
#: 8 experts, top 2, no shared one, a tied head over a sliced vocabulary), at
#: widths a test run can hold
TINY = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, num_experts=2,
    num_experts_per_tok=2, num_hidden_layers=4, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv"], vocab_size=256,
    local_batch=2, seq_len=32, tau=2,
    share=dict(chips_sharing_a_layer=4, num_experts=8, experts_held=[2, 2],
               vocab_rows=[0, 256]))
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: (seeds 31-36): the program's probe_diff read 0.0089-0.0124 and the fp8
#: control's 0.0905-0.0922; update_gap 0.0029-0.0107 sound, 0.066-0.102 with
#: a convolution of two taps (momentum_gap 0.0025-0.0229 against
#: 0.064-0.074); loss_gap 3e-5-1.4e-4 sound (the fp8 control's 4.3e-4-6.3e-4).
TINY_LIMITS = {"loss_gap": 1.0e-3, "update_gap": 0.03, "momentum_gap": 0.045,
               "probe_diff": 0.03, "routing_diff_share": 0.2}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "token-round",
                           "chips": 1}
    assert "1/4 of a deployment's" in cells[CELL]["why"] and len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    for name in NEW:
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "train_round_rate"
        assert os.path.exists(os.path.join(BENCH, "readers", name + ".py"))
    for name in SHARED + ["train_round_rate"]:
        assert CELL in by[name]["workloads"], name
    for name in ("train_mfu", "lm_train_mfu", "mla_device_ms", "mla_core_roofline",
                 "mtp_device_ms", "lrn_roofline", "avg_collective_ms"):
        assert CELL not in by[name]["workloads"], name
    reported = [m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]]
    assert len(reported) == 22 == len(NEW) + len(SHARED)
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1
    assert by["shortconv_mix_roofline"]["unit"] == by["gqa_core_roofline"]["unit"] == "%"


def test_the_configuration_file_holds_the_published_keys():
    """Every number of the public config.json under its own key, but the
    `reduced` ones; the share block and the held counts agree; the counts of
    ISSUE 31's table, re-reckoned."""
    published = dict(
        conv_L_cache=3, conv_bias=False, hidden_size=2048, intermediate_size=7168,
        max_position_embeddings=128000, model_type="lfm2_moe",
        moe_intermediate_size=1792, norm_eps=1e-5, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=4, num_key_value_heads=8,
        rope_theta=1000000, routed_scaling_factor=1, use_expert_bias=True)
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["num_hidden_layers", "layer_types", "num_dense_layers",
                                 "num_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (9, 1, 8, 16384)
    pub = CONFIG["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"], pub["num_experts"],
            pub["vocab_size"]) == (24, 2, 32, 65536)
    assert len(pub["layer_types"]) == 24 and pub["layer_types"].count("conv") == 18
    # the layers kept: the published list's entries 1 to 9, two whole periods
    assert CONFIG["layer_types"] == pub["layer_types"][1:10]
    assert CONFIG["layer_types"].count("full_attention") == 2
    share = CONFIG["share"]
    assert share["num_experts"] == 32 and share["experts_held"] == [0, 8]
    assert share["vocab_rows"] == [0, 65536 // 4] and share["chips_sharing_a_layer"] == 4
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"]) == (4, 2, 8192)
    for key in ("deployment", "expert_load", "changed_from_source", "assumed"):
        assert CONFIG[key], key
    for key in ("head_dim", "qk_norm", "conv_order", "rotary_pairing",
                "tie_word_embeddings", "weights_seed"):
        assert key in CONFIG["assumed"], key
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    assert count("l0_conv") == 16_783_360 and count("l1_attn") == 10_485_888
    assert count("l0_mlp") == 44_040_192 and count("embed") == 33_554_432
    assert count("l1_moe") == 8 * 11_010_048 + 2048 * 32 + 32
    assert "lm_head" not in shapes, "tied: the head holds nothing of its own"
    assert ref.n_params() == 921_256_448, "ISSUE 31's table, re-reckoned"
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.lfm2_moe(CONFIG, rows=2, positions=8192))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    from sparknet_tpu.model.seq_layers import moe_capacity
    assert moe_capacity(net.spec.layer_by_name("l1_moe").moe, 2 * 8192) == 32768


# -- hybrid_lm_flops by hand -------------------------------------------------

def test_hybrid_lm_flops_by_hand():
    hybrid, lm = load("hybrid_lm_flops.py"), load("lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 8192
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    gqa = 2048 * (2048 + 2 * 512) + 2048 * 2048
    expert = 3 * 2048 * 1792
    even = hybrid.even_slots_per_row(layers, p)
    assert even == {f"l{i}_moe": p * 4 * 8 / 32 for i in range(1, 9)}
    macs = hybrid.forward_macs_per_row(layers, p, even)
    head = 2048 * 16384
    dense = p * (7 * conv + 2 * gqa + 3 * 2048 * 7168 + 8 * 2048 * 32 + head)
    assert macs["dense"] == pytest.approx(dense)
    assert macs["experts"] == pytest.approx(8 * p * expert)
    assert macs["core"] == pytest.approx(2 * (p * (p + 1) / 2) * 32 * 128)
    # the kinds lm_flops.py knows are its own terms: the difference is the new kinds'
    base = lm.forward_macs_per_row(layers, p, even)
    assert macs["dense"] - base["dense"] == pytest.approx(p * (7 * conv + 2 * gqa))
    assert base["core"] == 0 and base["experts"] == macs["experts"]
    per_row = hybrid.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values()))
    # ISSUE 31: 2.03 GFLOP a token, 33.2 TFLOP a step of two rows
    assert per_row / p == pytest.approx(2.03e9, rel=0.01)
    assert 2 * per_row == pytest.approx(33.2e12, rel=0.01)
    core = hybrid.gqa_core_step_cost(layers, 2, p, 2)
    assert core["ops"] == pytest.approx(6 * 2 * macs["core"])
    assert core["bytes"] == 2 * (2 * p * 6 * 64 * (32 + 8)) * 2
    mix = hybrid.shortconv_mix_step_cost(layers, 2, p, 2)
    assert mix["bytes"] == 7 * 11 * 2 * p * 2048 * 2
    assert mix["ops"] == 7 * 3 * 7 * 2 * p * 2048
    assert mix["bytes"] / 819e9 > mix["ops"] / 197e12, "bytes bind, not operations"
    fewer = hybrid.train_flops_per_row(layers, p, {k: v / 2 for k, v in even.items()})
    assert per_row - fewer == pytest.approx(6 * macs["experts"] / 2)
    # a table without the new kinds reads as lm_flops.py reads it
    glm = RUN.load_module(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.reference.py"))
    assert hybrid.train_flops_per_row(glm.LAYERS, p) == lm.train_flops_per_row(glm.LAYERS, p)
    assert hybrid.gqa_core_step_cost(glm.LAYERS, 2, p, 2) == {"ops": 0.0, "bytes": 0.0}


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-hybrid-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-hybrid", model="benchmark/configs/tiny-hybrid.json",
               reference="benchmark/configs/tiny-hybrid.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-hybrid.json", json.dumps(cfg))
    write("benchmark/configs/tiny-hybrid.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_hybrid_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    write("benchmark/traffic/tiny-hybrid.json", json.dumps(
        {"driver": "token-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-hybrid", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-hybrid.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-hybrid", "config": "tiny-hybrid",
                               "traffic": "tiny-hybrid", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-hybrid")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-hybrid", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_hybrid_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=2_500_000_031)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff",
                           "moe_dropped_slots", "routing_diff_share"}
    assert out["correct"] is True, checks
    assert checks["moe_dropped_slots"]["value"] == 0 == checks["moe_dropped_slots"]["limit"]
    assert set(checks["routing_diff_share"]["by_layer"]) == {"l1_moe", "l2_moe", "l3_moe"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    moe = run_note["moe"]
    assert moe["slots_dropped"] == 0 and moe["load_max_over_min"] >= 1
    assert set(moe["by_layer"]) == {f"l{i}_moe_counters" for i in (1, 2, 3)}
    # 64 tokens a step, top 2 of 8, 2 held: 32 slots a step if spread evenly
    assert 5 < moe["by_layer"]["l1_moe_counters"]["slots_landed_per_step"] < 100
    assert run_note["tokens_per_s_per_chip"] == pytest.approx(
        32 * out["metrics"]["train_round_rate"]["value"])


def test_correct_is_false_when_the_round_returns_its_state_unchanged(tiny_tree, monkeypatch, capsys):
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    real = ParallelTrainer.train_round

    def lazy_round(self, state, batches, rng, **kw):
        import jax
        _, loss = real(self, jax.tree.map(lambda x: x.copy(), state), batches, rng, **kw)
        return state, loss

    monkeypatch.setattr(ParallelTrainer, "train_round", lazy_round)
    out = _run_tiny(tiny_tree, seed=32, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["update_gap"]["ok"]
    assert checks["loss_gap"]["ok"]


def test_correct_is_false_when_the_convolution_has_two_taps(tiny_tree, monkeypatch, capsys):
    """The short convolution with its oldest tap left out (position t no
    longer sees t - 2): every layer above reads another stream."""
    from sparknet_tpu.model import seq_layers
    real = seq_layers.causal_taps
    monkeypatch.setattr(seq_layers, "causal_taps",
                        lambda s, w: real(s, w.at[:, 0].set(0.0)))
    out = _run_tiny(tiny_tree, seed=33, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["update_gap"]["ok"]
    assert not checks["momentum_gap"]["ok"]


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-hybrid")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=seed, seconds=0.0, trace=trace,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "token-round.py"))
    return ctx, driver, driver.Program(ctx)


def test_the_fp8_control_fails_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's: at least one limit must catch it."""
    ctx, _, prog = _program(tiny_tree, 34)
    _, rows = prog.stack_makers()
    reference = prog.reference_round(rows)
    assert ctx.reference.CONTROL_PRECISION == "fp8"
    assert ctx.reference.PROBE_LEAF == ("l0_mlp", "down")
    control = prog.reference_round(rows, ctx.reference.CONTROL_PRECISION)
    compare = ctx.load("compare.py")
    limits = {k: v for k, v in TINY_LIMITS.items() if k != "routing_diff_share"}
    failed = [c["name"] for c in compare.first_round_checks(control, reference, limits)
              if not c["ok"]]
    assert "probe_diff" in failed, failed
    sound = compare.first_round_checks(reference, reference, limits)
    assert all(c["ok"] and c["value"] == 0 for c in sound)


# -- the new readers, against the real program at a tiny size ----------------

def test_the_round_is_attributed_to_the_new_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the new
    layer types and sub-scopes, a window made of the report's own names (a
    CPU trace has no device plane) joins with nothing unmatched, and all
    22 readers of the cell return numbers."""
    ctx, driver, prog = _program(tiny_tree, 35, trace=True)
    make_stack, _ = prog.stack_makers()
    program = prog.check_round(make_stack(0))
    names = list(prog.trainer.net.counter_blobs()["l1_moe_counters"])
    moe = driver.counter_summary(names, [program["counters"]], prog.tau)
    assert moe["slots_dropped"] == 0

    sm = ctx.load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {})
    monkeypatch.setattr(sm, "_joined", {})
    report, _ = sm.report()
    assert report is prog.trainer.program_report()
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "ShortConv", "GQAttention", "GatedMLP", "MoE",
            "Eltwise", "InnerProduct", "SoftmaxWithLoss"} <= types_seen
    assert not {"MLAttention", "MTP"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for part in ("/in_proj", "/mix", "/out_proj", "GQAttention/l1_attn)/core",
                 "/router", "/dispatch", "/experts", "/combine", "solver_update",
                 "tau_boundary"):
        assert part in scopes, part
    assert "/shared" not in scopes  # no shared expert
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("ShortConv", "GQAttention", "MoE", "GatedMLP", "InnerProduct"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {"moe": moe})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    device = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
              "round_outside_step_ms", "round_temp_bytes", "moe_experts_device_ms",
              "moe_experts_roofline", "moe_route_device_ms", "moe_dropped_slots",
              "moe_load_max_over_min", "lm_head_loss_device_ms"]
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in NEW + device}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    for k in ("shortconv_device_ms", "gqa_device_ms", "moe_experts_device_ms",
              "moe_route_device_ms", "lm_head_loss_device_ms"):
        assert 0 < values[k] < sum(parts), k
    # three short convolutions against one attention layer
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    assert values["shortconv_device_ms"] == pytest.approx(by_type("ShortConv"))
    assert values["gqa_device_ms"] == pytest.approx(by_type("GQAttention"))
    # utilisation and the two shares by hand
    hybrid = ctx.load("hybrid_lm_flops.py")
    landed = {b[:-len("_counters")]: v["slots_landed_per_step"] / 2
              for b, v in moe["by_layer"].items()}
    per_row = hybrid.train_flops_per_row(prog.layers, 32, landed)
    assert values["hybrid_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    mix_ms = sum(0.5 for op in own.values() if op["layer_type"] == "ShortConv"
                 and "/mix/" in "/" + op["scope"] + "/")
    cost = hybrid.shortconv_mix_step_cost(prog.layers, 2, 32, 2)
    assert values["shortconv_mix_roofline"] == pytest.approx(
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * mix_ms))
    assert fake.notes["shortconv_mix_roofline_bound"] == "bytes"
    core_ms = sum(0.5 for op in own.values() if op["layer_type"] == "GQAttention"
                  and "/core/" in "/" + op["scope"] + "/")
    cost = hybrid.gqa_core_step_cost(prog.layers, 2, 32, 2)
    assert values["gqa_core_roofline"] == pytest.approx(
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * core_ms))


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load)
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", ["shortconv_device_ms", "shortconv_mix_roofline",
                                    "gqa_device_ms", "gqa_core_roofline"])
def test_new_scope_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has neither layer type (the parent
    commit's, or the other sequence model's): 0 ms under the types, and the
    two shares return nothing and do not raise."""
    sm = load("scope_math.py")
    op = {"scope": "tau_step/jvp(MLAttention/l0_attn)/core", "phase": "forward",
          "layer_type": "MLAttention", "layer": "l0_attn"}
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: ({"ops": {"%a": op}}, 0.0)})
    monkeypatch.setattr(sm, "_joined", {})
    ctx = types.SimpleNamespace(load=load)
    run = types.SimpleNamespace(
        ctx=ctx, trace={"rounds": 1, "window_s": 1.0, "device_ops": [("%a", 1e-3)]},
        notes={}, device_kind="TPU v5 lite")
    got = load(os.path.join("readers", metric + ".py")).read(run)
    assert got in (None, 0), got
    if metric.endswith("roofline"):
        assert got is None
