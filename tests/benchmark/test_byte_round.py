"""The dense byte-level configuration's files (`evabyte-l4-tau4`: EVA attention
-- an exact causal window beside chunk summaries under one softmax -- norms
scaled by 1 + w, a float32 residual stream, eight next-byte heads) through the
`byte-round` traffic, on the CPU at a tiny size: a throw-away cell added as
new files is `correct`; it is not when the round returns its state unchanged,
when the summaries are masked out, when the norm's offset is dropped, or
under the fp8 control; `eva_lm_flops.py` gives hand-worked numbers; the
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
CELL, NAME = "evabyte-train-round", "evabyte-l4-tau4"
NEW = ["eva_device_ms", "eva_core_roofline", "eva_summary_roofline",
       "eva_lm_train_mfu", "eva_core_blocks_visited"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "lm_head_loss_device_ms"]

# the token cells' shared helpers: a checkout's run.py as a module, a run's
# check notes, a made-up traced run
from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))

#: the tiny configuration: every mechanism of the published one (rows of four
#: windows of four chunks, so the last window reads twelve summaries; unit
#: offset norms, the float32 residual, three next-byte heads), at widths a
#: test run can hold
TINY = dict(hidden_size=64, intermediate_size=96, num_attention_heads=2,
            num_key_value_heads=2, window_size=8, chunk_size=2, num_pred_heads=3,
            vocab_size=32, num_hidden_layers=2, local_batch=1, seq_len=32, tau=2,
            share=dict(chips_sharing_a_layer=1, vocab_rows=[0, 32]))
#: the tiny configuration's limits, from CPU readings of this file's own runs
TINY_LIMITS = {"loss_gap": 1.0e-3, "update_gap": 0.03, "momentum_gap": 0.03,
               "probe_diff": 0.05}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "byte-round",
                           "chips": 1}
    assert "1x16384" in cells[CELL]["why"] and len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    for name in NEW:
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "train_round_rate"
        assert os.path.exists(os.path.join(BENCH, "readers", name + ".py"))
    for name in SHARED + ["train_round_rate"]:
        assert CELL in by[name]["workloads"], name
    for m in BENCHMARK["per_layer"]:  # no expert, start-up or other model's metric
        if m["name"].startswith(("moe_", "setup_", "mla_", "gqa_", "kda_", "mtp_", "lrn_")):
            assert CELL not in m["workloads"], m["name"]
    reported = {m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
    assert reported == set(NEW + SHARED)
    assert cells[CELL]["chips"] == 1
    for name in ("eva_core_roofline", "eva_summary_roofline", "eva_lm_train_mfu"):
        assert by[name]["unit"] == "%" and by[name]["better"] == "higher"
    assert by["eva_core_roofline"]["layer"] == "kernels" == by["eva_summary_roofline"]["layer"]
    assert by["eva_core_blocks_visited"]["source"] == "program_counter"
    traffic = RUN.load_json(os.path.join(BENCH, "traffic", "byte-round.json"))
    assert traffic["driver"] == "byte-round" and traffic["warmup_rounds"] == 3
    assert (traffic["trace_skip_rounds"], traffic["trace_rounds"]) == (1, 2)


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog's row under its own name and value, but the
    `reduced` one; ISSUE 40's parameter count, re-reckoned."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    row = [json.loads(l) for l in open(catalog) if '"EvaByte"' in l] \
        if os.path.exists(catalog) else []
    published = row[0]["config"] if row else dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, window_size=2048, chunk_size=16, num_pred_heads=8,
        vocab_size=320, max_seq_length=32768, rope_theta=100000, rms_norm_eps=1e-05,
        norm_add_unit_offset=True, fp32_skip_add=True, fp32_logits=True,
        init_std=0.01275, attention_class="eva", tie_word_embeddings=False)
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    same = {k: v for k, v in published.items() if k not in CONFIG["reduced"]}
    assert {k: CONFIG[k] for k in same} == same
    if row:
        assert CONFIG["source"] == row[0]["source_url"]
        assert CONFIG["published"] == {k: published[k] for k in CONFIG["reduced"]}
    assert CONFIG["num_hidden_layers"] == 4 and CONFIG["published"] == {"num_hidden_layers": 32}
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"]) == (4, 1, 16384)
    assert CONFIG["model_type"] == "evabyte" and CONFIG["precision"] == "bfloat16"
    assert CONFIG["share"] == {"chips_sharing_a_layer": 1, "vocab_rows": [0, 320]}
    for key in ("deployment", "memory", "changed_from_source", "assumed", "sample"):
        assert CONFIG[key], key
    for key in ("summary_keys", "summary_values", "exact_set", "one_softmax", "rotary",
                "heads", "initialisation", "weights_seed", "solver", "one_document"):
        assert key in CONFIG["assumed"], key
    assert "16,384 of the published max_seq_length 32,768" in CONFIG["changed_from_source"]["seq_len"]
    assert "as recalled" in CONFIG["assumed"]["summary_keys"]
    assert "as recalled" in CONFIG["assumed"]["summary_values"]
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    assert count("l0_attn") == 4 * 4096 ** 2 + 2 * 32 * 128
    assert count("l0_mlp") == 3 * 4096 * 11008
    layer = count("l0_attn") + count("l0_mlp") + count("l0_attn_norm") + count("l0_mlp_norm")
    assert layer == 202_391_552
    assert count("embed") == 320 * 4096 and count("lm_head") == 4096 * 8 * 320
    assert ref.n_params() == 4 * layer + 1_310_720 + 4096 + 10_485_760 \
        == 821_366_784 == CONFIG["n_params"]
    assert ref.PROBE_LEAF == ("l0_attn", "phi") and ref.CONTROL_PRECISION == "fp8"
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"
    assert 'default_matmul_precision("highest")' in src and "pallas" not in src


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.evabyte(CONFIG, rows=1, positions=16384))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    assert net.eva_scopes() == ({"EVAttention": ("summaries", "core")}, {
        "keys_per_query": 17408, "blocks_visited": 76, "blocks": 544})


# -- eva_lm_flops by hand ----------------------------------------------------

def test_eva_lm_flops_by_hand():
    eva, lm = load("eva_lm_flops.py"), load("lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 16384
    macs = eva.forward_macs_per_row(layers, p)
    dense = p * (4 * (4 * 4096 ** 2 + 3 * 4096 * 11008) + 4096 * 2560)
    assert macs["dense"] == pytest.approx(dense)
    assert macs["experts"] == 0
    # a query: its window's causal keys and 128 summaries a window before it
    own = 8 * 2048 * 2049 / 2
    summaries = 128 * 2048 * (0 + 1 + 2 + 3 + 4 + 5 + 6 + 7)
    assert own + summaries == 24_125_440
    attn = dict(layers)["l0_attn"] if False else layers[2][2]
    assert eva.core_pairs(attn, p) == own + summaries
    brute = sum((i % 2048) + 1 + (i // 2048) * 128 for i in range(p))
    assert brute == own + summaries
    assert summaries / p == 448, "448 summaries a query on average"
    assert macs["core"] == 4 * (own + summaries) * 2 * 128 * 32
    assert macs["summaries"] == 4 * p * 2 * 128 * 32
    per_row = eva.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values()))
    # ISSUE 40 reckoned 4.92 GFLOP a position in the dense products, 0.29 in
    # the core, 85.4 TFLOP a row
    assert 6 * macs["dense"] / p == pytest.approx(4.92e9, rel=0.002)
    assert 6 * macs["core"] / p == pytest.approx(0.29e9, rel=0.01)
    assert per_row == pytest.approx(85.4e12, rel=0.002)
    cost = eva.eva_core_step_cost(layers, 1, p, 2)
    assert cost["ops"] == 6 * macs["core"]
    assert cost["bytes"] == 4 * 32 * 128 * 6 * (16384 + 17408) * 2
    assert cost["ops"] / 197e12 > cost["bytes"] / 819e9, "operations bind the core"
    cost = eva.eva_summary_step_cost(layers, 1, p, 2)
    assert cost["ops"] == 6 * macs["summaries"]
    assert cost["bytes"] == 4 * 32 * 128 * 3 * 2 * 17408 * 2
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12, "bytes bind the summaries"
    # a row within one window: plain causal attention, no summaries
    short = eva.forward_macs_per_row(layers, 1024)
    assert short["summaries"] == 0
    assert short["core"] == 4 * (1024 * 1025 / 2) * 2 * 128 * 32
    assert eva.eva_summary_step_cost(layers, 1, 1024, 2) == {"ops": 0.0, "bytes": 0.0}
    # a table without the new kind reads as lm_flops.py reads it
    glm = RUN.load_module(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.reference.py"))
    assert eva.eva_core_step_cost(glm.LAYERS, 2, 8192, 2) == {"ops": 0.0, "bytes": 0.0}
    assert lm.TRAIN_FWD_MULT == eva.TRAIN_FWD_MULT


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-byte-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-byte", model="benchmark/configs/tiny-byte.json",
               reference="benchmark/configs/tiny-byte.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-byte.json", json.dumps(cfg))
    write("benchmark/configs/tiny-byte.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_byte_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    write("benchmark/traffic/tiny-byte.json", json.dumps(
        {"driver": "byte-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-byte", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-byte.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-byte", "config": "tiny-byte",
                               "traffic": "tiny-byte", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-byte")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-byte", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_byte_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=4_000_000_031)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff"}
    assert out["correct"] is True, checks
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    assert "moe" not in run_note
    assert run_note["tokens_per_s_per_chip"] == pytest.approx(
        32 * out["metrics"]["train_round_rate"]["value"])
    # uniform over 32 ids, three heads: the loss starts near ln 32
    assert run_note["round_losses"][0] == pytest.approx(np.log(32), abs=0.2)


def test_correct_is_false_when_the_round_returns_its_state_unchanged(tiny_tree, monkeypatch, capsys):
    from sparknet_tpu.parallel.trainer import ParallelTrainer
    real = ParallelTrainer.train_round

    def lazy_round(self, state, batches, rng, **kw):
        import jax
        _, loss = real(self, jax.tree.map(lambda x: x.copy(), state), batches, rng, **kw)
        return state, loss

    monkeypatch.setattr(ParallelTrainer, "train_round", lazy_round)
    out = _run_tiny(tiny_tree, seed=42, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["update_gap"]["ok"]
    assert checks["loss_gap"]["ok"]


def test_correct_is_false_when_the_summaries_are_masked_out(tiny_tree, monkeypatch, capsys):
    """Every query reads its own window alone: the summaries' columns are
    there and no query is granted them, so nothing reaches mu and phi."""
    from sparknet_tpu.ops import eva
    real = eva.WindowSummaryMask.__call__
    monkeypatch.setattr(eva.WindowSummaryMask, "__call__",
                        lambda self, q, kv: real(self, q, kv) & (kv < self.positions))
    out = _run_tiny(tiny_tree, seed=43, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    # what is left of the probe's momentum is the weight decay's part
    assert checks["probe_diff"]["value"] == pytest.approx(1.0, abs=0.03)
    assert not checks["probe_diff"]["ok"]


def test_correct_is_false_when_the_norms_offset_is_dropped(tiny_tree, monkeypatch, capsys):
    """Norms whose scale is w, not 1 + w: from the benchmark's zero w every
    normed stream is zero."""
    from sparknet_tpu import zoo
    real = zoo.evabyte
    monkeypatch.setitem(zoo.SEQUENCE_MODELS, "evabyte", lambda config, **kw: real(
        dict(config, norm_add_unit_offset=False), **kw))
    out = _run_tiny(tiny_tree, seed=44, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    assert not checks["update_gap"]["ok"] or not checks["momentum_gap"]["ok"]


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-byte")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=seed, seconds=0.0, trace=trace,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "byte-round.py"))
    return ctx, driver, driver.program(ctx)


def test_the_two_controls_fail_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's, and with the summary columns masked out:
    `probe_diff` must catch each."""
    ctx, _, prog = _program(tiny_tree, 45)
    _, rows = prog.stack_makers()
    reference = prog.reference_round(rows)
    compare = ctx.load("compare.py")
    failed = lambda got: [c["name"] for c in compare.first_round_checks(
        got, reference, TINY_LIMITS) if not c["ok"]]
    assert "probe_diff" in failed(prog.reference_round(rows, "fp8"))
    solver = dict(ctx.config["solver"])
    blind = ctx.reference.round_reference(
        prog.params0, rows, tau=prog.tau, solver=solver, layers=prog.layers,
        summaries=False)
    assert "probe_diff" in failed(blind)
    # no summary read: nothing but the weight decay reaches phi
    assert np.linalg.norm(blind["probe"][0]) < 0.25 * np.linalg.norm(reference["probe"][0])
    sound = compare.first_round_checks(reference, reference, TINY_LIMITS)
    assert all(c["ok"] and c["value"] == 0 for c in sound)


# -- the new readers, against the real program at a tiny size ----------------

def test_the_round_is_attributed_to_the_new_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the new
    layer type and its two sub-scopes, a window made of the report's own
    names (a CPU trace has no device plane) joins with nothing unmatched, and
    all 17 readers of the cell return numbers."""
    from sparknet_tpu.model import seq_layers as sl
    monkeypatch.setattr(sl, "ATTN_BLOCKS", (8, 8, 8))  # tiles a tiny row fills
    ctx, driver, prog = _program(tiny_tree, 46, trace=True)
    make_stack, _ = prog.stack_makers()
    prog.check_round(make_stack(0))

    sm = ctx.load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {})
    monkeypatch.setattr(sm, "_joined", {})
    report, _ = sm.report()
    assert report is prog.trainer.program_report()
    part = report["eva"]
    # off the chip no kernel runs; the tables are those a kernel would get:
    # 4 query blocks x 6 key blocks, a window its own block, and of the two
    # blocks of summaries (two windows' each) those that hold an earlier one
    assert part == {**part, "layers": 2, "core_forward_calls": 0,
                    "core_backward_calls": 0, "keys_per_query": 48,
                    "blocks_visited": 4 + (0 + 1 + 1 + 2), "blocks": 24}
    assert part["summary_instructions"] > 0 and part["summary_bytes"] > 0
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "EVAttention", "GatedMLP", "Eltwise", "InnerProduct",
            "SoftmaxWithLoss"} <= types_seen
    assert not {"GQAttention", "MLAttention", "MoE", "MTP", "KDAttention"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for piece in ("EVAttention/l0_attn)/summaries", "EVAttention/l1_attn)/core",
                  "solver_update", "tau_boundary"):
        assert piece in scopes, piece
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("EVAttention", "GatedMLP", "InnerProduct"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    device = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
              "round_outside_step_ms", "round_temp_bytes", "lm_head_loss_device_ms"]
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in NEW + device}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    assert values["eva_device_ms"] == pytest.approx(by_type("EVAttention"))
    assert 0 < values["lm_head_loss_device_ms"] < sum(parts)
    assert values["eva_core_blocks_visited"] == 8 and fake.notes["eva"] == part
    # utilisation and the shares by hand
    eva = ctx.load("eva_lm_flops.py")
    per_row = eva.train_flops_per_row(prog.layers, 32)
    assert values["eva_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    under = lambda s: sum(0.5 for op in own.values() if op["layer_type"] == "EVAttention"
                          and f"/{s}/" in "/" + op["scope"] + "/")
    assert 0 < under("core") < values["eva_device_ms"]
    assert 0 < under("summaries") < values["eva_device_ms"]
    for name, cost, ms in (
            ("eva_core_roofline", eva.eva_core_step_cost(prog.layers, 1, 32, 2), under("core")),
            ("eva_summary_roofline", eva.eva_summary_step_cost(prog.layers, 1, 32, 2),
             under("summaries"))):
        assert values[name] == pytest.approx(
            100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * ms))
    assert fake.notes["eva_summary_roofline_bound"] == "bytes"


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load)
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", ["eva_device_ms", "eva_core_roofline",
                                    "eva_summary_roofline", "eva_core_blocks_visited"])
def test_new_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has no such layer and no such part
    of its report (the parent commit's, or another sequence model's): 0 ms
    under the type, and the shares and the count return nothing and do not
    raise."""
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
    if metric != "eva_device_ms":
        assert got is None
