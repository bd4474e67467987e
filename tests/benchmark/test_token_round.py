"""The token-round traffic and the sequence-model configuration's files, on the
CPU at a tiny size: a throw-away `token-round` cell added as new files is
`correct`; it is not when the round returns its state unchanged, when the MTP
loss is left out, or under the fp8 control; `lm_flops.py` gives hand-worked
numbers; the round's ops are attributed to the new scopes and the ten new
readers return numbers. Counts and arithmetic only, never a device time.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import shutil
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "glm47-flash-train-round"
NEW = ["lm_train_mfu", "mla_device_ms", "mla_core_roofline",
       "moe_experts_device_ms", "moe_experts_roofline", "moe_route_device_ms",
       "mtp_device_ms", "lm_head_loss_device_ms", "moe_dropped_slots",
       "moe_load_max_over_min"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes"]


def _run_py(root=ROOT):
    spec = importlib.util.spec_from_file_location(
        "bench_run_" + re.sub(r"\W", "_", root), os.path.join(root, "benchmark", "run.py"))
    if spec.name in sys.modules:
        return sys.modules[spec.name]
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.json"))

#: the tiny configuration: every mechanism of the published one (a leading
#: dense layer, expert layers holding 2 of 8 experts, top 2, a shared expert,
#: the MTP module, a sliced vocabulary), at widths a test run can hold
TINY = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=2, num_experts_per_tok=2,
    num_hidden_layers=3, vocab_size=256, local_batch=2, seq_len=32, tau=2,
    share=dict(chips_sharing_a_layer=4, n_routed_experts=8, experts_held=[2, 2],
               vocab_rows=[0, 256], mtp_loss_weight=0.3))
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: (seeds 21-26): the program's probe_diff read 0.011-0.015 and the fp8
#: control's 0.13-0.17; loss_gap 1e-4-4e-4 against 3e-3-8e-3. Leaving the MTP
#: loss out moves the loss by 0.3 x ln(256) = 1.66.
TINY_LIMITS = {"loss_gap": 1.5e-3, "update_gap": 0.05, "momentum_gap": 0.05,
               "probe_diff": 0.05, "routing_diff_share": 0.2}


# -- the entries -------------------------------------------------------------

def test_the_cell_and_its_ten_metrics_are_appended():
    cell = BENCHMARK["workloads"][-1]
    assert cell == {**cell, "name": CELL, "config": "glm47-flash-ep8-tau4",
                    "traffic": "token-round", "chips": 1}
    assert "more than its share" in cell["why"] and len(cell["why"]) <= 200
    assert [m["name"] for m in BENCHMARK["per_layer"]][-10:] == NEW
    for m in BENCHMARK["per_layer"][-10:]:
        assert m["workloads"] == [CELL] and m["moves"] == "train_round_rate"
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    for name in SHARED + ["train_round_rate"]:
        assert by[name]["workloads"][-1] == CELL
    for name in ("train_mfu", "lrn_roofline", "conv_fc_device_ms", "avg_collective_ms"):
        assert CELL not in by[name]["workloads"]
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1


def test_the_configuration_file_holds_the_published_keys():
    """Every number of the public config.json under its own key, but the
    three `reduced` ones; the share block and the held counts agree."""
    published = dict(
        hidden_size=2048, intermediate_size=10240, moe_intermediate_size=1536,
        num_attention_heads=20, num_key_value_heads=20, q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, num_experts_per_tok=4, n_shared_experts=1,
        routed_scaling_factor=1.8, first_k_dense_replace=1, n_group=1,
        topk_group=1, num_nextn_predict_layers=1, rms_norm_eps=1e-5,
        rope_theta=1000000, max_position_embeddings=202752)
    assert {k: CONFIG[k] for k in published} == published
    assert CONFIG["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (5, 8, 19360)
    assert CONFIG["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64,
                                   "vocab_size": 154880}
    share = CONFIG["share"]
    assert share["n_routed_experts"] == 64 and share["experts_held"] == [0, 8]
    assert share["vocab_rows"] == [0, 154880 // 8] and share["chips_sharing_a_layer"] == 8
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"]) == (4, 2, 8192)
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    assert ref.n_params() == 706_518_848, "ISSUE 27's table, re-reckoned"


# -- lm_flops by hand --------------------------------------------------------

def test_lm_flops_by_hand():
    lm = load("lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 8192
    mla = (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448 + 5120 * 2048)
    assert mla == 21_757_952  # the attention's matrices (its two norms apart)
    expert = 3 * 2048 * 1536
    even = lm.even_slots_per_row(layers, p)
    assert even == {name: p * 4 * 8 / 64 for name in
                    ("l1_moe", "l2_moe", "l3_moe", "l4_moe", "mtp")}
    macs = lm.forward_macs_per_row(layers, p, even)
    head = 2048 * 19360
    dense = p * (6 * mla + 3 * 2048 * 10240 + 5 * (2048 * 64 + expert)
                 + 2 * head + 2 * 2048 * 2048)
    assert macs["dense"] == pytest.approx(dense)
    assert macs["experts"] == pytest.approx(5 * (p / 2) * expert)
    assert macs["core"] == pytest.approx(6 * (p * (p + 1) / 2) * 20 * 512)
    per_row = lm.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * (macs["dense"] + macs["experts"] + macs["core"]))
    assert 28e12 < per_row < 32e12  # ISSUE 27: a step of two rows is ~60 TFLOP
    core = lm.core_step_cost(layers, 2, p, 2)
    assert core["ops"] == pytest.approx(6 * 2 * macs["core"])
    assert core["bytes"] == 6 * 2 * p * 20 * 12 * 256 * 2
    ex = lm.experts_cost(layers, slots=1000.0, held_layers=5, itemsize=2)
    assert ex["ops"] == pytest.approx(6 * 1000 * expert)
    assert ex["bytes"] == pytest.approx(2 * (4 * 5 * 8 * expert
                                             + 3 * 1000 * (2 * 2048 + 3 * 1536)))
    # fewer slots landed, fewer FLOPs: the counter's reading goes in
    fewer = lm.train_flops_per_row(layers, p, {k: v / 2 for k, v in even.items()})
    assert per_row - fewer == pytest.approx(6 * macs["experts"] / 2)


# -- the ids -----------------------------------------------------------------

def test_token_rows_repeat_and_are_uniform_over_the_held_rows():
    import jax.numpy as jnp
    driver, seeded = load(os.path.join("drivers", "token-round.py")), load("seeded.py")
    kw = dict(tau=3, rows=2, positions=64, vocab=19360)
    seed = 3_000_000_019  # the driver's seeds pass 2**31
    a = np.asarray(driver.token_rows(seeded, seed, 2, 0, 3, **kw))
    assert a.shape == (3, 2, 64) and a.dtype == np.int32
    assert np.array_equal(a, np.asarray(driver.token_rows(seeded, seed, 2, 0, 3, **kw)))
    one = np.asarray(driver.token_rows(seeded, seed, jnp.uint32(2), jnp.uint32(1), 1, **kw))
    assert np.array_equal(one[0], a[1]), "any step alone equals that part of the stack"
    assert not np.array_equal(a, np.asarray(driver.token_rows(seeded, seed + 1, 2, 0, 3, **kw)))
    assert not np.array_equal(a, np.asarray(driver.token_rows(seeded, seed, 3, 0, 3, **kw)))
    big = np.asarray(driver.token_rows(seeded, seed, 0, 0, 3, **dict(kw, positions=8192)))
    assert big.min() >= 0 and big.max() < 19360
    assert abs(big.mean() / 19360 - 0.5) < 0.01 and len(np.unique(big)) > 17000


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-token-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-lm", model="benchmark/configs/tiny-lm.json",
               reference="benchmark/configs/tiny-lm.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-lm.json", json.dumps(cfg))
    write("benchmark/configs/tiny-lm.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_lm_ref_base', os.path.join("
        "os.path.dirname(os.path.abspath(__file__)), 'glm47-flash-ep8-tau4.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    write("benchmark/traffic/tiny-token.json", json.dumps(
        {"driver": "token-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-lm", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-lm.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-token", "config": "tiny-lm",
                               "traffic": "tiny-token", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-token")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-token", seed, seconds, trace,
                                  time.perf_counter())


def _checks(capsys):
    notes = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    return ({n["name"]: n for n in notes if n.get("note") == "check"},
            next(n for n in notes if n.get("note") == "run"))


def test_tiny_token_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=2_500_000_021)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff",
                           "moe_dropped_slots", "routing_diff_share"}
    assert out["correct"] is True, checks
    assert checks["moe_dropped_slots"]["value"] == 0 == checks["moe_dropped_slots"]["limit"]
    assert set(checks["routing_diff_share"]["by_layer"]) == {"l1_moe", "l2_moe", "mtp"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    moe = run_note["moe"]
    assert moe["slots_dropped"] == 0 and moe["load_max_over_min"] >= 1
    assert set(moe["by_layer"]) == {"l1_moe_counters", "l2_moe_counters", "mtp_counters"}
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
    out = _run_tiny(tiny_tree, seed=22, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["update_gap"]["ok"]
    assert checks["loss_gap"]["ok"]


def test_correct_is_false_when_the_mtp_loss_is_left_out(tiny_tree, monkeypatch, capsys):
    from sparknet_tpu import zoo
    real = zoo.glm4_moe_lite
    monkeypatch.setitem(zoo.SEQUENCE_MODELS, "glm4_moe_lite", lambda config, **kw: real(
        dict(config, share=dict(config["share"], mtp_loss_weight=0.0)), **kw))
    out = _run_tiny(tiny_tree, seed=23, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["loss_gap"]["ok"]


def test_the_fp8_control_fails_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's: at least one limit must catch it."""
    run = _run_py(tiny_tree)
    root = tiny_tree
    bench, cell, entry = run.resolve(root, "tiny-token")
    config = run.load_json(os.path.join(root, entry["file"]))
    ctx = run.Ctx(root=root, bench=os.path.join(root, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=24, seconds=0.0, trace=False,
                  reference=run.load_module(os.path.join(root, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "token-round.py"))
    prog = driver.Program(ctx)
    _, rows = prog.stack_makers()
    reference = prog.reference_round(rows)
    assert ctx.reference.CONTROL_PRECISION == "fp8"
    control = prog.reference_round(rows, ctx.reference.CONTROL_PRECISION)
    compare = ctx.load("compare.py")
    limits = {k: v for k, v in TINY_LIMITS.items() if k != "routing_diff_share"}
    failed = [c["name"] for c in compare.first_round_checks(control, reference, limits)
              if not c["ok"]]
    assert "probe_diff" in failed, failed
    sound = compare.first_round_checks(reference, reference, limits)
    assert all(c["ok"] and c["value"] == 0 for c in sound)


# -- the new readers, against the real program at a tiny size ----------------

def _fake_run(ctx, device_ops, notes):
    return types.SimpleNamespace(
        ctx=ctx, trace={"rounds": 2, "window_s": 4.0, "device_ops": list(device_ops)},
        notes=notes, samples_per_round_per_chip=4.0, device_kind="TPU v5 lite")


def test_the_round_is_attributed_to_the_new_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the new
    layer types and sub-scopes, a window made of the report's own names (a
    CPU trace has no device plane) joins with nothing unmatched, and all
    ten new readers and the shared ones return numbers."""
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-token")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=25, seconds=0.0, trace=True,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "token-round.py"))
    prog = driver.Program(ctx)
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
    ops = report["ops"]
    own = {n: op for n, op in ops.items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "MLAttention", "GatedMLP", "MoE", "MTP", "Eltwise",
            "InnerProduct", "SoftmaxWithLoss"} <= types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for part in ("/router", "/dispatch", "/experts", "/combine", "/shared", "/core",
                 "MTP/mtp)/attention", "MTP/mtp)/moe/experts", "solver_update",
                 "tau_boundary"):
        assert part in scopes, part
    # the recomputed forward counts as backward (its path runs through
    # `transpose(`), so both passes of every block are there
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("MLAttention", "MoE", "MTP", "GatedMLP"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {"moe": moe})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in NEW + ["step_forward_ms", "step_backward_ms",
                              "step_optimizer_ms", "round_outside_step_ms",
                              "round_temp_bytes"]}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert all(p > 0 for p in parts)
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    for k in ("mla_device_ms", "moe_experts_device_ms", "moe_route_device_ms",
              "mtp_device_ms", "lm_head_loss_device_ms"):
        assert 0 < values[k] < sum(parts), k
    assert values["moe_dropped_slots"] == 0
    assert values["moe_load_max_over_min"] == pytest.approx(moe["load_max_over_min"])
    # utilisation by hand: rows/s x FLOPs a row over the peak
    lm = ctx.load("lm_flops.py")
    landed = {b[:-len("_counters")]: v["slots_landed_per_step"] / 2
              for b, v in moe["by_layer"].items()}
    per_row = lm.train_flops_per_row(prog.layers, 32, landed)
    assert values["lm_train_mfu"] == pytest.approx(100 * (2 * 4 / 4.0) * per_row / 197e12)
    assert values["mla_core_roofline"] > 0 and values["moe_experts_roofline"] > 0


@pytest.mark.parametrize("metric", NEW[:8])
def test_new_device_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load)
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None
