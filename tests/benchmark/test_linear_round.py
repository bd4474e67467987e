"""The linear-attention configuration's files (`ling3-flash-ep64-tau4`: Kimi
Delta Attention among latent attention with direct queries, head-wise gates,
experts chosen among the best groups) through the `token-round` traffic, on
the CPU at a tiny size: a throw-away cell added as new files is `correct`; it
is not when the round returns its state unchanged, when the decay is left
out, when a convolution loses a tap, or under the fp8 control;
`linear_lm_flops.py` gives hand-worked numbers; the round's ops are
attributed to the new scopes and the three new readers return numbers.
Counts and arithmetic only, never a device time.
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
CELL, NAME = "ling3-flash-train-round", "ling3-flash-ep64-tau4"
NEW = ["kda_device_ms", "kda_delta_roofline", "kda_lm_train_mfu"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "mla_device_ms",
          "mla_core_roofline", "moe_experts_device_ms", "moe_experts_roofline",
          "moe_route_device_ms", "moe_dropped_slots", "moe_load_max_over_min",
          "lm_head_loss_device_ms"]


# the token cells' shared helpers: a checkout's run.py as a module, a run's
# check notes, a made-up traced run
from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))

#: the tiny configuration: every mechanism of the published one (a leading
#: dense layer, delta-rule layers around one latent attention by the
#: published period, expert layers holding 2 of 16 experts in 4 groups of
#: which 2 are kept, top 2, one shared expert, an untied head over a sliced
#: vocabulary), at widths a test run can hold; 128 positions are two chunks
TINY = dict(
    hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=48, num_attention_heads=4, head_dim=16,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    num_experts=2, num_experts_per_tok=2, n_group=4, topk_group=2,
    num_hidden_layers=4, first_k_dense_replace=1, vocab_size=256,
    local_batch=2, seq_len=128, tau=2,
    share=dict(chips_sharing_a_layer=8, num_experts=16, experts_held=[4, 2],
               vocab_rows=[0, 256], first_layer=3))
#: the tiny configuration's limits, from CPU readings of this file's own runs
TINY_LIMITS = {"loss_gap": 1.0e-3, "update_gap": 0.03, "momentum_gap": 0.045,
               "probe_diff": 0.03, "routing_diff_share": 0.2}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "token-round",
                           "chips": 1}
    assert "8 of 512 experts held" in cells[CELL]["why"] and len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    for name in NEW:
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "train_round_rate"
        assert os.path.exists(os.path.join(BENCH, "readers", name + ".py"))
    for name in SHARED + ["train_round_rate"]:
        assert CELL in by[name]["workloads"], name
    for name in ("train_mfu", "lm_train_mfu", "mtp_device_ms", "lrn_roofline",
                 "avg_collective_ms", "shortconv_device_ms", "gqa_core_roofline",
                 "hybrid_lm_train_mfu"):
        assert CELL not in by[name]["workloads"], name
    reported = [m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]]
    assert len(reported) == 22 == len(NEW) + len(SHARED)
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1
    assert by["kda_delta_roofline"]["unit"] == "%" == by["kda_lm_train_mfu"]["unit"]
    assert by["kda_delta_roofline"]["layer"] == "kernels"


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog's row under its own name and value, but the
    `reduced` ones; the share block and the held counts agree; the counts of
    ISSUE 33's table, re-reckoned."""
    row = [json.loads(l) for l in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Ling-3.0-flash-VL"' in l] if os.path.exists(
        "/opt/skills/guides/model-configs/architectures.jsonl") else []
    published = row[0]["config"] if row else dict(
        hidden_size=2560, intermediate_size=6144, moe_intermediate_size=768,
        num_attention_heads=32, head_dim=128, q_lora_rank=None, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_experts_per_tok=8, n_group=8, topk_group=4, routed_scaling_factor=2.5,
        layer_group_size=6, short_conv_kernel_size=4, kda_lower_bound=-5,
        kda_safe_gate=True, no_kda_lora=True, rope_theta=6000000,
        rms_norm_eps=1e-06, moe_shared_expert_intermediate_size=768)
    assert CONFIG["reduced"] == ["num_hidden_layers", "first_k_dense_replace",
                                 "num_experts", "vocab_size"]
    same = {k: v for k, v in published.items() if k not in CONFIG["reduced"]}
    assert {k: CONFIG[k] for k in same} == same
    if row:
        assert CONFIG["source"] == row[0]["source_url"]
        assert CONFIG["published"] == {k: published[k] for k in CONFIG["reduced"]}
    assert (CONFIG["hidden_size"], CONFIG["num_attention_heads"], CONFIG["head_dim"],
            CONFIG["kv_lora_rank"], CONFIG["qk_nope_head_dim"], CONFIG["qk_rope_head_dim"],
            CONFIG["v_head_dim"], CONFIG["moe_intermediate_size"],
            CONFIG["intermediate_size"], CONFIG["num_experts_per_tok"], CONFIG["n_group"],
            CONFIG["topk_group"], CONFIG["short_conv_kernel_size"],
            CONFIG["kda_lower_bound"]) == (2560, 32, 128, 512, 128, 64, 128, 768, 6144,
                                           8, 8, 4, 4, -5)
    assert (CONFIG["num_hidden_layers"], CONFIG["first_k_dense_replace"],
            CONFIG["num_experts"], CONFIG["vocab_size"]) == (7, 1, 8, 19648)
    assert CONFIG["published"] == dict(num_hidden_layers=42, first_k_dense_replace=2,
                                       num_experts=512, vocab_size=157184)
    share = CONFIG["share"]
    assert share["num_experts"] == 512 and share["experts_held"] == [0, 8]
    assert share["vocab_rows"] == [0, 157184 // 8] and share["chips_sharing_a_layer"] == 64
    assert share["first_layer"] == 1 and share["capacity_factor"] == 2.0
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"]) == (4, 2, 8192)
    assert CONFIG["model_type"] == "ling3_flash"
    for key in ("deployment", "expert_load", "changed_from_source", "assumed"):
        assert CONFIG[key], key
    for key in ("layer_kinds", "kda_gate", "qk_norm", "output_gates", "group_score",
                "tie_word_embeddings", "weights_seed", "initialisation"):
        assert key in CONFIG["assumed"], key
    assert "vision tower" in CONFIG["changed_from_source"]["left_out"]
    # the swiglu clamp is off at every layer held (published layers 1 to 7)
    assert not any(CONFIG["expert_swiglu_limit_list"][1:8])
    assert not any(CONFIG["share_expert_swiglu_limit_list"][1:8])
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    assert count("l0_kda") == 52_646_048 and count("l4_attn") == 31_965_696
    assert count("l0_mlp") == 47_185_920 and count("embed") == 50_298_880 == count("lm_head")
    assert count("l1_moe") == 9 * 5_898_240 + 2560 * 512 + 512
    kinds = [k for _, k, _ in ref.LAYERS if k in ("kda", "mla")]
    assert kinds == ["kda"] * 4 + ["mla"] + ["kda"] * 2  # published layers 1 to 7
    assert ref.n_params() == 822_036_416 == CONFIG["n_params"], "ISSUE 33's table, re-reckoned"
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"
    assert "lax.scan(step" in src and "chunk" not in src.split('"""')[2], \
        "the reference runs the recurrence a position at a time"


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.ling3_flash(CONFIG, rows=2, positions=8192))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    from sparknet_tpu.model.seq_layers import moe_capacity
    assert moe_capacity(net.spec.layer_by_name("l1_moe").moe, 2 * 8192) == 4096


# -- linear_lm_flops by hand -------------------------------------------------

def test_linear_lm_flops_by_hand():
    linear, lm = load("linear_lm_flops.py"), load("lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 8192
    kda = 2560 * 4 * 4096 + 3 * 4096 * 4 + 2 * 2560 * 32 + 4096 * 2560
    mla = 2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 32 * 128 * 2560 + 2560 * 32
    expert = 3 * 2560 * 768
    even = linear.even_slots_per_row(layers, p)
    assert even == {f"l{i}_moe": p * 8 * 8 / 512 for i in range(1, 7)}
    macs = linear.forward_macs_per_row(layers, p, even)
    dense = p * (6 * kda + mla + 3 * 2560 * 6144 + 6 * (2560 * 512 + expert)
                 + 2560 * 19648)
    assert macs["dense"] == pytest.approx(dense)
    assert macs["experts"] == pytest.approx(6 * p * 8 * 8 / 512 * expert)
    assert macs["core"] == pytest.approx((p * (p + 1) / 2) * 32 * (192 + 128))
    assert macs["delta"] == 6 * p * 32 * 3 * 128 * 128
    base = lm.forward_macs_per_row(layers, p, even)
    assert macs["dense"] - base["dense"] == pytest.approx(
        p * (6 * kda + 2560 * 32 * 193))  # the new kind, the direct queries, the gate
    assert base["core"] == macs["core"] and base["experts"] == macs["experts"]
    per_row = linear.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values()))
    # ISSUE 33 reckoned 1.07 GFLOP a token forward in matmuls before the rule
    assert 2 * (sum(macs.values()) - macs["delta"]) / p == pytest.approx(1.07e9, rel=0.03)
    cost = linear.kda_delta_step_cost(layers, 2, p, 2)
    assert cost["ops"] == 6 * 2 * macs["delta"]
    forward = p * 32 * (128 * (4 * 2 + 4) + 4)
    states = p / 64 * 32 * 128 * 128 * 4 * 2
    assert cost["bytes"] == 6 * 2 * (3 * forward + states)
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12, "bytes bind, not operations"
    fewer = linear.train_flops_per_row(layers, p, {k: v / 2 for k, v in even.items()})
    assert per_row - fewer == pytest.approx(6 * macs["experts"] / 2)
    # a table without the new kind reads as lm_flops.py reads it
    glm = RUN.load_module(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.reference.py"))
    assert linear.train_flops_per_row(glm.LAYERS, p) == lm.train_flops_per_row(glm.LAYERS, p)
    assert linear.kda_delta_step_cost(glm.LAYERS, 2, p, 2) == {"ops": 0.0, "bytes": 0.0}


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-linear-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-linear", model="benchmark/configs/tiny-linear.json",
               reference="benchmark/configs/tiny-linear.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    # the tiny model's four layers are published layers 3 to 6
    cfg["expert_swiglu_limit_list"] = cfg["share_expert_swiglu_limit_list"] = [0] * 8
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-linear.json", json.dumps(cfg))
    write("benchmark/configs/tiny-linear.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_linear_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    write("benchmark/traffic/tiny-linear.json", json.dumps(
        {"driver": "token-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-linear", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-linear.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-linear", "config": "tiny-linear",
                               "traffic": "tiny-linear", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-linear")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-linear", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_linear_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=3_300_000_031)
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
    # 256 tokens a step, top 2 of 16, 2 held: 64 slots a step if spread evenly
    assert 10 < moe["by_layer"]["l1_moe_counters"]["slots_landed_per_step"] < 200
    assert run_note["tokens_per_s_per_chip"] == pytest.approx(
        128 * out["metrics"]["train_round_rate"]["value"])


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


def test_correct_is_false_when_the_decay_is_left_out(tiny_tree, monkeypatch, capsys):
    """The delta rule with its state never decayed (log-decay 0 everywhere):
    a plain delta rule, another model."""
    import jax.numpy as jnp
    from sparknet_tpu.ops import delta_rule
    real = delta_rule.gated_delta_rule
    monkeypatch.setattr(delta_rule, "gated_delta_rule",
                        lambda q, k, v, g, beta, **kw: real(q, k, v, jnp.zeros_like(g),
                                                            beta, **kw))
    out = _run_tiny(tiny_tree, seed=33, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    assert not checks["update_gap"]["ok"] or not checks["momentum_gap"]["ok"]


def test_correct_is_false_when_a_convolution_drops_a_tap(tiny_tree, monkeypatch, capsys):
    """The short convolutions with their oldest tap left out (position t no
    longer sees t - 3)."""
    from sparknet_tpu.model import seq_layers
    real = seq_layers.causal_taps
    monkeypatch.setattr(seq_layers, "causal_taps",
                        lambda s, w: real(s, w.at[..., 0].set(0.0)))
    out = _run_tiny(tiny_tree, seed=34, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    assert not checks["update_gap"]["ok"] or not checks["momentum_gap"]["ok"]


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-linear")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=seed, seconds=0.0, trace=trace,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "token-round.py"))
    return ctx, driver, driver.Program(ctx)


def test_the_fp8_control_fails_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's: `probe_diff` must catch it."""
    ctx, _, prog = _program(tiny_tree, 35)
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
    layer type and its six sub-scopes, a window made of the report's own
    names (a CPU trace has no device plane) joins with nothing unmatched, and
    all 22 readers of the cell return numbers."""
    ctx, driver, prog = _program(tiny_tree, 36, trace=True)
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
    delta = report["delta_rule"]
    assert delta["loops"] > 0 and delta["trips"] >= delta["loops"]
    assert delta["kept_bytes"] == 3 * 2 * 128 * 64 * 2  # three layers' results, bf16
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "KDAttention", "MLAttention", "GatedMLP", "MoE",
            "Eltwise", "InnerProduct", "SoftmaxWithLoss"} <= types_seen
    assert not {"GQAttention", "ShortConv", "MTP"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for part in ("/in_proj", "/conv", "/gates", "/delta", "/out_gate", "/out_proj",
                 "MLAttention/l2_attn)/core", "/router", "/dispatch", "/experts",
                 "/combine", "/shared", "solver_update", "tau_boundary"):
        assert part in scopes, part
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("KDAttention", "MLAttention", "MoE", "GatedMLP", "InnerProduct"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {"moe": moe})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    device = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
              "round_outside_step_ms", "round_temp_bytes", "mla_device_ms",
              "mla_core_roofline", "moe_experts_device_ms", "moe_experts_roofline",
              "moe_route_device_ms", "moe_dropped_slots", "moe_load_max_over_min",
              "lm_head_loss_device_ms"]
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in NEW + device}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    for k in ("kda_device_ms", "mla_device_ms", "moe_experts_device_ms",
              "moe_route_device_ms", "lm_head_loss_device_ms"):
        assert 0 < values[k] < sum(parts), k
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    assert values["kda_device_ms"] == pytest.approx(by_type("KDAttention"))
    assert values["mla_device_ms"] == pytest.approx(by_type("MLAttention"))
    assert values["kda_device_ms"] > values["mla_device_ms"]  # three layers to one
    # utilisation and the share by hand
    linear = ctx.load("linear_lm_flops.py")
    landed = {b[:-len("_counters")]: v["slots_landed_per_step"] / 2
              for b, v in moe["by_layer"].items()}
    per_row = linear.train_flops_per_row(prog.layers, 128, landed)
    assert values["kda_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    delta_ms = sum(0.5 for op in own.values() if op["layer_type"] == "KDAttention"
                   and "/delta/" in "/" + op["scope"] + "/")
    assert 0 < delta_ms < values["kda_device_ms"]
    cost = linear.kda_delta_step_cost(prog.layers, 2, 128, 2)
    assert values["kda_delta_roofline"] == pytest.approx(
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * delta_ms))
    assert fake.notes["kda_delta_roofline_bound"] == "bytes"


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load)
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", ["kda_device_ms", "kda_delta_roofline"])
def test_new_scope_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has no such layer (the parent
    commit's, or another sequence model's): 0 ms under the type, and the
    share returns nothing and does not raise."""
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
