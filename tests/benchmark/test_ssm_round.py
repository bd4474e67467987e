"""The state-space hybrid's files (`nemotron3-super-tp4-ep64-tau4`: Mamba-2
mixers, grouped-query attention without a rotary turn, LatentMoE with relu^2
experts, an MTP module of the same layer types, every operator a share)
through the `token-round` traffic, on the CPU at a tiny size: the cell, its
configuration and its metrics are in `BENCHMARK.json` BY NAME; a throw-away
cell added as new files is `correct`; it is not when the round returns its
state unchanged, when the scan drops its state between chunks, or under
either of the reference's two controls; `ssm_lm_flops.py` gives hand-worked
numbers; the round's ops are attributed to the new scopes and the new readers
return numbers. Counts and arithmetic only, never a device time.
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
CELL, NAME = "nemotron3-super-train-round", "nemotron3-super-tp4-ep64-tau4"
NEW = ["mamba_device_ms", "mamba_ssd_roofline", "latent_moe_experts_roofline",
       "latent_proj_device_ms", "ssm_lm_train_mfu", "gqa_share_device_ms",
       "gqa_share_core_roofline"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "moe_experts_device_ms",
          "moe_route_device_ms", "moe_dropped_slots", "moe_load_max_over_min",
          "lm_head_loss_device_ms"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


# the token cells' shared helpers: a checkout's run.py as a module, a run's
# check notes, a made-up traced run
from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))

#: the tiny configuration: every mechanism of the published one (Mamba-2
#: mixers holding 4 of 8 heads in 2 of 4 groups, chunks of 16 so that 128
#: positions are eight; attention holding 2 of 4 query heads and 1 of 2
#: key/value heads; expert layers holding 2 of 16 experts in a latent of 32,
#: the 6 best a token, 24 of the shared expert's 96 columns; an MTP module *E;
#: a sliced vocabulary), at widths a test run can hold
TINY = dict(
    hidden_size=64, expand=2, mamba_num_heads=4, mamba_head_dim=16, n_groups=2,
    ssm_state_size=16, chunk_size=16, num_attention_heads=2,
    num_key_value_heads=1, head_dim=16, n_routed_experts=2,
    num_experts_per_tok=6, moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, num_hidden_layers=5,
    hybrid_override_pattern="MEM*E", vocab_size=256, local_batch=2,
    seq_len=128, tau=2,
    share=dict(chips_sharing_a_layer=8, tensor_parallel=2, n_routed_experts=16,
               mamba_num_heads=8, n_groups=4, num_attention_heads=4,
               num_key_value_heads=2, experts_held=[4, 2], mamba_heads_held=[4, 4],
               mamba_groups_held=[2, 2], attention_heads_held=[2, 2],
               kv_heads_held=[1, 1], shared_columns=[24, 24],
               vocab_rows=[0, 256], first_layer=3, mtp_loss_weight=0.1))
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: (bfloat16 program against the float32 reference, weights of spread 0.16)
TINY_LIMITS = {"loss_gap": 2.0e-2, "update_gap": 0.12, "momentum_gap": 0.06,
               "probe_diff": 0.15, "routing_diff_share": 0.3}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark_by_name():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "token-round",
                           "chips": 1}
    assert "22 of 512" in cells[CELL]["why"] and len(cells[CELL]["why"]) <= 200
    entry = {c["name"]: c for c in BENCHMARK["configs"]}[NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json" and len(entry["why"]) <= 200
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == CONFIG["reduced"]
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    for name in NEW:
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "train_round_rate"
        assert by[name]["source"] == "device_trace"
        assert os.path.exists(os.path.join(BENCH, "readers", name + ".py"))
    for name in SHARED + ["train_round_rate"]:
        assert CELL in by[name]["workloads"], name
    for name in ("moe_experts_roofline", "lm_train_mfu", "hybrid_lm_train_mfu",
                 "kda_lm_train_mfu", "eva_lm_train_mfu", "kda_device_ms",
                 "kda_delta_roofline", "mla_device_ms", "mla_core_roofline",
                 "eva_device_ms", "mtp_device_ms", "gqa_device_ms",
                 "gqa_core_roofline", "setup_import_s", "setup_cache_misses",
                 "train_mfu", "lrn_roofline"):
        assert CELL not in by[name]["workloads"], name
    reported = {m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
    assert reported >= set(NEW) | set(SHARED)
    for name in ("mamba_ssd_roofline", "latent_moe_experts_roofline",
                 "gqa_share_core_roofline"):
        assert (by[name]["unit"], by[name]["layer"], by[name]["better"]) == (
            "%", "kernels", "higher")
    assert (by["ssm_lm_train_mfu"]["unit"], by["ssm_lm_train_mfu"]["layer"]) == (
        "%", "model / solver")
    assert os.path.exists(os.path.join(BENCH, "traffic", "token-round.json"))


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog's row under its own name and value, but the
    eight `reduced` ones; `published` holds those eight as published; the share
    block and the held counts agree; no width differs from the row."""
    row = [json.loads(l) for l in open(CATALOG)
           if '"NVIDIA-Nemotron-3-Super-120B-A12B-BF16"' in l] \
        if os.path.exists(CATALOG) else []
    reduced = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
               "mamba_num_heads", "n_groups", "num_attention_heads",
               "num_key_value_heads", "vocab_size"]
    # the shared expert's width is a width: the file keeps it whole and the
    # share block says which of its columns are held
    assert "moe_shared_expert_intermediate_size" not in reduced
    assert CONFIG["reduced"] == reduced
    if row:
        published = row[0]["config"]
        same = {k: v for k, v in published.items() if k not in reduced}
        assert {k: CONFIG[k] for k in same} == same
        assert CONFIG["source"] == row[0]["source_url"]
        assert CONFIG["published"] == {k: published[k] for k in reduced}
        # the layers held are a stretch of the published pattern
        assert published["hybrid_override_pattern"][27:38] == CONFIG["hybrid_override_pattern"]
    widths = dict(hidden_size=4096, mamba_head_dim=64, ssm_state_size=128,
                  conv_kernel=4, chunk_size=128, head_dim=128, expand=2,
                  num_experts_per_tok=22, routed_scaling_factor=5,
                  moe_latent_size=1024, moe_intermediate_size=2688,
                  moe_shared_expert_intermediate_size=5376,
                  intermediate_size=2688, mlp_hidden_act="relu2",
                  mtp_hybrid_override_pattern="*E", model_type="nemotron_h")
    assert {k: CONFIG[k] for k in widths} == widths
    assert {k: CONFIG[k] for k in reduced} == dict(
        num_hidden_layers=11, hybrid_override_pattern="MEMEMEMEM*E",
        n_routed_experts=8, mamba_num_heads=32, n_groups=2, num_attention_heads=8,
        num_key_value_heads=1, vocab_size=16384)
    assert CONFIG["published"] == dict(
        num_hidden_layers=88, n_routed_experts=512, mamba_num_heads=128, n_groups=8,
        num_attention_heads=32, num_key_value_heads=2, vocab_size=131072,
        hybrid_override_pattern=CONFIG["published"]["hybrid_override_pattern"])
    assert len(CONFIG["published"]["hybrid_override_pattern"]) == 88
    share = CONFIG["share"]
    assert {k: share[k] for k in (
        "chips_sharing_a_layer", "tensor_parallel", "experts_held",
        "mamba_heads_held", "mamba_groups_held", "attention_heads_held",
        "kv_heads_held", "shared_columns", "vocab_rows", "first_layer",
        "mtp_loss_weight")} == dict(
        chips_sharing_a_layer=64, tensor_parallel=4, experts_held=[0, 8],
        mamba_heads_held=[0, 32], mamba_groups_held=[0, 2],
        attention_heads_held=[0, 8], kv_heads_held=[0, 1],
        shared_columns=[0, 1344], vocab_rows=[0, 16384], first_layer=27,
        mtp_loss_weight=0.1)
    # what the held counts are a share of: the published counts, and a
    # quarter (the experts a sixty-fourth, the vocabulary an eighth) of each
    for key in reduced[2:-1]:
        assert share[key] == CONFIG["published"][key], key
    assert (share["mamba_num_heads"] // 4, share["n_groups"] // 4,
            share["num_attention_heads"] // 4, share["n_routed_experts"] // 64,
            CONFIG["moe_shared_expert_intermediate_size"] // 4, 131072 // 8) == (
        32, 2, 8, 8, 1344, 16384)
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"],
            CONFIG["precision"]) == (4, 2, 8192, "bfloat16")
    for key in ("deployment", "expert_load", "changed_from_source", "plain_reference"):
        assert CONFIG[key], key
    for key in ("no_rotary", "moe_order", "mtp", "initialisation", "router_bias",
                "weights_seed", "capacity_factor"):
        assert key in CONFIG["assumed"], key
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    assert count("l0_mamba") == 4096 * 4640 + 2048 * 4096 + 2560 * 5 + 3 * 32 + 2048 \
        == 27_408_992
    assert count("l9_attn") == 4096 * (1024 + 128 + 128) + 1024 * 4096 == 9_437_184
    assert count("l1_moe") == (4096 * 512 + 512 + 2 * 4096 * 1024
                               + 8 * 2 * 1024 * 2688 + 2 * 4096 * 1344) == 65_536_512
    assert count("embed") == 16384 * 4096 == count("lm_head")
    assert count("mtp_eh_proj") == 8192 * 4096
    kinds = [k for _, k, _ in ref.LAYERS if k in ("mamba2", "gqa", "latent_moe")]
    assert kinds == ["mamba2", "latent_moe"] * 4 + ["mamba2", "gqa", "latent_moe",
                                                   "gqa", "latent_moe"]
    assert ref.n_params() == 716_980_192 == CONFIG["n_params"], "ISSUE 42's count, re-reckoned"
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"
    assert "lax.scan(step" in src and 'default_matmul_precision("highest")' in src
    assert ref.PROBE_LEAF == ("l0_mamba", "in_proj") and ref.CONTROL_PRECISION == "fp8"
    assert set(ref.LIMITS) == {"probe_diff", "momentum_gap", "update_gap", "loss_gap",
                               "routing_diff_share"}
    assert all(0 < v < 1 for v in ref.LIMITS.values()), ref.LIMITS


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    from sparknet_tpu.model.seq_layers import moe_capacity
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.nemotron_h(CONFIG, rows=2, positions=8192))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    # the buffer: at least twice the even share of 5,632 rows, whole tiles
    rows = moe_capacity(net.spec.layer_by_name("l1_moe").moe, 2 * 8192)
    assert rows % 512 == 0 and rows >= 2 * 5632
    assert rows == -(-int(np.ceil(CONFIG["share"]["capacity_factor"] * 5632)) // 512) * 512


# -- ssm_lm_flops by hand ----------------------------------------------------

def test_ssm_lm_flops_by_hand():
    ssm, hybrid = load("ssm_lm_flops.py"), load("hybrid_lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 8192
    mamba = 4096 * (2048 + 2560 + 32) + 2560 * 4 + 2048 * 4096
    attn = 4096 * (1024 + 2 * 128) + 1024 * 4096
    latent = 4096 * (512 + 2 * 1024 + 2 * 1344)
    expert = 2 * 1024 * 2688
    even = ssm.even_slots_per_row(layers, p)
    moes = [f"l{i}_moe" for i in (1, 3, 5, 7, 10)] + ["mtp1_moe"]
    assert even == {name: p * 22 * 8 / 512 for name in moes}
    macs = ssm.forward_macs_per_row(layers, p, even)
    dense = p * (5 * mamba + 2 * attn + 6 * latent + 2 * 4096 * 16384 + 2 * 4096 * 4096)
    assert macs["dense"] == pytest.approx(dense)
    assert macs["experts"] == pytest.approx(6 * p * 22 * 8 / 512 * expert)
    assert macs["core"] == pytest.approx(2 * (p * (p + 1) / 2) * 8 * 2 * 128)
    assert macs["ssd"] == 5 * p * 32 * 2 * 64 * 128
    per_row = ssm.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values()))
    # a step of two rows: 47.5 TFLOP, the dense products 94 % of them
    assert 2 * per_row == pytest.approx(47.5e12, rel=0.01)
    assert macs["dense"] / sum(macs.values()) > 0.9
    fewer = ssm.train_flops_per_row(layers, p, {k: v / 2 for k, v in even.items()})
    assert per_row - fewer == pytest.approx(6 * macs["experts"] / 2)
    # the scans: bytes bind, not operations
    cost = ssm.ssd_step_cost(layers, 2, p, 2)
    assert cost["ops"] == 6 * 2 * macs["ssd"]
    forward = p * (2 * (2 * 32 * 64 + 2 * 2 * 128) + 4 * 32)
    states = p / 128 * 32 * 64 * 128 * 4 * 2
    assert cost["bytes"] == 5 * 2 * (3 * forward + states)
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12
    # the latent experts: 5,632 slots a layer-step, six layers, four steps
    slots = 6 * 4 * 5632.0
    cost = ssm.latent_experts_cost(layers, slots, 6 * 4, 2)
    assert cost["ops"] == 6 * slots * expert
    assert cost["bytes"] == 2 * (4 * 24 * 8 * expert + 3 * slots * (2 * 1024 + 2 * 2688))
    # the attention's terms are hybrid_lm_flops.py's own at the held heads
    a = next(x for _, k, x in layers if k == "gqa")
    assert (a["heads"], a["kv_heads"], a["head_dim"]) == (8, 1, 128)
    assert hybrid._gqa_macs(a) == attn
    core = hybrid.gqa_core_step_cost(layers, 2, p, 2)
    assert core["ops"] == pytest.approx(6 * 2 * macs["core"])
    # a table without the new kinds reads nothing of them
    glm = RUN.load_module(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.reference.py"))
    assert ssm.ssd_step_cost(glm.LAYERS, 2, p, 2) == {"ops": 0.0, "bytes": 0.0}
    assert ssm.even_slots_per_row(glm.LAYERS, p) == {}


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-ssm-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-ssm", model="benchmark/configs/tiny-ssm.json",
               reference="benchmark/configs/tiny-ssm.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-ssm.json", json.dumps(cfg))
    # weights of spread 0.16: at a hidden size of 64 the projections are then
    # the size 0.02 gives them at 4,096, and the scan adds what the skip does
    write("benchmark/configs/tiny-ssm.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_ssm_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"
        "init_params = lambda seed, layers=LAYERS, std=0.16: _m.init_params(seed, layers, std)\n"))
    write("benchmark/traffic/tiny-ssm.json", json.dumps(
        {"driver": "token-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-ssm", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-ssm.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-ssm", "config": "tiny-ssm",
                               "traffic": "tiny-ssm", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-ssm")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-ssm", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_ssm_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=4_200_000_031)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff",
                           "moe_dropped_slots", "routing_diff_share"}
    assert out["correct"] is True, checks
    assert checks["moe_dropped_slots"]["value"] == 0 == checks["moe_dropped_slots"]["limit"]
    assert set(checks["routing_diff_share"]["by_layer"]) == {"l1_moe", "l4_moe", "mtp1_moe"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    moe = run_note["moe"]
    assert moe["slots_dropped"] == 0 and moe["load_max_over_min"] >= 1
    assert set(moe["by_layer"]) == {f"{l}_moe_counters" for l in ("l1", "l4", "mtp1")}
    # 256 tokens a step, the 6 best of 16, 2 held: 192 slots a step if even
    assert 30 < moe["by_layer"]["l1_moe_counters"]["slots_landed_per_step"] < 512
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
    out = _run_tiny(tiny_tree, seed=42, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False and not checks["update_gap"]["ok"]
    assert checks["loss_gap"]["ok"]


def test_correct_is_false_when_the_scan_drops_its_state_between_chunks(tiny_tree, monkeypatch, capsys):
    """The program's scan with every chunk started from zero: within a chunk
    it is the model's, across chunks it forgets."""
    import jax.numpy as jnp
    from sparknet_tpu.ops import ssd
    real = ssd.ssd

    def every_chunk_alone(x, dt, a, b, c, chunk=ssd.CHUNK):
        cut = lambda t, i: t[:, i:i + chunk]
        return jnp.concatenate([real(cut(x, i), cut(dt, i), a, cut(b, i), cut(c, i), chunk)
                                for i in range(0, x.shape[1], chunk)], axis=1)

    monkeypatch.setattr(ssd, "ssd", every_chunk_alone)
    out = _run_tiny(tiny_tree, seed=43, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    assert not checks["probe_diff"]["ok"] or not checks["momentum_gap"]["ok"]


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-ssm")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic={}, seed=seed, seconds=0.0, trace=trace,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "token-round.py"))
    return ctx, driver, driver.Program(ctx)


def test_both_controls_fail_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's, and with the scan's state dropped at every
    chunk boundary (what `ssm_control.py` reads on the chip): `probe_diff`
    must catch each."""
    ctx, _, prog = _program(tiny_tree, 45)
    _, rows = prog.stack_makers()
    reference = prog.reference_round(rows)
    compare = ctx.load("compare.py")
    limits = {k: v for k, v in TINY_LIMITS.items() if k != "routing_diff_share"}
    fp8 = prog.reference_round(rows, ctx.reference.CONTROL_PRECISION)
    dropped = ctx.reference.round_reference(
        prog.params0, rows, tau=prog.tau, solver=dict(ctx.config["solver"]),
        layers=prog.layers, carry_state=False, mtp_weight=0.1)
    for control in (fp8, dropped):
        failed = [c["name"] for c in compare.first_round_checks(control, reference, limits)
                  if not c["ok"]]
        assert "probe_diff" in failed, failed
    sound = compare.first_round_checks(reference, reference, limits)
    assert all(c["ok"] and c["value"] == 0 for c in sound)
    src = open(os.path.join(BENCH, "ssm_control.py")).read()
    assert "carry_state=False" in src and "CONTROL_PRECISION" in src


# -- the new readers, against the real program at a tiny size ----------------

def test_the_round_is_attributed_to_the_new_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the new
    layer type and its five sub-scopes and under the expert layers' two new
    ones, a window made of the report's own names (a CPU trace has no device
    plane) joins with nothing unmatched, and all 23 readers of the cell
    return numbers."""
    ctx, driver, prog = _program(tiny_tree, 46, trace=True)
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
    scans = report["ssm"]
    assert scans["layers"] == 2 and scans["loops"] > 0 and scans["trips"] >= 8 * scans["loops"]
    assert scans["kernel_calls"] == 0
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "Mamba2", "GQAttention", "MoE", "Eltwise", "Concat",
            "InnerProduct", "SoftmaxWithLoss"} <= types_seen
    assert not {"MTP", "KDAttention", "MLAttention", "GatedMLP", "ShortConv"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for part in ("/in_proj", "/conv", "/ssd", "/gate_norm", "/out_proj",
                 "GQAttention/l3_attn)/core", "GQAttention/mtp0_attn)/core",
                 "/router", "/latent_down", "/dispatch", "/experts", "/combine",
                 "/latent_up", "/shared", "solver_update", "tau_boundary"):
        assert part in scopes, part
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("Mamba2", "GQAttention", "MoE", "InnerProduct"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {"moe": moe})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    device = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
              "round_outside_step_ms", "round_temp_bytes", "moe_experts_device_ms",
              "moe_route_device_ms", "moe_dropped_slots", "moe_load_max_over_min",
              "lm_head_loss_device_ms"]
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in NEW + device}
    assert all(v is not None for v in values.values()), values
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    for k in ("mamba_device_ms", "gqa_share_device_ms", "latent_proj_device_ms",
              "moe_experts_device_ms", "moe_route_device_ms", "lm_head_loss_device_ms"):
        assert 0 < values[k] < sum(parts), k
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    under = lambda *parts: sum(0.5 for op in own.values() if any(
        p in "/" + op["scope"] + "/" for p in parts))
    assert values["mamba_device_ms"] == pytest.approx(by_type("Mamba2"))
    assert values["gqa_share_device_ms"] == pytest.approx(by_type("GQAttention"))
    assert values["latent_proj_device_ms"] == pytest.approx(
        under("/latent_down/", "/latent_up/"))
    # utilisation and the shares by hand
    ssm = ctx.load("ssm_lm_flops.py")
    landed = {b[:-len("_counters")]: v["slots_landed_per_step"] / 2
              for b, v in moe["by_layer"].items()}
    assert set(landed) == {"l1_moe", "l4_moe", "mtp1_moe"}
    per_row = ssm.train_flops_per_row(prog.layers, 128, landed)
    assert values["ssm_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    ssd_ms = sum(0.5 for op in own.values() if op["layer_type"] == "Mamba2"
                 and "/ssd/" in "/" + op["scope"] + "/")
    assert 0 < ssd_ms < values["mamba_device_ms"]
    cost = ssm.ssd_step_cost(prog.layers, 2, 128, 2)
    assert values["mamba_ssd_roofline"] == pytest.approx(
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * ssd_ms))
    assert fake.notes["mamba_ssd_roofline_bound"] == "bytes"
    cost = ssm.latent_experts_cost(prog.layers, moe["slots_landed_per_round"], 3 * 2, 2)
    assert values["latent_moe_experts_roofline"] == pytest.approx(
        100 * max(cost["bytes"] / 819e9, cost["ops"] / 197e12)
        / (1e-3 * values["moe_experts_device_ms"]))
    hybrid = ctx.load("hybrid_lm_flops.py")
    cost = hybrid.gqa_core_step_cost(prog.layers, 2, 128, 2)
    core_ms = sum(0.5 for op in own.values() if op["layer_type"] == "GQAttention"
                  and "/core/" in "/" + op["scope"] + "/")
    assert values["gqa_share_core_roofline"] == pytest.approx(
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / (1e-3 * core_ms))


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load, config=CONFIG, reference=RUN.load_module(
        os.path.join(ROOT, CONFIG["reference"])))
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", [m for m in NEW if m != "ssm_lm_train_mfu"])
def test_new_scope_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has no such layer (another
    sequence model's): the reader returns nothing and does not raise."""
    sm = load("scope_math.py")
    op = {"scope": "tau_step/jvp(MLAttention/l0_attn)/core", "phase": "forward",
          "layer_type": "MLAttention", "layer": "l0_attn"}
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: ({"ops": {"%a": op}}, 0.0)})
    monkeypatch.setattr(sm, "_joined", {})
    glm = RUN.load_json(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.json"))
    ctx = types.SimpleNamespace(load=load, config=glm, reference=RUN.load_module(
        os.path.join(ROOT, glm["reference"])))
    run = types.SimpleNamespace(
        ctx=ctx, trace={"rounds": 1, "window_s": 1.0, "device_ops": [("%a", 1e-3)]},
        notes={}, device_kind="TPU v5 lite")
    assert load(os.path.join("readers", metric + ".py")).read(run) is None
