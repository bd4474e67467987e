"""The sliding-window configuration's files (`smallthinker-21b-ep4-tau4`:
grouped-query attention global without a rotary turn or over a sliding
window with one, seven query heads a key/value head, a router fed from
before the attention, ReGLU experts under a softmax of the chosen logits, an
untied head) through the `token-round` traffic, on the CPU at a tiny size:
the cell, its configuration and its metrics are in `BENCHMARK.json` BY NAME;
a throw-away cell added as new files is `correct`; it is not when the round
returns its state unchanged, when the sliding layers read every key, when the
router reads the experts' input, or under the fp8 control;
`window_lm_flops.py` gives hand-worked numbers; the round's ops are
attributed to the layers' scopes and the five new readers return numbers.
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
CELL, NAME = "smallthinker-21b-train-round", "smallthinker-21b-ep4-tau4"
NEW = ["swa_gqa_device_ms", "swa_window_core_roofline", "swa_global_core_roofline",
       "swa_core_blocks_visited", "swa_lm_train_mfu"]
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "moe_experts_device_ms",
          "moe_experts_roofline", "moe_route_device_ms", "moe_dropped_slots",
          "moe_load_max_over_min", "lm_head_loss_device_ms"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "sliding_window_layout", "rope_layout",
           "moe_num_primary_experts", "vocab_size"]


# the token cells' shared helpers: a checkout's run.py as a module, a run's
# check notes, a made-up traced run
from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))

#: the tiny configuration: every mechanism of the published one (a global
#: layer without a rotary turn, then sliding layers with one under a window
#: of 8 of the 32 positions, seven query heads a key/value head, 2 of 8
#: experts held and the 2 best a token, an untied head over a sliced
#: vocabulary), at widths a test run can hold
TINY = dict(
    hidden_size=64, head_dim=16, num_attention_heads=14, num_key_value_heads=2,
    moe_ffn_hidden_size=48, moe_num_primary_experts=2,
    moe_num_active_primary_experts=2, num_hidden_layers=3,
    sliding_window_layout=[0, 1, 1], rope_layout=[0, 1, 1], sliding_window_size=8,
    max_position_embeddings=32, vocab_size=256, local_batch=2, seq_len=32, tau=2,
    share=dict(chips_sharing_a_layer=4, moe_num_primary_experts=8,
               experts_held=[2, 2], vocab_rows=[0, 256], first_layer=0))
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: (bfloat16 program against the float32 reference; the docstrings of the
#: tests below give what a broken round reads)
TINY_LIMITS = {"loss_gap": 1.0e-3, "update_gap": 0.03, "momentum_gap": 0.045,
               "probe_diff": 0.03, "routing_diff_share": 0.2}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark_by_name():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME, "traffic": "token-round",
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
    for name in ("gqa_device_ms", "gqa_core_roofline", "gqa_share_device_ms",
                 "gqa_share_core_roofline", "hybrid_lm_train_mfu", "lm_train_mfu",
                 "kda_lm_train_mfu", "eva_lm_train_mfu", "ssm_lm_train_mfu",
                 "eva_core_blocks_visited", "mla_device_ms", "mtp_device_ms",
                 "latent_moe_experts_roofline", "setup_import_s", "setup_cache_misses",
                 "train_mfu", "lrn_roofline"):
        assert CELL not in by[name]["workloads"], name
    reported = {m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
    assert reported == set(NEW) | set(SHARED) and len(reported) == 22
    for name in ("swa_window_core_roofline", "swa_global_core_roofline"):
        assert (by[name]["unit"], by[name]["layer"], by[name]["better"],
                by[name]["source"]) == ("%", "kernels", "higher", "device_trace")
    assert (by["swa_lm_train_mfu"]["unit"], by["swa_lm_train_mfu"]["layer"]) == (
        "%", "model / solver")
    assert (by["swa_core_blocks_visited"]["unit"],
            by["swa_core_blocks_visited"]["source"]) == ("count", "program_counter")
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1
    assert os.path.exists(os.path.join(BENCH, "traffic", "token-round.json"))


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog's row under its own name and value, but the
    five `reduced` ones; `published` holds those five as published; the share
    block and the held counts agree; no width differs from the row; the count
    of ISSUE 46, re-reckoned from the reference."""
    row = [json.loads(l) for l in open(CATALOG)
           if '"SmallThinker-21BA3B-Instruct"' in l] if os.path.exists(CATALOG) else []
    assert CONFIG["reduced"] == REDUCED
    if row:
        published = row[0]["config"]
        same = {k: v for k, v in published.items() if k not in REDUCED}
        assert {k: CONFIG[k] for k in same} == same
        assert CONFIG["source"] == row[0]["source_url"]
        assert CONFIG["published"] == {k: published[k] for k in REDUCED}
    widths = dict(hidden_size=2560, head_dim=128, num_attention_heads=28,
                  num_key_value_heads=4, moe_ffn_hidden_size=768,
                  moe_num_active_primary_experts=6, sliding_window_size=4096,
                  max_position_embeddings=16384, rope_theta=1500000,
                  rms_norm_eps=1e-6, moe_primary_router_apply_softmax=True,
                  norm_topk_prob=True, tie_word_embeddings=False, rope_scaling=None,
                  model_type="smallthinker")
    assert {k: CONFIG[k] for k in widths} == widths
    assert {k: CONFIG[k] for k in REDUCED} == dict(
        num_hidden_layers=4, sliding_window_layout=[0, 1, 1, 1],
        rope_layout=[0, 1, 1, 1], moe_num_primary_experts=16, vocab_size=37984)
    pub = CONFIG["published"]
    assert (pub["num_hidden_layers"], pub["moe_num_primary_experts"],
            pub["vocab_size"]) == (52, 64, 151936)
    assert pub["sliding_window_layout"] == pub["rope_layout"] == [0, 1, 1, 1] * 13
    # the layers kept: one whole period in the published order
    first = CONFIG["share"]["first_layer"]
    assert CONFIG["sliding_window_layout"] == pub["sliding_window_layout"][first:first + 4]
    share = CONFIG["share"]
    assert share["moe_num_primary_experts"] == 64 and share["experts_held"] == [0, 16]
    assert share["vocab_rows"] == [0, 151936 // 4] and share["chips_sharing_a_layer"] == 4
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"]) == (4, 1, 16384)
    for key in ("deployment", "expert_load", "changed_from_source", "assumed",
                "plain_reference", "sample"):
        assert CONFIG[key], key
    for key in ("model_type", "router_input", "router_weights", "expert_form",
                "layouts", "qk_norm", "rotary_pairing", "weights_seed",
                "capacity_factor"):
        assert key in CONFIG["assumed"], key
    assert "secondary" in CONFIG["changed_from_source"]["left_out"]
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    # the table's spread is the file's own choice, said to depart from the
    # family's, and both sides read it from the file
    assert ref.EMBED_STD == CONFIG["embed_init_std"] == 1.0
    assert "DEPARTS" in CONFIG["assumed"]["initialisation"]
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    assert count("l0_attn") == count("l3_attn") == 20_971_520
    assert count("l1_moe") == 16 * 5_898_240 + 2560 * 64
    assert "router_bias" not in shapes["l1_moe"]
    assert count("embed") == count("lm_head") == 97_239_040
    assert count("l0_op_norm") + count("l0_attn") + count("l0_mlp_norm") + count(
        "l0_moe") == 115_512_320
    assert ref.n_params() == CONFIG["n_params"] == 656_529_920, "ISSUE 46's count"
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"
    table = {n: a for n, _, a in ref.LAYERS}
    assert (table["l0_attn"]["window"], table["l0_attn"]["rotary"]) == (None, False)
    for i in (1, 2, 3):
        assert (table[f"l{i}_attn"]["window"], table[f"l{i}_attn"]["rotary"]) == (4096, True)


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    from sparknet_tpu.model.seq_layers import moe_capacity, sum_walks_buffer
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.SEQUENCE_MODELS[CONFIG["model_type"]](
        CONFIG, rows=1, positions=16384))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    assert net.spec.layer_by_name("embed").embed.std == CONFIG["embed_init_std"]
    moe = net.spec.layer_by_name("l1_moe")
    assert moe.bottoms == ("l1_mlp_norm", "l1_op_norm")
    rows = moe_capacity(moe.moe, 16384)
    assert rows == 61440 == int(CONFIG["share"]["capacity_factor"] * 24576)
    assert not sum_walks_buffer(rows, 16384, 6)  # the six gathers' side
    # the window part's counts, by hand from ATTN_BLOCKS (512, 1024, 512): a
    # block of 512 queries from 512 i meets the key blocks of 1,024 from
    # max(0, i // 2 - 4) to i // 2 under the window and from 0 without
    scopes, layers = net.window_scopes()
    assert scopes == {"GQAttention": "core"}
    causal = sum(i // 2 + 1 for i in range(32))
    sliding = sum(i // 2 - max(0, i // 2 - 4) + 1 for i in range(32))
    assert (causal, sliding) == (272, 140)
    assert layers == {
        "l0_attn": {"window": None, "blocks_visited": causal, "blocks_causal": causal},
        **{f"l{i}_attn": {"window": 4096, "blocks_visited": sliding,
                          "blocks_causal": causal} for i in (1, 2, 3)}}


# -- window_lm_flops by hand --------------------------------------------------

def test_window_lm_flops_by_hand():
    window, lm = load("window_lm_flops.py"), load("lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    # a small size first: 8 positions under a window of 3: 1 + 2 + 3 x 6 pairs
    a = dict(d=8, heads=4, kv_heads=2, head_dim=2, window=3)
    assert window.core_pairs(a, 8) == 1 + 2 + 3 * 6 == 3 * 8 - 3 * 2 / 2
    assert window.core_pairs(dict(a, window=None), 8) == 36 == 8 * 9 / 2
    assert window.core_pairs(dict(a, window=8), 8) == 36  # as long as the row
    assert window.core_pairs(dict(a, window=50), 8) == 36
    small = (("l0_attn", "gqa", dict(a, window=None)), ("l1_attn", "gqa", a),
             ("l1_moe", "moe", dict(d=8, width=6, routed=8, held=2, k=2, shared=0)),
             ("lm_head", "head", dict(d=8, vocab=10)))
    proj = 8 * (8 + 2 * 4) + 8 * 8
    macs = window.forward_macs_per_row(small, 8, {"l1_moe": 5.0})
    assert macs["core"] == (36 + 21) * 4 * 2 * 2
    assert macs["dense"] == 8 * (2 * proj + 8 * 8 + 8 * 10)
    assert macs["experts"] == 5 * 3 * 8 * 6  # the slots that landed, no more
    assert window.even_slots_per_row(small, 8) == {"l1_moe": 8 * 2 * 2 / 8}
    cost = window.core_step_cost(small, 2, 8, 2, sliding=True)
    assert cost == {"ops": 6 * 2 * 21 * 4 * 2 * 2, "bytes": 2 * 8 * 6 * 2 * (4 + 2) * 2,
                    "layers": ["l1_attn"]}
    assert window.core_step_cost(small, 2, 8, 2, sliding=False)["layers"] == ["l0_attn"]
    # the cell's size: ISSUE 46's table
    layers, p = ref.LAYERS, 16384
    assert window.core_pairs({"window": 4096}, p) == 58_722_304
    assert window.core_pairs({"window": None}, p) == 134_225_920
    even = window.even_slots_per_row(layers, p)
    assert even == {f"l{i}_moe": 24576.0 for i in range(4)}
    macs = window.forward_macs_per_row(layers, p, even)
    gqa = 2560 * (3584 + 2 * 512) + 3584 * 2560
    head = 2560 * 37984
    assert macs["dense"] == pytest.approx(p * (4 * gqa + 4 * 2560 * 64 + head))
    assert macs["core"] == pytest.approx((3 * 58_722_304 + 134_225_920) * 28 * 256)
    assert macs["core"] == pytest.approx(2225e9, rel=1e-3)
    assert macs["experts"] == pytest.approx(4 * 24576 * 3 * 2560 * 768)
    assert sum(macs.values()) == pytest.approx(5.78e12, rel=2e-3)
    base = lm.forward_macs_per_row(layers, p, even)
    assert macs["dense"] - base["dense"] == pytest.approx(p * 4 * gqa)
    assert base["core"] == 0 and base["experts"] == macs["experts"]
    per_row = window.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values())) and per_row > 34e12
    sliding = window.core_step_cost(layers, 1, p, 2, sliding=True)
    assert sliding["layers"] == ["l1_attn", "l2_attn", "l3_attn"]
    assert sliding["ops"] == pytest.approx(6 * 3 * 58_722_304 * 28 * 256)
    assert sliding["bytes"] == 3 * p * 6 * 128 * (28 + 4) * 2
    glob = window.core_step_cost(layers, 1, p, 2, sliding=False)
    assert glob["layers"] == ["l0_attn"]
    assert glob["ops"] == pytest.approx(6 * 134_225_920 * 28 * 256)
    # both compute-bound on a v5e
    for cost in (sliding, glob):
        assert cost["ops"] / 197e12 > cost["bytes"] / 819e9
    fewer = window.train_flops_per_row(layers, p, {k: v / 2 for k, v in even.items()})
    assert per_row - fewer == pytest.approx(6 * macs["experts"] / 2)
    # the experts' own cost reads this table: three products a slot
    experts = lm.experts_cost(layers, 4 * 4 * 24576.0, 16, 2)
    assert experts["ops"] == pytest.approx(6 * 4 * 4 * 24576 * 3 * 2560 * 768)
    # a table without the new kind reads as lm_flops.py reads it
    glm = RUN.load_module(os.path.join(BENCH, "configs", "glm47-flash-ep8-tau4.reference.py"))
    assert window.train_flops_per_row(glm.LAYERS, 8192) == lm.train_flops_per_row(
        glm.LAYERS, 8192)


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-window-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-window", model="benchmark/configs/tiny-window.json",
               reference="benchmark/configs/tiny-window.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-window.json", json.dumps(cfg))
    write("benchmark/configs/tiny-window.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_window_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"))
    write("benchmark/traffic/tiny-window.json", json.dumps(
        {"driver": "token-round", "warmup_rounds": 1, "trace_skip_rounds": 0,
         "trace_rounds": 2}))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-window", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-window.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-window", "config": "tiny-window",
                               "traffic": "tiny-window", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-window")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-window", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_window_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=2_500_000_046)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff",
                           "moe_dropped_slots", "routing_diff_share"}
    assert out["correct"] is True, checks
    assert checks["moe_dropped_slots"]["value"] == 0 == checks["moe_dropped_slots"]["limit"]
    assert set(checks["routing_diff_share"]["by_layer"]) == {"l0_moe", "l1_moe", "l2_moe"}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    moe = run_note["moe"]
    assert moe["slots_dropped"] == 0 and moe["load_max_over_min"] >= 1
    assert set(moe["by_layer"]) == {f"l{i}_moe_counters" for i in (0, 1, 2)}
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


def test_correct_is_false_when_the_sliding_layers_read_every_key(tiny_tree, monkeypatch, capsys):
    """The window dropped (a sliding layer handed no mask: causal over all 32
    positions where the model reads 8): every layer above reads another
    stream."""
    from sparknet_tpu.model import seq_layers
    monkeypatch.setattr(seq_layers, "gqa_mask", lambda p, positions: None)
    out = _run_tiny(tiny_tree, seed=33, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False, checks
    assert not (checks["update_gap"]["ok"] and checks["momentum_gap"]["ok"]
                and checks["probe_diff"]["ok"])


def test_correct_is_false_when_the_router_reads_the_experts_input(tiny_tree, monkeypatch, capsys):
    """The router fed the norm AFTER the attention, as every other model's
    is: it chooses other experts than the reference's for some positions and
    every weight differs."""
    from sparknet_tpu.model import seq_layers
    real = seq_layers.moe
    monkeypatch.setattr(seq_layers, "moe",
                        lambda p, params, x, ctx, router_x=None: real(p, params, x, ctx))
    out = _run_tiny(tiny_tree, seed=36, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False, checks
    assert not (checks["update_gap"]["ok"] and checks["momentum_gap"]["ok"]
                and checks["probe_diff"]["ok"] and checks["routing_diff_share"]["ok"])


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-window")
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
    assert ctx.reference.PROBE_LEAF == ("l0_attn", "o")
    control = prog.reference_round(rows, ctx.reference.CONTROL_PRECISION)
    compare = ctx.load("compare.py")
    limits = {k: v for k, v in TINY_LIMITS.items() if k != "routing_diff_share"}
    failed = [c["name"] for c in compare.first_round_checks(control, reference, limits)
              if not c["ok"]]
    assert "probe_diff" in failed, failed
    sound = compare.first_round_checks(reference, reference, limits)
    assert all(c["ok"] and c["value"] == 0 for c in sound)


# -- the new readers, against the real program at a tiny size ----------------

def test_the_round_is_attributed_to_the_layers_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the layer
    types and sub-scopes, layer by layer; a window made of the report's own
    names (a CPU trace has no device plane) joins with nothing unmatched, and
    all 22 readers of the cell return numbers (the blocks' count from a
    report part written in by hand: a row of 32 positions is no whole
    tile)."""
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
    part = report["window"]
    assert part["windowed_layers"] == 2 and part["blocks_visited"] == 0
    assert {n: l["window"] for n, l in part["layers"].items()} == {
        "l0_attn": None, "l1_attn": 8, "l2_attn": 8}
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "GQAttention", "MoE", "Eltwise", "InnerProduct",
            "SoftmaxWithLoss"} <= types_seen
    assert not {"MLAttention", "MTP", "GatedMLP", "ShortConv"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for piece in ("GQAttention/l0_attn)/core", "GQAttention/l2_attn)/core",
                  "/router", "/dispatch", "/experts", "/combine", "solver_update",
                  "tau_boundary"):
        assert piece in scopes, piece
    assert "/shared" not in scopes  # no shared expert
    phases = {(op["phase"], op["layer_type"]) for op in own.values()}
    for kind in ("GQAttention", "MoE", "InnerProduct"):
        assert ("forward", kind) in phases and ("backward", kind) in phases

    fake = _fake_run(ctx, [(n, 1e-3) for n in own], {"moe": moe})
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    device = ["step_forward_ms", "step_backward_ms", "step_optimizer_ms",
              "round_outside_step_ms", "round_temp_bytes", "moe_experts_device_ms",
              "moe_experts_roofline", "moe_route_device_ms", "moe_dropped_slots",
              "moe_load_max_over_min", "lm_head_loss_device_ms"]
    read = lambda m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
    assert read("swa_core_blocks_visited") is None  # no kernel ran: nothing to read
    monkeypatch.setitem(report, "window", {**part, "blocks_visited": 692,
                                           "blocks_causal": 1088})
    values = {m: read(m) for m in NEW + device}
    assert all(v is not None for v in values.values()), values
    assert values["swa_core_blocks_visited"] == 692
    assert fake.notes["window"]["blocks_causal"] == 1088
    parts = [values[k] for k in ("step_forward_ms", "step_backward_ms",
                                 "step_optimizer_ms", "round_outside_step_ms")]
    assert sum(parts) == pytest.approx(0.5 * len(own))  # 1 ms over 2 rounds
    for k in ("swa_gqa_device_ms", "moe_experts_device_ms", "moe_route_device_ms",
              "lm_head_loss_device_ms"):
        assert 0 < values[k] < sum(parts), k
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    assert values["swa_gqa_device_ms"] == pytest.approx(by_type("GQAttention"))
    # utilisation and the two shares by hand
    window = ctx.load("window_lm_flops.py")
    landed = {b[:-len("_counters")]: v["slots_landed_per_step"] / 2
              for b, v in moe["by_layer"].items()}
    per_row = window.train_flops_per_row(prog.layers, 32, landed)
    assert values["swa_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    core_ms = lambda layers: sum(
        0.5 for op in own.values() if op["layer_type"] == "GQAttention"
        and op["layer"] in layers and "/core/" in "/" + op["scope"] + "/")
    for metric, sliding, layers in (
            ("swa_window_core_roofline", True, ("l1_attn", "l2_attn")),
            ("swa_global_core_roofline", False, ("l0_attn",))):
        cost = window.core_step_cost(prog.layers, 2, 32, 2, sliding)
        assert tuple(cost["layers"]) == layers
        assert values[metric] == pytest.approx(
            100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12)
            / (1e-3 * core_ms(layers)))
        assert fake.notes[metric + "_bound"] in ("ops", "bytes")


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_returns_none_with_no_trace(metric):
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    ctx = types.SimpleNamespace(load=load, config=CONFIG, reference=ref)
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", ["swa_gqa_device_ms", "swa_window_core_roofline",
                                    "swa_global_core_roofline",
                                    "swa_core_blocks_visited"])
def test_new_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has no such layer and no `window`
    part in its report (the parent commit's): 0 ms under the type, and the
    shares and the count return nothing and do not raise."""
    sm = load("scope_math.py")
    op = {"scope": "tau_step/jvp(MLAttention/l0_attn)/core", "phase": "forward",
          "layer_type": "MLAttention", "layer": "l0_attn"}
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: ({"ops": {"%a": op}}, 0.0)})
    monkeypatch.setattr(sm, "_joined", {})
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    ctx = types.SimpleNamespace(load=load, config=CONFIG, reference=ref)
    run = types.SimpleNamespace(
        ctx=ctx, trace={"rounds": 1, "window_s": 1.0, "device_ops": [("%a", 1e-3)]},
        notes={}, device_kind="TPU v5 lite")
    got = load(os.path.join("readers", metric + ".py")).read(run)
    assert got in (None, 0), got
    if metric != "swa_gqa_device_ms":
        assert got is None
