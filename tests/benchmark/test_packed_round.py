"""The packed state-space hybrid's files (`granite4-h-micro-pp4-tau4`: Mamba-2
mixers of one group, one grouped-query attention without a rotary turn, a
dense SwiGLU after every mixer, muP's multipliers, a tied head, rows that
hold several documents) through the `packed-token-round` traffic, on the CPU
at a tiny size: the cell, its configuration and its metrics are in
`BENCHMARK.json` BY NAME; the driver's boundaries are drawn as the traffic
says; a throw-away cell added as new files is `correct`; it is not when the
mixers read across a document's first position, when a mixer miscounts, or
under either of the reference's two controls; `packed_ssm_lm_flops.py` gives
hand-worked numbers; the new readers return numbers. Counts and arithmetic
only, never a device time.
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
CELL, NAME = "granite4-h-micro-packed-round", "granite4-h-micro-pp4-tau4"
NEW = {"packed_mamba_device_ms": "device_trace", "packed_ssd_roofline": "device_trace",
       "dense_mlp_device_ms": "device_trace", "packed_gqa_device_ms": "device_trace",
       "packed_lm_train_mfu": "device_trace", "doc_boundaries_seen": "program_counter"}
SHARED = ["round_device_ms", "round_interval_p50_ms", "round_window_compiles",
          "round_idle_share", "step_forward_ms", "step_backward_ms",
          "step_optimizer_ms", "round_outside_step_ms", "round_host_call_ms",
          "round_host_keys_ms", "round_temp_bytes", "lm_head_loss_device_ms"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

from test_token_round import _checks, _fake_run, _run_py  # noqa: E402

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
BENCHMARK = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
CONFIG = RUN.load_json(os.path.join(BENCH, "configs", NAME + ".json"))
REDUCED = ["num_hidden_layers", "layer_types", "vocab_size"]

#: the tiny configuration: every mechanism of the published one (Mamba-2
#: mixers of 8 heads of 16 in ONE group of state 16, chunks of 16 so that 128
#: positions are eight; one attention of 4 query heads over 2 of 16, scores
#: times 0.1 and not 1/sqrt(16); SwiGLUs; the four multipliers; a tied head
#: over a sliced vocabulary), documents of 4 to 63 positions
TINY = dict(
    hidden_size=64, intermediate_size=96, shared_intermediate_size=96,
    attention_multiplier=0.1, layer_types=["mamba", "attention", "mamba"],
    num_hidden_layers=3, mamba_chunk_size=16, mamba_d_head=16, mamba_d_state=16,
    mamba_n_heads=8, num_attention_heads=4, num_key_value_heads=2,
    vocab_size=256, local_batch=2, seq_len=128, tau=2,
    share=dict(first_layer=0, vocab_rows=[0, 256], chips_sharing_the_vocabulary=4,
               pipeline_stages=4))
TINY_TRAFFIC = {"driver": "packed-token-round", "log2_min_len": 2, "log2_max_len": 6,
                "warmup_rounds": 1, "trace_skip_rounds": 0, "trace_rounds": 2}
#: the tiny configuration's limits, from CPU readings of this file's own runs
#: over three seeds (bfloat16 program against the float32 reference, weights
#: of spread 0.16): probe_diff sound 0.0093-0.0095, fp8 0.0894-0.0901, leak
#: 0.297-0.341; update_gap sound 0.0014-0.0024, fp8 0.0092-0.0155, leak
#: 0.433-0.472; momentum_gap sound 0.0010-0.0027, fp8 0.0077-0.0131, leak
#: 0.414-0.461; loss_gap sound 1e-5-3e-5 (neither control moves it far: fp8
#: 9e-5-7e-4, leak 5e-4-2e-3)
TINY_LIMITS = {"loss_gap": 1.0e-3, "update_gap": 0.005, "momentum_gap": 0.005,
               "probe_diff": 0.03}


# -- the entries -------------------------------------------------------------

def test_the_cell_its_configuration_and_its_metrics_are_in_the_benchmark_by_name():
    cells = {w["name"]: w for w in BENCHMARK["workloads"]}
    assert cells[CELL] == {**cells[CELL], "config": NAME,
                           "traffic": "packed-token-round", "chips": 1}
    assert "documents a row" in cells[CELL]["why"] and len(cells[CELL]["why"]) <= 200
    assert BENCHMARK["workloads"][-1]["name"] == CELL, "appended, the last"
    assert len(BENCHMARK["workloads"]) == 9
    assert sum(w["chips"] == 4 for w in BENCHMARK["workloads"]) == 1
    entry = BENCHMARK["configs"][-1]
    assert entry["name"] == NAME and len(entry["why"]) <= 200
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == CONFIG["source"] and entry["reduced"] == REDUCED
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    by = {m["name"]: m for m in BENCHMARK["per_layer"] + BENCHMARK["end_to_end"]}
    assert [m["name"] for m in BENCHMARK["per_layer"][-len(NEW):]] == list(NEW)
    for name, source in NEW.items():
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == "train_round_rate"
        assert by[name]["source"] == source
        assert os.path.exists(os.path.join(BENCH, "readers", name + ".py"))
    for name in SHARED + ["train_round_rate"]:
        assert by[name]["workloads"][-1] == CELL, name
    reported = {m["name"] for m in BENCHMARK["per_layer"] if CELL in m["workloads"]}
    assert reported == set(NEW) | set(SHARED)  # no other model's, no moe_*, no setup_*
    assert (by["packed_ssd_roofline"]["unit"], by["packed_ssd_roofline"]["layer"],
            by["packed_ssd_roofline"]["better"]) == ("%", "kernels", "higher")
    assert (by["packed_lm_train_mfu"]["unit"], by["packed_lm_train_mfu"]["layer"]) == (
        "%", "model / solver")
    traffic = RUN.load_json(os.path.join(BENCH, "traffic", "packed-token-round.json"))
    assert (traffic["driver"], traffic["log2_min_len"], traffic["log2_max_len"],
            traffic["warmup_rounds"], traffic["trace_skip_rounds"],
            traffic["trace_rounds"]) == ("packed-token-round", 4, 14, 3, 1, 2)
    assert os.path.exists(os.path.join(BENCH, "drivers", "packed-token-round.py"))


def test_the_configuration_file_holds_the_published_keys():
    """Every key of the catalog's row under its own name and value, but the
    three `reduced` ones; `published` holds those as published; the layers
    held are the first period; no width differs from the row."""
    row = [json.loads(l) for l in open(CATALOG) if '"granite-4.0-h-micro"' in l] \
        if os.path.exists(CATALOG) else []
    assert CONFIG["reduced"] == REDUCED
    if row:
        published = row[0]["config"]
        same = {k: v for k, v in published.items() if k not in REDUCED}
        assert {k: CONFIG[k] for k in same} == same
        assert CONFIG["source"] == row[0]["source_url"]
        assert CONFIG["published"] == {k: published[k] for k in REDUCED}
        assert published["layer_types"][:10] == CONFIG["layer_types"]
        # one period: the other three stages hold the same ten kinds
        assert all(published["layer_types"][i:i + 10] == CONFIG["layer_types"]
                   for i in (10, 20, 30))
    widths = dict(hidden_size=2048, intermediate_size=8192, mamba_n_heads=64,
                  mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
                  mamba_d_conv=4, mamba_chunk_size=256, mamba_expand=2,
                  num_attention_heads=32, num_key_value_heads=8,
                  attention_multiplier=0.015625, embedding_multiplier=12,
                  residual_multiplier=0.22, logits_scaling=8,
                  position_embedding_type="nope", tie_word_embeddings=True,
                  num_local_experts=0, model_type="granitemoehybrid")
    assert {k: CONFIG[k] for k in widths} == widths
    assert CONFIG["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (CONFIG["num_hidden_layers"], CONFIG["published"]["num_hidden_layers"]) == (10, 40)
    # the vocabulary at the floor, an eighth: a quarter does not fit the chip
    assert CONFIG["vocab_size"] == 100352 // 8 == CONFIG["share"]["vocab_rows"][1]
    assert CONFIG["published"]["vocab_size"] == 100352
    assert "does not fit" in CONFIG["deployment"]
    assert CONFIG["share"] == dict(first_layer=0, vocab_rows=[0, 12544],
                                   chips_sharing_the_vocabulary=8, pipeline_stages=4)
    assert (CONFIG["tau"], CONFIG["local_batch"], CONFIG["seq_len"],
            CONFIG["precision"]) == (4, 1, 16384, "bfloat16")
    for key in ("deployment", "documents", "changed_from_source", "plain_reference"):
        assert CONFIG[key], key
    for key in ("chunk_size", "seq_idx", "gated_norm", "swiglu", "multipliers",
                "initialisation", "weights_seed", "solver"):
        assert key in CONFIG["assumed"], key
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    shapes = ref.param_shapes()
    count = lambda name: sum(int(np.prod(s)) for s in shapes[name].values())
    # ISSUE 49's arithmetic, a layer at a time
    assert count("l0_mamba") == 2048 * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 \
        + 4096 * 2048 == 25_847_232
    assert count("l0_mlp") == 2048 * 16384 + 8192 * 2048 == 50_331_648
    assert count("l5_attn") == 2 * 2048 ** 2 + 2 * 2048 * 512 == 10_485_760
    assert "lm_head" not in shapes  # tied: the table's
    period = 9 * (25_847_232 + 50_331_648 + 4096) + 10_485_760 + 50_331_648 + 4096
    assert period == 746_468_288
    assert ref.n_params() == period + 12544 * 2048 + 2048 == 772_160_448 \
        == CONFIG["n_params"]
    assert period + 25088 * 2048 + 2048 == 797_850_560  # at a quarter
    src = open(os.path.join(ROOT, CONFIG["reference"])).read()
    assert "sparknet_tpu" not in src.replace("`sparknet_tpu", ""), \
        "the reference imports nothing of the program"
    assert "lax.scan(step" in src and 'default_matmul_precision("highest")' in src
    assert ref.PROBE_LEAF == ("l0_mamba", "in_proj") and ref.CONTROL_PRECISION == "fp8"
    assert set(ref.LIMITS) == {"probe_diff", "momentum_gap", "update_gap", "loss_gap"}
    assert all(0 < v < 1 for v in ref.LIMITS.values()), ref.LIMITS


def test_the_programs_parameter_count_is_the_references():
    import jax
    from sparknet_tpu import zoo
    from sparknet_tpu.model.net import CompiledNet
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    net = CompiledNet.compile(zoo.granitemoehybrid(CONFIG, rows=1, positions=16384))
    shapes = jax.eval_shape(net.init_params, jax.random.PRNGKey(0))
    assert {l: {p: tuple(v.shape) for p, v in lp.items()} for l, lp in shapes.items()} \
        == ref.param_shapes()
    assert set(net.input_shapes) == {"tokens", "doc_ids"}
    assert set(net.counter_blobs()) == {f"l{i}_mamba_counters"
                                        for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)}
    assert set(net.counter_blobs().values()) == {("doc_boundaries",)}
    assert net.ssd_kernel_shape(net.spec.layer_by_name("l0_mamba")) == {
        "chunk": 256, "heads_per_program": 8, "programs_per_group": 8}


# -- packed_ssm_lm_flops by hand ----------------------------------------------

def test_packed_ssm_lm_flops_by_hand():
    packed, ssm = load("packed_ssm_lm_flops.py"), load("ssm_lm_flops.py")
    ref = RUN.load_module(os.path.join(ROOT, CONFIG["reference"]))
    layers, p = ref.LAYERS, 16384
    mamba = 2048 * 8512 + 4352 * 4 + 4096 * 2048
    attn = 2048 * (2048 + 2 * 512) + 2048 * 2048
    swiglu = 3 * 2048 * 8192
    macs = packed.forward_macs_per_row(layers, p)
    assert macs["dense"] == pytest.approx(
        p * (9 * mamba + attn + 10 * swiglu + 2048 * 12544))
    assert macs["ssd"] == 9 * p * 64 * 2 * 64 * 128
    assert macs["core"] == pytest.approx(p * (p + 1) / 2 * 32 * 2 * 64)
    per_row = packed.train_flops_per_row(layers, p)
    assert per_row == pytest.approx(6 * sum(macs.values()))
    # a step of one row: 80 TFLOP, the SwiGLUs over three fifths of them
    assert per_row == pytest.approx(80.1e12, rel=0.01)
    assert p * 10 * swiglu / sum(macs.values()) == pytest.approx(0.62, abs=0.01)
    # documents: seven of 2,048 and one of 2,048 more hold an eighth of a
    # row's causal pairs, and only the core's term moves
    pairs = 8 * packed.causal_pairs(2048)
    fewer = packed.forward_macs_per_row(layers, p, pairs)
    assert fewer["core"] == pytest.approx(macs["core"] / 8, rel=1e-3)
    assert (fewer["dense"], fewer["ssd"]) == (macs["dense"], macs["ssd"])
    # the scans: `ssm_lm_flops.py`'s count at this table's chunk; bytes bind
    cost = packed.ssd_step_cost(layers, 1, p, 2)
    assert cost == ssm.ssd_step_cost(layers, 1, p, 2)
    assert cost["ops"] == 6 * macs["ssd"]
    forward = p * (2 * (2 * 64 * 64 + 2 * 128) + 4 * 64)
    states = p / 256 * 64 * 64 * 128 * 4 * 2
    assert cost["bytes"] == 9 * (3 * forward + states)
    assert cost["bytes"] / 819e9 > cost["ops"] / 197e12


# -- the driver's boundaries ---------------------------------------------------

def _documents(seed, **over):
    import jax.numpy as jnp
    driver, seeded = load("drivers/packed-token-round.py"), load("seeded.py")
    kw = dict(tau=4, rows=2, positions=4096, lo=4, hi=12, **over)
    return np.asarray(driver.document_ids(seeded, seed, jnp.uint32(3), jnp.uint32(0),
                                          kw["tau"], **kw))


def test_the_drivers_boundaries_fill_the_rows_with_documents_in_range():
    """Ids count a row's documents from 0 and never skip; every document but
    a row's last is floor(2^u) positions, 16 <= len <= 4,096; the last is cut
    by the row's end; the same seed draws the same, another seed others."""
    docs = _documents(4_100_000_007)
    assert docs.shape == (4, 2, 4096) and docs.dtype == np.int32
    assert (docs[..., 0] == 0).all()
    steps = np.diff(docs, axis=-1)
    assert set(np.unique(steps)) <= {0, 1}, "rows exactly filled, one behind another"
    lengths = [np.bincount(row) for row in docs.reshape(-1, 4096)]
    assert all(n.sum() == 4096 for n in lengths)
    whole = np.concatenate([n[:-1] for n in lengths])
    assert whole.min() >= 16 and whole.max() <= 4096 and len(whole) >= 8
    assert all(1 <= n[-1] <= 4096 for n in lengths)
    # log-uniform: the median whole document is far below the mean
    assert np.median(whole) < whole.mean()
    assert (docs == _documents(4_100_000_007)).all()
    other = _documents(4_100_000_008)
    assert (other != docs).any() and (other[..., -1] != docs[..., -1]).any()
    # every step of every round draws its own
    assert len({tuple(n) for n in map(tuple, lengths)}) == len(lengths)


def test_a_later_steps_rows_are_the_stacks_own():
    import jax.numpy as jnp
    driver, seeded = load("drivers/packed-token-round.py"), load("seeded.py")
    kw = dict(tau=4, rows=2, positions=1024, lo=4, hi=10)
    whole = driver.document_ids(seeded, 11, jnp.uint32(0), jnp.uint32(0), 4, **kw)
    step2 = driver.document_ids(seeded, 11, jnp.uint32(0), jnp.uint32(2), 1, **kw)
    assert (np.asarray(whole)[2] == np.asarray(step2)[0]).all()


# -- a tiny cell end to end, added as new files only -------------------------

@pytest.fixture(scope="module")
def tiny_tree(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("tiny-packed-checkout"))
    shutil.copytree(BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: open(p, "rb").read() for d, _, fs in os.walk(root)
              for p in (os.path.join(d, f) for f in fs)}
    cfg = dict(CONFIG, name="tiny-packed", model="benchmark/configs/tiny-packed.json",
               reference="benchmark/configs/tiny-packed.reference.py",
               reduced=sorted(set(TINY) - {"share"}), weights_seed=5, **TINY)
    write = lambda rel, text: open(os.path.join(root, rel), "w").write(text)
    write("benchmark/configs/tiny-packed.json", json.dumps(cfg))
    # weights of spread 0.16: at a hidden size of 64 the projections are then
    # the size 0.02 gives them at 2,048, and the scan adds what the skip does
    write("benchmark/configs/tiny-packed.reference.py", (
        "import importlib.util, os\n"
        "_s = importlib.util.spec_from_file_location('tiny_packed_ref_base', os.path.join("
        f"os.path.dirname(os.path.abspath(__file__)), '{NAME}.reference.py'))\n"
        "_m = importlib.util.module_from_spec(_s); _s.loader.exec_module(_m)\n"
        "globals().update({k: v for k, v in vars(_m).items() if not k.startswith('__')})\n"
        f"LIMITS = {TINY_LIMITS!r}\n"
        "init_params = lambda seed, layers=LAYERS, std=0.16: _m.init_params(seed, layers, std)\n"))
    write("benchmark/traffic/tiny-packed.json", json.dumps(TINY_TRAFFIC))
    bench = json.loads(json.dumps(BENCHMARK))
    bench["configs"].append({"name": "tiny-packed", "source": cfg["source"],
                             "file": "benchmark/configs/tiny-packed.json",
                             "reduced": cfg["reduced"], "why": "a test's own"})
    bench["workloads"].append({"name": "tiny-packed", "config": "tiny-packed",
                               "traffic": "tiny-packed", "chips": 1, "why": "a test's own"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append("tiny-packed")
    write("BENCHMARK.json", json.dumps(bench))
    assert all(open(p, "rb").read() == b for p, b in before.items())
    return root


def _run_tiny(root, seed, seconds=2.0, trace=False):
    return _run_py(root).run_cell(root, "tiny-packed", seed, seconds, trace,
                                  time.perf_counter())


def test_tiny_packed_cell_added_as_files_is_correct(tiny_tree, capsys):
    out = _run_tiny(tiny_tree, seed=4_200_000_031)
    checks, run_note = _checks(capsys)
    assert set(checks) == {"loss_gap", "update_gap", "momentum_gap", "probe_diff",
                           "doc_boundaries_miscounted"}
    assert out["correct"] is True, checks
    exact = checks["doc_boundaries_miscounted"]
    assert exact["value"] == 0 == exact["limit"] and exact["drawn"] > 4
    assert exact["counted"] == {"l0_mamba_counters": exact["drawn"],
                                "l2_mamba_counters": exact["drawn"]}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"train_round_rate", "setup_s"}
    seen = run_note["doc_boundaries"]
    # 2 steps x 2 rows of 128 positions, documents of 4 to 63: several a row
    assert seen["per_round"] > 4 and seen["documents_per_row"] > 2
    assert seen["check_round_drawn"] == exact["drawn"]
    assert 0 < seen["causal_pairs_per_row"] < 128 * 129 / 2
    assert run_note["tokens_per_s_per_chip"] == pytest.approx(
        128 * out["metrics"]["train_round_rate"]["value"])


def test_correct_is_false_when_the_mixers_read_across_a_documents_first_position(
        tiny_tree, monkeypatch, capsys):
    """The program's mixers given one document a row (the loss keeps its
    targets): the taps, the state and the keys leak, and the comparison
    says so -- while the mixers, handed no boundary, count none."""
    import jax.numpy as jnp
    from sparknet_tpu.model import seq_layers as sl
    real_mamba, real_gqa = sl.mamba2, sl.gqa
    monkeypatch.setattr(sl, "mamba2", lambda p, params, u, ctx, docs=None:
                        real_mamba(p, params, u, ctx, jnp.zeros_like(docs)))
    monkeypatch.setattr(sl, "gqa", lambda p, params, x, ctx, docs=None:
                        real_gqa(p, params, x, ctx, jnp.zeros_like(docs)))
    out = _run_tiny(tiny_tree, seed=43, seconds=0.5)
    checks, _ = _checks(capsys)
    assert out["correct"] is False
    assert not checks["probe_diff"]["ok"]
    assert not checks["doc_boundaries_miscounted"]["ok"]


def _program(tiny_tree, seed, trace=False):
    run = _run_py(tiny_tree)
    bench, cell, entry = run.resolve(tiny_tree, "tiny-packed")
    config = run.load_json(os.path.join(tiny_tree, entry["file"]))
    ctx = run.Ctx(root=tiny_tree, bench=os.path.join(tiny_tree, "benchmark"), cell=cell,
                  config=config, traffic=TINY_TRAFFIC, seed=seed, seconds=0.0,
                  trace=trace,
                  reference=run.load_module(os.path.join(tiny_tree, config["reference"])),
                  t0=time.perf_counter(), tmp="")
    driver = ctx.load(os.path.join("drivers", "packed-token-round.py"))
    return ctx, driver, driver.program(ctx)


def test_both_controls_fail_the_comparison(tiny_tree):
    """The reference put in the program's place, computed in the precision
    below the configuration's, and with its mixers given one document a row
    (what `packed_control.py` reads on the chip): `probe_diff` must catch
    each, on both seeds."""
    compare = None
    for seed in (45, 4_000_000_045):
        ctx, _, prog = _program(tiny_tree, seed)
        _, rows = prog.stack_makers()
        reference = prog.reference_round(rows)
        compare = ctx.load("compare.py")
        fp8 = prog.reference_round(rows, ctx.reference.CONTROL_PRECISION)
        leak = prog.reference_round(rows, leak=True)
        for control in (fp8, leak):
            failed = [c["name"] for c in compare.first_round_checks(
                control, reference, TINY_LIMITS) if not c["ok"]]
            assert "probe_diff" in failed, failed
        sound = compare.first_round_checks(reference, reference, TINY_LIMITS)
        assert all(c["ok"] and c["value"] == 0 for c in sound)
    src = open(os.path.join(BENCH, "packed_control.py")).read()
    assert "leak=True" in src and "CONTROL_PRECISION" in src


# -- the new readers, against the real program at a tiny size ----------------

def test_the_round_is_attributed_to_the_scopes_and_the_readers_read(tiny_tree, monkeypatch):
    """A tiny round's compiled text: the report puts its ops under the
    mixers' five scopes, the SwiGLU's and the attention's core, its `ssd`
    part says what the scans walk, a window made of the report's own names
    (a CPU trace has no device plane) joins with nothing unmatched, and the
    cell's readers return numbers -- the kernels' roofline share none, on a
    backend whose scans ran no kernel."""
    ctx, driver, prog = _program(tiny_tree, 46, trace=True)
    make_stack, rows = prog.stack_makers()
    program = prog.check_round(make_stack(0))
    drawn = prog.boundaries_drawn(rows)
    assert {b: float(v[0]) for b, v in program["counters"].items()} == {
        "l0_mamba_counters": drawn, "l2_mamba_counters": drawn}

    sm = ctx.load("scope_math.py")
    monkeypatch.setattr(sm, "_reports", {})
    monkeypatch.setattr(sm, "_joined", {})
    report, _ = sm.report()
    assert report is prog.trainer.program_report()
    # heads of 16 fill no lane tile: the `jnp` form, and the report says so
    assert report["ssd"] == {"kernel_calls": 0, "chunk": 0, "heads_per_program": 0,
                             "programs_per_group": 0, "layers_under_documents": 2}
    assert report["ssm"]["layers"] == 2 and report["ssm"]["loops"] > 0
    own = {n: op for n, op in report["ops"].items()
           if op["opcode"] not in ("while", "call", "conditional")}
    types_seen = {op["layer_type"] for op in own.values()}
    assert {"Embed", "RMSNorm", "Mamba2", "GQAttention", "GatedMLP", "Eltwise",
            "InnerProduct", "SoftmaxWithLoss"} <= types_seen
    assert not {"MoE", "MTP", "KDAttention", "MLAttention", "ShortConv"} & types_seen
    scopes = " ".join(op["scope"] for op in own.values())
    for part in ("/in_proj", "/conv", "/ssd", "/gate_norm", "/out_proj", "/mlp_pre",
                 "GQAttention/l1_attn)/core", "solver_update", "tau_boundary"):
        assert part in scopes, part

    notes = {"doc_boundaries": {"per_round": drawn, "causal_pairs_per_row": 2000.0}}
    fake = _fake_run(ctx, [(n, 1e-3) for n in own], notes)
    j = sm.joined(fake)
    assert j is not None and j["unmatched_share"] == 0.0
    values = {m: ctx.load(os.path.join("readers", m + ".py")).read(fake)
              for m in list(NEW) + ["lm_head_loss_device_ms", "step_forward_ms"]}
    assert values.pop("packed_ssd_roofline") is None  # no kernel ran here
    assert all(v is not None for v in values.values()), values
    by_type = lambda t: sum(0.5 for op in own.values() if op["layer_type"] == t)
    assert values["packed_mamba_device_ms"] == pytest.approx(by_type("Mamba2"))
    assert values["dense_mlp_device_ms"] == pytest.approx(by_type("GatedMLP"))
    assert values["packed_gqa_device_ms"] == pytest.approx(by_type("GQAttention"))
    assert values["doc_boundaries_seen"] == drawn
    packed = ctx.load("packed_ssm_lm_flops.py")
    per_row = packed.train_flops_per_row(prog.layers, 128, 2000.0)
    assert values["packed_lm_train_mfu"] == pytest.approx(
        100 * (2 * 4 / 4.0) * per_row / 197e12)
    # the kernels' share, on ops a kernel made: a made-up report entry
    kernel = {"scope": "tau_step/jvp(Mamba2/l0_mamba)/ssd", "phase": "forward",
              "layer_type": "Mamba2", "layer": "l0_mamba", "pallas": True,
              "opcode": "custom-call"}
    plain = dict(kernel, pallas=False, opcode="fusion")
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: (
        {"ops": {"%k": kernel, "%p": plain}}, 0.0)})
    monkeypatch.setattr(sm, "_joined", {})
    fake = _fake_run(ctx, [("%k", 4e-3), ("%p", 9e-3)], notes)
    share = ctx.load("readers/packed_ssd_roofline.py").read(fake)
    cost = packed.ssd_step_cost(prog.layers, 2, 128, 2)
    assert share == pytest.approx(  # 4 ms over 2 rounds: the kernel's alone
        100 * max(cost["bytes"] * 2 / 819e9, cost["ops"] * 2 / 197e12) / 2e-3)
    assert fake.notes["packed_ssd_kernels_ms"] == pytest.approx(2.0)


@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_reader_returns_none_with_no_trace(metric):
    ctx = types.SimpleNamespace(load=load, config=CONFIG, reference=RUN.load_module(
        os.path.join(ROOT, CONFIG["reference"])))
    run = types.SimpleNamespace(ctx=ctx, trace=None, notes={})
    assert load(os.path.join("readers", metric + ".py")).read(run) is None


@pytest.mark.parametrize("metric", sorted(set(NEW) - {"packed_lm_train_mfu"}))
def test_new_reader_finds_nothing_in_a_program_without_such_layers(metric, monkeypatch):
    """On a made-up run of a program that has no such layer and counts no
    boundary (another sequence model's): the reader returns nothing and does
    not raise."""
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
