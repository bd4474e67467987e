"""The two per-layer metrics that read the `recomputed` key of the program's
account of itself (PR 51): `step_recompute_ms`, `step_recompute_matmul_ms` and
the table the first puts into the run note, by hand on a made-up report, and
their `BENCHMARK.json` entries by name. All on the CPU: arithmetic, never a
device time.
"""
from __future__ import annotations

import os
import types

import pytest
from test_token_round import _run_py

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NEW = ("step_recompute_ms", "step_recompute_matmul_ms")
CELLS = ["glm47-flash-train-round", "nemotron3-super-train-round",
         "granite4-h-micro-packed-round"]

RUN = _run_py()
load = lambda name: RUN.load_module(os.path.join(BENCH, name))
reader = lambda metric: load(os.path.join("readers", metric + ".py"))

_AGAIN = "while/body/tau_step/transpose(jvp(tau_step))/jvp()/checkpoint/rematted_computation/"


def _op(phase, layer_type, layer, scope, again=False, matmul=False,
        opcode="fusion"):
    return {"scope": scope, "phase": phase, "recomputed": again,
            "layer_type": layer_type, "layer": layer, "opcode": opcode,
            "matmul": matmul}


#: a made-up report of one block's layers: a mixer's in-projection forward,
#: made again and transposed, its scan's kernel made again, its norm made
#: again, a dense layer whose kept products are not, the solver's update
OPS = {
    "%fusion.1": _op("forward", "Mamba2", "l0_mamba", "tau_step/jvp(Mamba2/l0_mamba)/in_proj", matmul=True),
    "%fusion.2": _op("backward", "Mamba2", "l0_mamba", _AGAIN + "Mamba2/l0_mamba/in_proj", True, True),
    "%ssd.1": _op("backward", "Mamba2", "l0_mamba", _AGAIN + "Mamba2/l0_mamba/ssd/ssd_chunk_fwd", True, opcode="custom-call"),
    "%fusion.3": _op("backward", "Mamba2", "l0_mamba", _AGAIN + "Mamba2/l0_mamba/ssd/jit(softplus)", True),
    "%fusion.4": _op("backward", "RMSNorm", "l0_norm", _AGAIN + "RMSNorm/l0_norm", True),
    "%fusion.5": _op("backward", "Mamba2", "l0_mamba", "tau_step/transpose(jvp(tau_step))/jvp()/checkpoint/Mamba2/l0_mamba/in_proj", matmul=True),
    "%fusion.6": _op("backward", "GatedMLP", "l0_mlp", _AGAIN + "GatedMLP/l0_mlp/jit(silu)", True),
    "%fusion.7": _op("optimizer", None, None, "tau_step/solver_update"),
}
#: seconds over TWO traced rounds
DEVICE_OPS = [("%fusion.1", 0.020), ("%fusion.2", 0.022), ("%ssd.1", 0.030),
              ("%fusion.3", 0.004), ("%fusion.4", 0.006), ("%fusion.5", 0.040),
              ("%fusion.6", 0.002), ("%fusion.7", 0.001)]
RECOMPUTE = {"mlp_pre": {"maker": "mlp_pre", "step_bodies": 2, "forward": 2,
                         "backward": 0, "kept_bytes": 1024}}


def _run(sm, monkeypatch, ops=OPS, device_ops=DEVICE_OPS, trace=True):
    monkeypatch.setattr(sm, "_reports", {sm.PROGRAM: (
        {"ops": ops, "recompute": RECOMPUTE}, 0.5)})
    monkeypatch.setattr(sm, "_joined", {})
    return types.SimpleNamespace(
        ctx=types.SimpleNamespace(load=load), notes={},
        trace={"rounds": 2, "device_ops": list(device_ops)} if trace else None)


def test_the_two_readers_by_hand(monkeypatch):
    sm = load("scope_math.py")
    run = _run(sm, monkeypatch)
    total = reader("step_recompute_ms").read(run)
    products = reader("step_recompute_matmul_ms").read(run)
    assert total == pytest.approx(11 + 15 + 2 + 3 + 1)
    assert products == pytest.approx(11 + 15), "the product and the kernel call"
    assert 0 < products <= total <= sm.phase_ms(run, "backward") == pytest.approx(52.0)
    assert sm.phase_ms(run, "forward") == pytest.approx(10.0), "phases read what they read"


@pytest.mark.parametrize("metric", NEW)
def test_a_report_without_the_key_reads_nothing_not_zero(metric, monkeypatch):
    """The parent's program: no op carries `recomputed`, and a sum over
    nothing would be 0 ms made again -- the readers return None, as they do
    without a trace."""
    sm = load("scope_math.py")
    bare = {n: {k: v for k, v in op.items() if k != "recomputed"}
            for n, op in OPS.items()}
    run = _run(sm, monkeypatch, ops=bare)
    assert reader(metric).read(run) is None and run.notes == {}
    assert reader(metric).read(_run(sm, monkeypatch, trace=False)) is None
    # ... and a program with the key whose blocks make nothing again reads 0
    none = {n: dict(op, recomputed=False) for n, op in OPS.items()}
    assert reader(metric).read(_run(sm, monkeypatch, ops=none)) == 0


def test_the_note_holds_the_table_and_what_is_kept(monkeypatch):
    sm = load("scope_math.py")
    run = _run(sm, monkeypatch)
    first = reader("step_recompute_ms")
    total = first.read(run)
    table = run.notes["recompute_by_layer_ms"]
    assert table == [
        ["Mamba2/l0_mamba", "ssd", pytest.approx(15.0), pytest.approx(2.0)],
        ["Mamba2/l0_mamba", "in_proj", pytest.approx(11.0), 0.0],
        ["RMSNorm/l0_norm", "", 0.0, pytest.approx(3.0)],
        ["GatedMLP/l0_mlp", "", 0.0, pytest.approx(1.0)],   # `jit(silu)` is no scope
        ["other", "", 0.0, 0.0]]
    assert sum(p + r for *_, p, r in table) == pytest.approx(total)
    assert run.notes["recompute"] == {
        "mlp_pre": {"forward": 2, "backward": 0, "kept_bytes": 1024}}
    # the same by layer type, every row: no `other`
    assert run.notes["recompute_by_type_ms"] == [
        ["Mamba2", *table[0][1:]], ["Mamba2", *table[1][1:]],
        ["RMSNorm", *table[2][1:]], ["GatedMLP", *table[3][1:]]]
    # more layers than rows: the longest `ROWS` and the remainder in `other`
    many = {f"%f.{i}": _op("backward", "RMSNorm", f"l{i}_norm",
                           _AGAIN + f"RMSNorm/l{i}_norm", True)
            for i in range(first.ROWS + 5)}
    run = _run(sm, monkeypatch, ops=many,
               device_ops=[(n, 0.002 * (i + 1)) for i, n in enumerate(many)])
    total = first.read(run)
    table = run.notes["recompute_by_layer_ms"]
    assert len(table) == first.ROWS + 1 and table[0][0] == f"RMSNorm/l{first.ROWS + 4}_norm"
    assert table[-1] == ["other", "", 0.0, pytest.approx(1 + 2 + 3 + 4 + 5)]
    assert sum(p + r for *_, p, r in table) == pytest.approx(total)
    assert run.notes["recompute_by_type_ms"] == [
        ["RMSNorm", "", 0.0, pytest.approx(total)]]


@pytest.mark.parametrize("metric", NEW)
def test_the_entry_is_in_the_benchmark_by_name(metric):
    bench = RUN.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = {m["name"]: m for m in bench["per_layer"]}[metric]
    assert entry == {"name": metric, "unit": "ms", "better": "lower",
                     "source": "device_trace", "layer": "model / solver",
                     "moves": "train_round_rate", "workloads": CELLS}
    backward = {m["name"]: m for m in bench["per_layer"]}["step_backward_ms"]
    assert set(CELLS) <= set(backward["workloads"]), "a part of what those cells report"
    assert os.path.isfile(os.path.join(BENCH, "readers", metric + ".py"))
    assert callable(reader(metric).read)
