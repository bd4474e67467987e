"""The traced window's device ops joined with the round program's own account
of itself: `run.trace["device_ops"]` (every op of the window by instruction
name, seconds, mean over the chips) against
`sparknet_tpu.obs.device.program_report("train_round")["ops"]` (the same
names with the scope, the part of the step and the layer each belongs to,
parsed by the program from its compiled text).

`joined(run)` is None when there is no trace, when the program has no report
to give (a checkout from before it had one), or when more than
`MAX_UNMATCHED` of the window's op time carries a name the report does not
hold. The names it does not hold, and their time, go into one note line: the
benchmark's own stack-making program (about 1 %) is expected there and is
not the program's to name. `device_ops` is keyed by instruction name across
programs, so an op of another program that shares a name with one of the
round is counted with it.
"""
from __future__ import annotations

import json
import time

MAX_UNMATCHED = 0.03
PROGRAM = "train_round"

_reports: dict = {}   # PROGRAM -> (report | None, seconds the call took)
_joined: dict = {}    # id(run) -> joined(run)


def report():
    """The program's report, asked for once a process, and the seconds the
    call took (after the window: a compile-cache hit or a second compile)."""
    if PROGRAM not in _reports:
        t0 = time.perf_counter()
        try:
            from sparknet_tpu.obs.device import program_report
            rep = program_report(PROGRAM)
        except ImportError:  # a program that has no such account of itself
            rep = None
        _reports[PROGRAM] = (rep, time.perf_counter() - t0)
    return _reports[PROGRAM]


def join(device_ops: list, ops: dict, rounds: int) -> dict:
    """The arithmetic alone. `device_ops`: [(name, seconds over `rounds`
    traced rounds)]; `ops`: the report's. Returns {"matched": [(name, ms a
    round, the report's entry)], "unmatched": [(name, ms a round)],
    "unmatched_share": of the window's op time, "ok": within
    MAX_UNMATCHED}."""
    matched, unmatched = [], []
    for name, seconds in device_ops:
        ms = 1e3 * seconds / rounds
        if name in ops:
            matched.append((name, ms, ops[name]))
        else:
            unmatched.append((name, ms))
    total = sum(ms for _, ms, _ in matched) + sum(ms for _, ms in unmatched)
    share = sum(ms for _, ms in unmatched) / total if total else 1.0
    return {"matched": matched, "unmatched": unmatched,
            "unmatched_share": share, "ok": share <= MAX_UNMATCHED}


def joined(run):
    """`join` of a run's traced window, or None (module docstring); prints
    the note line once a run."""
    if id(run) in _joined:
        return _joined[id(run)]
    out = None
    if run.trace is not None and run.trace.get("rounds"):
        rep, seconds = report()
        if rep is not None:
            j = join(run.trace["device_ops"], rep["ops"],
                     run.trace["rounds"])
            print(json.dumps({
                "note": "scope_join", "program_report_s": seconds,
                "matched_ops": len(j["matched"]),
                "unmatched_share": j["unmatched_share"],
                "unmatched_ms_per_round": [[n, ms] for n, ms in sorted(
                    j["unmatched"], key=lambda kv: -kv[1])[:12]],
                "longest_ops_ms_per_round": [
                    [n, ms, op["phase"], f'{op["layer_type"]}/{op["layer"]}']
                    for n, ms, op in sorted(
                        j["matched"], key=lambda m: -m[1])[:12]],
                "by_layer_ms_per_round": by_layer(j)}), flush=True)
            out = j if j["ok"] else None
    _joined[id(run)] = out
    return out


def by_layer(j: dict) -> list:
    """[(phase, layer type / layer, ms a round)], longest first: PERF.md's
    table of where the device's time goes, by name."""
    total: dict = {}
    for _, ms, op in j["matched"]:
        key = (op["phase"], f'{op["layer_type"]}/{op["layer"]}'
               if op["layer"] else op["scope"].rsplit("/", 1)[-1] or "-")
        total[key] = total.get(key, 0.0) + ms
    return [[p, l, ms] for (p, l), ms in
            sorted(total.items(), key=lambda kv: -kv[1])[:40]]


def sum_ms(run, keep):
    """Milliseconds a traced round of the matched ops whose report entry
    `keep(entry)` accepts, or None where `joined` is None."""
    j = joined(run)
    if j is None:
        return None
    return sum(ms for _, ms, op in j["matched"] if keep(op))


def phase_ms(run, phase: str):
    return sum_ms(run, lambda op: op["phase"] == phase)


def layer_type_ms(run, *layer_types: str):
    """Both passes of the layers of these types."""
    return sum_ms(run, lambda op: op["layer_type"] in layer_types)


def memory_bytes(run, key: str):
    """One field of the report's memory analysis (bytes a device), in a
    traced run (the report is never asked for in an untraced one)."""
    if run.trace is None:
        return None
    rep, _ = report()
    return None if rep is None else rep["memory"].get(key)
