"""Where a set-up goes: `setup_s` (process start to the window's opening stamp)
put beside what the program recorded of its own start-up -- the kept spans of
the steps that run once a build (`sparknet_tpu.obs.trace.startup_spans()`) and
the compile log (`sparknet_tpu.utils.compile_cache.compile_log()`: one entry
an executable built or fetched, with its stages and the persistent cache's
verdict), both on `time.perf_counter()`, the clock of `ctx.t0`.

"Before the window" is `ctx.t0 <= t < ctx.t0 + setup_s`: a span by its start,
an entry of the log by its end (one that closes later compiled inside the
window). The seven `setup_*` readers are the program's own sums over those
(`sparknet_tpu.obs.device.startup_sums`, what its `start-up:` line tells);
`account()` is the arithmetic on top: the cut, the union, the rest. One `{"note": "startup", ...}` line a run prints every span
and entry it summed, the benchmark's own phases with what the record covers
of each, the seconds the phases put on executing rounds, and `unaccounted_s`.

Nothing to read from a program that keeps no such record (`of_run` -> None).

By hand, for a cell whose entries do not list the `setup_*` metrics yet:

    python3 benchmark/startup_account.py --workload <cell> --seed <n> --seconds <s>

is `run.py`'s run of that cell with the `startup` note among its lines (`main()`
goes when every cell lists the metrics: ROADMAP S12).
"""
from __future__ import annotations

import json
import os
import statistics
import sys


def covered(intervals: list, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] that lie inside any of `intervals` (a union: a
    compile inside a span counts once)."""
    total, end = 0.0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        if b > max(a, end):
            total += b - max(a, end)
            end = b
    return total


def account(sums, spans: list, log: list, t0: float, setup_s: float,
            phases: list, rounds_before: int, round_s) -> dict:
    """The arithmetic alone. `sums`: the program's own sums of a start-up
    (`sparknet_tpu.obs.device.startup_sums`: the ones its `start-up:` line
    tells), over what `spans` and `log`, its two records, hold before the
    window; `t0`, `setup_s`: process start and the set-up's length; `phases`:
    the benchmark's own milestones [(name, seconds since `t0`)];
    `rounds_before` rounds ran before the window, a steady one taking
    `round_s` seconds."""
    cut = t0 + setup_s
    spans = [s for s in spans if t0 <= s["t0"] < cut]
    log = [e for e in log if t0 <= e["t1"] < cut]
    got = sums(spans, log, t0)
    first = min((s["t0"] for s in spans), default=None)
    out = {
        "setup_s": setup_s,
        "setup_import_s": got["import_s"],
        "setup_build_s": got["build_s"],
        "setup_state_s": got["state_s"] + got["restore_s"],
        "setup_round_trace_s": got["round"]["trace_s"] + got["round"]["lower_s"],
        "setup_round_backend_s": got["round"]["backend_s"],
        "setup_other_compile_s": got["other"]["seconds"],
        "setup_cache_misses": got["cache_misses"]}
    # the union of what the record covers: the time before the first kept
    # span (imports), every outermost span, every entry from t0 to t1
    top = [s for s in spans if s["parent"] is None]
    recorded = ([(t0, first)] if first is not None else []) \
        + [(s["t0"], s["t1"]) for s in top] + [(e["t0"], e["t1"]) for e in log]
    out["phases"], at = [], 0.0
    for name, end in phases:
        if end > setup_s + 0.05:  # the reference and after: not set-up
            break
        end = min(end, setup_s)  # "warmup" is noted just after the stamp
        got = covered(recorded, t0 + at, t0 + end)
        out["phases"].append({"name": name, "seconds": end - at,
                              "recorded_s": got, "rest_s": end - at - got})
        at = end
    out["rounds_before"] = rounds_before
    out["rounds_s"] = None if round_s is None else rounds_before * round_s
    out["recorded_s"] = covered(recorded, t0, cut)
    # what neither the record nor the rounds' own time explains; a little
    # under zero where the host compiled the next program while the device
    # ran a round
    out["unaccounted_s"] = setup_s - out["recorded_s"] - (out["rounds_s"] or 0.0)
    out["spans"] = [{"name": s["name"], "at_s": s["t0"] - t0,
                     "seconds": s["t1"] - s["t0"], "parent": s["parent"],
                     "id": s["id"]}
                    for s in sorted(spans, key=lambda s: (s["t0"], -s["t1"]))]
    keep = ("what", "trace_s", "lower_s", "backend_s", "cache", "retrieval_s",
            "saved_s", "step", "thread")
    out["compiles"] = [{"at_s": e["t0"] - t0, "seconds": e["t1"] - e["t0"],
                        **{k: e[k] for k in keep if k in e}} for e in log]
    return out


def of_run(run):
    """`account()` of one run, or None for a program without the records;
    prints the run's `startup` note the first time it is asked (and leaves
    `startup_unaccounted_s` among the run's notes: the mark that it has)."""
    try:
        from sparknet_tpu.obs.device import startup_sums
        from sparknet_tpu.obs.trace import startup_spans
        from sparknet_tpu.utils.compile_cache import compile_log
    except ImportError:  # a program from before it kept them
        return None
    gaps = [b - a for a, b in zip(run.round_done_s, run.round_done_s[1:])]
    before = int(run.ctx.traffic.get("warmup_rounds", 0)) + any(
        name == "check_round" for name, _ in run.ctx.phases)
    out = account(startup_sums, startup_spans(), compile_log(), run.ctx.t0,
                  run.setup_s, run.ctx.phases, before,
                  statistics.median(gaps) if gaps else None)
    if "startup_unaccounted_s" not in run.notes:
        run.notes["startup_unaccounted_s"] = out["unaccounted_s"]
        print(json.dumps({"note": "startup", **out}), flush=True)
    return out


def read(run, key: str):
    out = of_run(run)
    return None if out is None else out[key]


def main(argv=None) -> int:
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run as bench  # its `_T0` is this process's start, near enough
    args = sys.argv[1:] if argv is None else list(argv)
    _, cell, _ = bench.resolve(os.path.dirname(here),
                               args[args.index("--workload") + 1])
    traffic = bench.load_json(os.path.join(here, "traffic",
                                           cell["traffic"] + ".json"))
    driver = bench.load_module(os.path.join(here, "drivers",
                                            traffic["driver"] + ".py"))
    drive = driver.run

    def run_and_note(ctx):
        run = drive(ctx)
        of_run(run)
        return run

    driver.run = run_and_note  # `run_cell` finds the loaded module again
    return bench.main(args)


if __name__ == "__main__":
    sys.exit(main())
