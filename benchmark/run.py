"""The benchmark's one command: one process, one cell, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell, its configuration, its traffic mix and its metrics
are entries of `BENCHMARK.json`; what belongs to one of them is a file of its
own that this harness finds by name --

    configs/<config>.json            the sizes as run, and its `reference`
    configs/<config>.reference.py    the plain reference and `correct`'s limits
    traffic/<traffic>.json           the mix's parameters, and its `driver`
    drivers/<driver>.py              `run(ctx) -> Run`: one kind of traffic
    readers/<metric>.py              `read(run) -> value | None`: one metric

-- and holds no list of cells, metrics or models. Notes go to earlier lines
of stdout, one JSON object each; the contract's result object is the last.
"""
from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, as near as Python lets us read it

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """A benchmark file as a module, by path (names hold `-` and `.`)."""
    path = os.path.abspath(path)
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path)
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def note(kind: str, **kv) -> None:
    print(json.dumps({"note": kind, **kv}), flush=True)


@dataclasses.dataclass
class Ctx:
    """What a driver is given."""
    root: str                 # the checkout (holds BENCHMARK.json)
    bench: str                # the benchmark's directory
    cell: dict                # the `workloads` entry
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<traffic>.json
    reference: object         # configs/<config>.reference.py, loaded
    seed: int
    seconds: float
    trace: bool
    t0: float                 # process start on time.perf_counter()
    tmp: str                  # a scratch directory of this run, removed after
    phases: list = dataclasses.field(default_factory=list)

    def phase(self, name: str, **kv) -> None:
        """A milestone of the run, noted at once: seconds since process
        start and the host's peak resident memory so far."""
        import resource
        self.phases.append((name, round(time.perf_counter() - self.t0, 3)))
        note("phase", name=name, at_s=self.phases[-1][1], host_peak_rss_gb=round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2), **kv)

    def load(self, name: str):
        """A file of the benchmark's directory as a module."""
        return load_module(os.path.join(self.bench, name))

    def result(self, **kw) -> "Run":
        return Run(ctx=self, **kw)


@dataclasses.dataclass
class Run:
    """What a driver hands back; the readers' one argument."""
    ctx: Ctx
    setup_s: float                      # process start to the first timed round
    round_done_s: list                  # completion times inside the window
    samples_per_round_per_chip: float
    losses: list                        # the window's round losses
    checks: list                        # compare.py check dicts
    compiles_in_window: int
    device_kind: str = ""
    spans: dict = dataclasses.field(default_factory=dict)   # name -> [(t0, t1)]
    loop_rows: list = dataclasses.field(default_factory=list)  # run_loop's metrics rows
    trace: dict | None = None           # trace_reduce.reduce(...), --trace 1 only
    notes: dict = dataclasses.field(default_factory=dict)


def resolve(root: str, workload: str) -> tuple:
    """(benchmark, cell, config entry) for a cell name, or SystemExit."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in BENCHMARK.json "
                         f"(has: {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return bench, cell, entry


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries this cell reports in this kind of run."""
    return [m for m in bench["per_layer" if trace else "end_to_end"]
            if cell in m.get("workloads", [cell])]


def require_chips(chips: int) -> None:
    """Exit non-zero with one line unless jax holds the chips the cell asks
    for: a CPU time is never printed under a device metric's name."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise SystemExit(
            f"run.py: this cell needs {chips} TPU chip(s) and jax found "
            f"{len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}); nothing was measured")


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             t0: float) -> dict:
    """Everything after the look for a chip: drive the cell, read its
    metrics, decide `correct`, and return the result object."""
    import shutil
    import tempfile

    import jax

    bench_dir = os.path.join(root, "benchmark")
    bench, cell, entry = resolve(root, workload)
    config = load_json(os.path.join(root, entry["file"]))
    traffic = load_json(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json"))
    tmp = tempfile.mkdtemp(prefix="bench-")  # under $TMPDIR, the run's own
    try:
        ctx = Ctx(root=root, bench=bench_dir, cell=cell, config=config,
                  traffic=traffic,
                  reference=load_module(os.path.join(root, config["reference"])),
                  seed=seed, seconds=seconds, trace=trace, t0=t0, tmp=tmp)
        driver = load_module(os.path.join(bench_dir, "drivers",
                                          traffic["driver"] + ".py"))
        run = driver.run(ctx)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        reader = load_module(os.path.join(bench_dir, "readers", m["name"] + ".py"))
        value = reader.read(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    lo, hi = (run.round_done_s[0], run.round_done_s[-1]) if run.round_done_s else (0, 0)
    spans_ms = {}
    for name, ts in run.spans.items():  # the benchmark's host spans, in the window
        d = [1e3 * (b - a) for a, b in ts if lo <= a <= hi]
        if d:
            spans_ms[name] = {"n": len(d), "mean": sum(d) / len(d), "max": max(d)}
    math = ctx.load("metric_math.py")
    gaps = sorted(math.intervals(run.round_done_s))
    note("run", rounds=len(run.losses), host_spans_ms=spans_ms,
         round_interval_ms={"min": 1e3 * gaps[0], "median": 1e3 * gaps[len(gaps) // 2],
                            "max": 1e3 * gaps[-1]} if gaps else None,
         window_mean_rate=math.window_rate(run.round_done_s,
                                           run.samples_per_round_per_chip),
         **run.notes)
    for c in run.checks:  # each number compared, beside its limit
        note("check", config=cell["config"], **c)
    failed = sum(1 for v in run.losses if not v == v or abs(v) == float("inf"))
    if run.compiles_in_window:
        failed = len(run.losses)  # a compile inside the window spoils them all
    devices = jax.devices()[:cell["chips"]]
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                  for d in devices)}
    result = {"correct": bool(run.checks) and all(c["ok"] for c in run.checks),
              "attempted": len(run.losses), "failed": failed,
              "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": [[k, v] for k, v in run.trace["device_ops"][:10]],
            "idle_gaps": [[k, v] for k, v in run.trace["idle_gaps"][:10]]}
    return result


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    root = os.path.dirname(HERE)
    sys.path.insert(0, root)  # the system under test lives beside us
    _, cell, _ = resolve(root, args.workload)
    # the compile cache: where the machine says, else a fixed place inside
    # the checkout (the program's own rule, utils/compile_cache.py)
    from sparknet_tpu.utils.compile_cache import init_compile_cache
    require_chips(cell["chips"])
    note("start", workload=args.workload, seed=args.seed, seconds=args.seconds,
         trace=args.trace, compile_cache=init_compile_cache(
             os.path.join(root, ".cache", "jax")
             if "JAX_COMPILATION_CACHE_DIR" not in os.environ else None))
    result = run_cell(root, args.workload, args.seed, args.seconds,
                      bool(args.trace), _T0)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
