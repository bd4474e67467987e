"""The controls of `correct` for a token cell whose rows hold several
documents, read on the chip at the cell's own size (not part of a benchmark
run; `tests/benchmark/test_packed_round.py` keeps them at a size a test run
can hold).

    python benchmark/packed_control.py --workload <cell> --seeds 1 2 3 [--control-seeds N]

For every seed, in one process: the program's round 0 through the window's
own call, the plain reference over the same ids and document ids, and -- for
the first `--control-seeds` seeds -- the reference put in the program's place
twice: computed in the configuration's CONTROL_PRECISION, and with every
mixer given one document a row (`leak=True`: the taps, the state and the keys
run across every boundary). Prints, per seed and side, every number `correct`
compares: the readings the limits in `configs/<config>.reference.py` are set
from, and the boundaries every mixer counted beside those the traffic drew.
`ssm_control.py` does the like for the state-space cell whose rows are one
document.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import run as harness


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--control-seeds", type=int, default=2)
    args = p.parse_args(argv)
    root = os.path.dirname(harness.HERE)
    sys.path.insert(0, root)
    bench, cell, entry = harness.resolve(root, args.workload)
    from sparknet_tpu.utils.compile_cache import init_compile_cache
    harness.require_chips(cell["chips"])
    init_compile_cache(os.path.join(root, ".cache", "jax")
                       if "JAX_COMPILATION_CACHE_DIR" not in os.environ else None)
    config = harness.load_json(os.path.join(root, entry["file"]))
    bench_dir = os.path.join(root, "benchmark")
    traffic = harness.load_json(os.path.join(bench_dir, "traffic",
                                             cell["traffic"] + ".json"))
    ctx = harness.Ctx(
        root=root, bench=bench_dir, cell=cell, config=config, traffic=traffic,
        reference=harness.load_module(os.path.join(root, config["reference"])),
        seed=args.seeds[0], seconds=0.0, trace=False, t0=time.perf_counter(),
        tmp=tempfile.mkdtemp(prefix="bench-control-"))
    driver = ctx.load(os.path.join("drivers", traffic["driver"] + ".py"))
    compare = ctx.load("compare.py")
    prog = driver.program(ctx)
    ref, loose = ctx.reference, {k: float("inf") for k in ctx.reference.LIMITS}
    for i, seed in enumerate(args.seeds):
        ctx.seed = seed
        make_stack, rows = prog.stack_makers()
        program = prog.check_round(make_stack(0))
        t0 = time.perf_counter()
        reference = prog.reference_round(rows)
        ref_s = time.perf_counter() - t0
        sides = {"program": program}
        if i < args.control_seeds:
            sides[ref.CONTROL_PRECISION] = prog.reference_round(
                rows, ref.CONTROL_PRECISION)
            sides["leak"] = prog.reference_round(rows, leak=True)
        for side, got in sides.items():
            checks = compare.first_round_checks(got, reference, loose)
            print(json.dumps({
                "seed": seed, "side": side, "reference_s": round(ref_s, 2),
                **{c["name"]: c["value"] for c in checks},
                "leaves": {c["name"]: c.get("leaf") for c in checks
                           if "leaf" in c}}), flush=True)
        print(json.dumps({
            "seed": seed, "boundaries_drawn": prog.boundaries_drawn(rows),
            "check_round_counters": {b: [float(x) for x in v] for b, v in
                                     program["counters"].items()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
