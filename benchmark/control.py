"""The control of `correct`, read on the chip at a cell's own size (not part of
a benchmark run; `tests/benchmark` keeps it at a size a test run can hold).

    python benchmark/control.py --workload <cell> --seeds 1 2 3 ... [--control-seeds N]

For every seed, in one process: the program's round 0 through the window's
own call, the plain reference over the same rows, and -- for the first
`--control-seeds` seeds -- the reference put in the program's place and
computed in each lower precision (int8, fp8). Prints, per seed, every number
`correct` compares, for the program and for each control: the readings the
limits in `configs/<config>.reference.py` are set from. Device-round traffic
only (the cached loop runs the same compiled round on rows of the same scale).
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
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--precisions", nargs="+", default=["int8", "fp8"])
    p.add_argument("--lr-scales", type=float, nargs="+", default=None,
                   help="check_lr_scale values to read at (default: the "
                        "configuration's own)")
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
    ctx = harness.Ctx(
        root=root, bench=bench_dir, cell=cell, config=config,
        traffic=harness.load_json(os.path.join(bench_dir, "traffic",
                                               cell["traffic"] + ".json")),
        reference=harness.load_module(os.path.join(root, config["reference"])),
        seed=args.seeds[0], seconds=0.0, trace=False, t0=time.perf_counter(),
        tmp=tempfile.mkdtemp(prefix="bench-control-"))
    common, compare = ctx.load("common.py"), ctx.load("compare.py")
    prog = common.Program(ctx)
    loose = {k: float("inf") for k in ctx.reference.LIMITS}
    scales = args.lr_scales or [prog.check_lr_scale]
    for i, (seed, scale) in enumerate((s, k) for s in args.seeds
                                      for k in scales):
        i //= len(scales)
        prog.check_lr_scale = scale
        ctx.seed = seed
        prog.params0 = ctx.reference.init_params(seed, prog.crop, prog.n_classes)
        make_stack, rows = prog.stack_makers()
        program = prog.check_round(make_stack(0))
        t0 = time.perf_counter()
        reference = prog.reference_round(rows)
        ref_s = time.perf_counter() - t0
        sides = {"program": program}
        if i < args.control_seeds:
            for precision in args.precisions:
                sides[precision] = prog.reference_round(rows, precision)
        for side, got in sides.items():
            checks = compare.first_round_checks(got, reference, loose)
            print(json.dumps({
                "seed": seed, "lr_scale": scale, "side": side,
                "reference_s": round(ref_s, 2),
                "replica_spread": got.get("replica_spread"),
                **{c["name"]: c["value"] for c in checks},
                "leaves": {c["name"]: c.get("leaf") for c in checks
                           if "leaf" in c}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
