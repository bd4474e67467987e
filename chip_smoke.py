#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path ONCE, through the entry points a user calls, at the full
width of CaffeNet (227², 1000 classes, batch 256 a chip, the ImageNet app's
recipe), with random weights made from --seed:

  device    what jax found, versions, where the compile cache is
  kernels   the Pallas LRN (forward + gradient, norm1/norm2, bf16 and f32),
            and the rows kernel at a serve bucket, compiled and executed,
            each checked against its XLA oracle
  train     `apps.train_loop.train()` fed by the real ingest path (synthetic
            JPEG tar shards -> ShardedTarLoader -> StreamingRoundSource ->
            ImagePreprocessor 256->227): rounds, evals, a checkpoint save, a
            bit-equal restore and a resumed round in bf16, then f32 rounds
  serve     an InferenceServer (outputs prob + fc7, buckets 1 and 8) behind
            the binary front door on loopback, a dozen requests through the
            repo's own client, each reply checked against JaxNet.forward
  profile   a profiled round through `train()`, the .xplane.pb read back

    python chip_smoke.py                one chip: every phase above
    python chip_smoke.py --four-chips   the τ-averaging round across four
                                        chips against four one-chip rounds,
                                        and a (data=2, model=2) round — no
                                        other phase
    python chip_smoke.py --tiny         the CPU rehearsal: every phase at crop
                                        67 / 16 classes / batch 16 with the
                                        kernels under the Pallas interpreter

Every phase prints one JSON object on its own line. The LAST line of stdout is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}` only
when jax's first device is a TPU and every phase passed; otherwise it says
`"ok": false` with the platform really found and the exit code is non-zero.
Off the chip the full-size run stops after the device phase: a CPU run gives
no device number. One process, no child that needs the chip; figures printed
here are information about one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import tempfile
import time
import traceback
import urllib.request
from types import SimpleNamespace

import sparknet_tpu  # noqa: F401 — without the program beside it, this
#                      script is nothing: fail here, before any output

FULL = SimpleNamespace(
    tiny=False, crop=227, size=256, n_classes=1000, batch=256, tau=5,
    shards=4, per_shard=384, rounds=5, f32_rounds=3,
    norm1=(256, 27, 27, 96), norm2=(256, 13, 13, 256), rows_batch=8)
TINY = SimpleNamespace(
    tiny=True, crop=67, size=72, n_classes=16, batch=16, tau=2,
    shards=2, per_shard=48, rounds=4, f32_rounds=3,
    norm1=(128, 7, 7, 32), norm2=(128, 3, 3, 64), rows_batch=8)

#: normalised max error |got - want|_max / |want|_max a kernel may show
#: against its oracle. bf16: a few ulps of the dtype the result is rounded
#: to. f32: the chip's transcendentals are approximate — the kernel's
#: rsqrt/sqrt and the oracle's exp/log agreed to 6.5e-5 there (PR 21's chip
#: run; 1.6e-7 under the interpreter), so 2^-12 and not a few f32 ulps
KERNEL_TOL = {"float32": 2.0 ** -12, "bfloat16": 2.0 ** -6}
#: serve replies vs a direct forward: the same f32 program at another batch
#: size (HIGHEST-precision matmuls), so tiling noise only
SERVE_RTOL, SERVE_ATOL = 1e-3, 1e-5
#: four-chip parameters vs the average of four one-chip rounds, per layer,
#: as a share of how far the round moved that layer: bf16 activations give
#: gradients good to ~2^-8, and the two programs may fuse differently
MULTICHIP_TOL = 2.0 ** -5


# -- helpers -----------------------------------------------------------------

def _norm_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                  1e-30))


def _custom_calls(fn, *args) -> int:
    """Pallas kernels in the program as lowered for THIS backend — 0 means
    the portable path (or the interpreter) took over."""
    import jax
    return jax.jit(fn).lower(*args).as_text().count("tpu_custom_call")


def _block(tree):
    import jax
    return jax.block_until_ready(tree)


def _run_config(sz, seed: int, workdir: str, **over):
    """The ImageNet app's own RunConfig, cut only where --tiny says."""
    from sparknet_tpu.apps import imagenet_app
    cfg = dataclasses.replace(
        imagenet_app.default_config(), n_devices=1, seed=seed,
        workdir=workdir, crop=sz.crop, n_classes=sz.n_classes,
        local_batch=sz.batch, tau=sz.tau, ops_interpret=sz.tiny)
    return dataclasses.replace(cfg, **over)


def _spec(cfg):
    from sparknet_tpu.apps.train_loop import resolve_spec
    return resolve_spec(cfg)


def _round_rows(path: str) -> list:
    """The loop's per-round step-time breakdown rows (metrics JSONL), in
    round order."""
    keep = ("loss", "grad_norm", "images_per_sec_per_chip", "t_data_ms",
            "t_h2d_ms", "t_round_ms", "t_collect_ms", "t_collect_bg_ms",
            "t_ckpt_fetch_ms")
    with open(path) as f:
        records = [json.loads(ln) for ln in f if ln.strip()]
    return [{"round": r["step"], "logged_at_s": r["t"],
             **{k: r[k] for k in keep if k in r}}
            for r in records if "loss" in r]


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


# -- phase 1: device ---------------------------------------------------------

def phase_device(ctx) -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib

    from sparknet_tpu.utils.compile_cache import init_compile_cache

    devs = jax.devices()
    ctx.device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    if ctx.four and len(devs) != 4:
        raise RuntimeError(f"--four-chips needs four devices, jax found "
                           f"{len(devs)}")
    return {**ctx.device, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu,
            "compile_cache_dir": init_compile_cache(), "seed": ctx.seed,
            "size": "tiny" if ctx.sz.tiny else "full"}


# -- phase 2: kernels --------------------------------------------------------

def phase_kernels(ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from sparknet_tpu.ops.lrn import _lrn_xla
    from sparknet_tpu.ops.pallas_lrn import lrn_pallas

    sz, interp = ctx.sz, ctx.sz.tiny
    keys = iter(jax.random.split(jax.random.PRNGKey(ctx.seed), 32))
    cases = []

    def check(name, dtype, kernel, oracle, x, dy):
        """Forward + gradient of `kernel` against `oracle` on the same
        inputs, both jitted and executed on the default device."""
        def both(f):
            def run(x, dy):
                y, vjp = jax.vjp(f, x)
                return y, vjp(dy.astype(y.dtype))[0]
            return run
        n_calls = _custom_calls(both(kernel), x, dy)
        y, dx = _block(jax.jit(both(kernel))(x, dy))
        y0, dx0 = _block(jax.jit(both(oracle))(x, dy))
        case = {"case": name, "dtype": dtype, "shape": list(x.shape),
                "interpret": interp, "tpu_custom_calls": n_calls,
                "fwd_err": _norm_err(y, y0), "grad_err": _norm_err(dx, dx0)}
        cases.append(case)
        assert y.dtype == x.dtype and dx.dtype == x.dtype, case
        assert max(case["fwd_err"], case["grad_err"]) <= KERNEL_TOL[dtype], \
            case
        # a kernel that gave way to a portable path must not pass for one
        assert interp or n_calls >= 1, case

    def lrn_oracle(x):  # the "window" oracle, in f32 on the same input
        return _lrn_xla(x.astype(jnp.float32))

    def lrn_kernel(x):
        return lrn_pallas(x, interpret=interp)

    lrn_shapes = [("lrn-norm1", sz.norm1), ("lrn-norm2", sz.norm2),
                  # batch 8 is no multiple of 128 lanes: the rows kernel,
                  # what a serve bucket runs
                  ("lrn-rows-bucket8", (sz.rows_batch,) + sz.norm1[1:])]
    for name, shape in lrn_shapes:
        for dtype in ("bfloat16", "float32"):
            # post-ReLU scale: large enough that the normalizer matters
            x = (30.0 * jax.random.normal(next(keys), shape)).astype(dtype)
            dy = jax.random.normal(next(keys), shape).astype(dtype)
            check(name, dtype, lrn_kernel, lrn_oracle, x, dy)

    return {"interpret": interp, "cases": cases}


# -- phase 3: train ----------------------------------------------------------

def _ingest(ctx, corpus: str):
    """The ImageNet app's ingest path over a seeded synthetic corpus:
    (loader, mean image, held-out uint8 dataset)."""
    import numpy as np

    from sparknet_tpu.data import imagenet
    from sparknet_tpu.data.dataset import ArrayDataset
    from sparknet_tpu.data.streaming import streaming_sum_count

    sz = ctx.sz
    t0 = time.perf_counter()
    imagenet.write_synthetic_shards(
        corpus, n_shards=sz.shards, per_shard=sz.per_shard,
        n_classes=sz.n_classes, size=sz.size, seed=ctx.seed)
    t_write = time.perf_counter() - t0
    loader = imagenet.ShardedTarLoader(
        imagenet.list_shards(corpus, prefix="train."),
        imagenet.load_label_map(os.path.join(corpus, "train.txt")),
        height=sz.size, width=sz.size)
    total, count = streaming_sum_count(
        loader, workers=min(sz.shards, os.cpu_count() or 1))
    mean = (total / count).astype(np.float32)
    images, labels = loader.load_all(limit=sz.batch)
    test_ds = ArrayDataset({"data": images, "label": labels[:, None]})
    return loader, mean, test_ds, {"corpus_jpegs": count,
                                   "corpus_write_s": round(t_write, 2)}


def _preprocessors(cfg, mean):
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    from sparknet_tpu.schema import Field, Schema
    schema = Schema(Field("data", "float32", (cfg.crop, cfg.crop, 3)),
                    Field("label", "int32", (1,)))
    return [ImagePreprocessor(schema, mean_image=mean, crop=cfg.crop,
                              seed=cfg.seed, out_dtype=cfg.precision)
            for _ in range(2)]


def _train(ctx, cfg, loader, mean, test_ds, tag: str, hook=None):
    """One `train()` call over a fresh streaming source. Returns (final
    state, per-round rows, compile counts, the text log)."""
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.streaming import StreamingRoundSource
    from sparknet_tpu.utils.compile_cache import track_compiles
    from sparknet_tpu.utils.logger import Logger

    pp_train, pp_eval = _preprocessors(cfg, mean)
    log_path = os.path.join(cfg.workdir, f"{tag}.log")
    jsonl = os.path.join(cfg.workdir, f"{tag}.jsonl")
    logger = Logger(log_path, echo=False, jsonl_path=jsonl)
    source = StreamingRoundSource(loader, 1, cfg.local_batch, cfg.tau)
    try:
        with track_compiles() as tc:
            state = train(cfg, _spec(cfg), source, test_ds, logger=logger,
                          round_hook=hook, batch_transform=pp_train,
                          eval_transform=pp_eval)
    finally:
        source.close()
        logger.close()
    compiles = {"xla_compiles": tc.xla_compiles,
                "cache_hits": tc.cache_hits, "cache_misses": tc.cache_misses}
    with open(log_path) as f:
        return state, _round_rows(jsonl), compiles, f.read()


def _finite_losses(rows, n: int, what: str) -> None:
    import math
    assert len(rows) == n, f"{what}: {len(rows)} round rows, wanted {n}"
    bad = [r for r in rows if not math.isfinite(r["loss"])]
    assert not bad, f"{what}: nonfinite loss in {bad}"


def _timing(rows) -> dict:
    """The first round's dispatch (trace + compile, or a cache load) and
    the wall time from one round's log row to the next, data wait and all
    — information about this run, not a benchmark."""
    import statistics
    gaps = [b["logged_at_s"] - a["logged_at_s"]
            for a, b in zip(rows[1:], rows[2:])] or [float("nan")]
    return {"first_round_dispatch_s": round(rows[0]["t_round_ms"] / 1e3, 2),
            "steady_round_wall_s_median": round(statistics.median(gaps), 3)}


def phase_train(ctx) -> dict:
    import jax
    import numpy as np

    from sparknet_tpu.apps.train_loop import build_trainer, probe_value
    from sparknet_tpu.data import jpeg_plane
    from sparknet_tpu.parallel.mesh import fetch_global
    from sparknet_tpu.utils import checkpoint as ckpt

    sz = ctx.sz
    work = os.path.join(ctx.workdir, "train")
    os.makedirs(work)
    loader, mean, test_ds, out = _ingest(ctx, os.path.join(ctx.workdir,
                                                            "corpus"))
    ctx.ingest = (loader, mean, test_ds)
    out["decode_plane"] = "native" if jpeg_plane.available() else "pil"
    ck_dir = os.path.join(work, "ck")
    cfg = _run_config(sz, ctx.seed, work, max_rounds=sz.rounds,
                      eval_every=sz.rounds - 1, eval_batch=sz.batch,
                      checkpoint_dir=ck_dir, checkpoint_every=10 ** 6,
                      status_port=0)
    assert cfg.precision == "bfloat16" and cfg.health.enabled
    assert cfg.h2d_prefetch and cfg.donate_batches and cfg.fused_boundary \
        and cfg.collect_async, "the RunConfig defaults this smoke is about"

    # the program the loop will run, lowered: the Pallas LRN must be in it
    trainer = build_trainer(cfg, _spec(cfg))
    net = trainer.net
    state0 = trainer.init_state(jax.random.PRNGKey(cfg.seed))
    probe0 = float(probe_value(state0, net))
    placed = trainer.place_batches({
        "data": np.zeros((cfg.tau, cfg.local_batch, cfg.crop, cfg.crop, 3),
                         np.float32),
        "label": np.zeros((cfg.tau, cfg.local_batch, 1), np.int32)})
    rngs = jax.random.split(jax.random.PRNGKey(0), trainer.n_data)
    out["round_tpu_custom_calls"] = trainer._round.lower(
        state0, placed, rngs, np.float32(1.0)).as_text().count(
            "tpu_custom_call")
    out["ops_interpret"] = cfg.ops_interpret
    assert cfg.ops_interpret or out["round_tpu_custom_calls"] >= 4, out
    del trainer, state0, placed

    # bf16: rounds, two evals, the final checkpoint
    variants = []

    def scrape_variants(rnd, _state):
        """The loop's own /metrics: jit-cache entries of the compiled
        round. The round-0 entry is keyed on the freshly placed state and
        later ones on the round's own donated output — one executable, at
        most two fast-path keys; growth past that is a recompile."""
        host, port = cfg.status_address
        with urllib.request.urlopen(
                f"http://{host}:{port}/metrics", timeout=10) as r:
            m = re.search(r"^sparknet_train_round_compiled_variants\S* "
                          r"(\S+)$", r.read().decode(), re.M)
        variants.append(int(float(m.group(1))))

    state, rows, compiles, log = _train(ctx, cfg, loader, mean, test_ds,
                                        "bf16", hook=scrape_variants)
    _finite_losses(rows, sz.rounds, "bf16")
    out["bf16"] = {"rounds": rows, **_timing(rows), **compiles,
                   "evals": len(re.findall(r"test accuracy: ", log)),
                   "compiled_variants_by_round": variants}
    assert out["bf16"]["evals"] == 2, out["bf16"]
    assert max(variants) <= 2 and variants[-1] == variants[1], variants
    probe1 = float(probe_value(state, net))
    out["probe"] = {"initial": probe0, "after": probe1}
    assert probe1 != probe0, "the weights did not move"

    # the checkpoint the loop wrote, restored: bit-equal to the live state
    saved = fetch_global(state)
    restored, step, _extra = ckpt.restore(ck_dir, saved)
    assert step == sz.rounds, step
    pairs = list(zip(jax.tree.leaves(saved), jax.tree.leaves(restored)))
    assert all(a.dtype == b.dtype and a.tobytes() == np.asarray(b).tobytes()
               for a, b in pairs), "restored state differs from the saved one"
    out["checkpoint"] = {"step": step, "leaves": len(pairs),
                         "bytes": int(sum(a.nbytes for a, _ in pairs)),
                         "restore_bit_equal": True}
    del state, saved, restored, pairs

    # resume from it: one more round through a NEW trainer (its compile is
    # a persistent-cache hit, the warm figure)
    cfg_resume = dataclasses.replace(cfg, max_rounds=sz.rounds + 1,
                                     eval_every=0, status_port=None)
    _, rows, compiles, log = _train(ctx, cfg_resume, loader, mean, None,
                                    "bf16-resume")
    assert f"resumed from checkpoint round {sz.rounds}" in log, log[-2000:]
    _finite_losses(rows, 1, "bf16 resume")
    out["bf16_resume"] = {"rounds": rows, **compiles,
                          "first_round_dispatch_s":
                              round(rows[0]["t_round_ms"] / 1e3, 2)}
    out["peak_bytes_in_use_bf16"] = _peak_bytes()

    # the DEFAULT precision policy at published width
    cfg32 = _run_config(sz, ctx.seed, work, precision="float32",
                        max_rounds=sz.f32_rounds, eval_every=0)
    _, rows, compiles, _ = _train(ctx, cfg32, loader, mean, None, "f32")
    _finite_losses(rows, sz.f32_rounds, "f32")
    out["f32"] = {"rounds": rows, **_timing(rows), **compiles}
    out["peak_bytes_in_use"] = _peak_bytes()
    return out


# -- phase 4: serve ----------------------------------------------------------

def phase_serve(ctx) -> dict:
    import numpy as np

    from sparknet_tpu import precision
    from sparknet_tpu.net_api import JaxNet
    from sparknet_tpu.obs import reqtrace
    from sparknet_tpu.obs.device import compile_stats
    from sparknet_tpu.serve import (BinaryClient, BinaryFrontend,
                                    InferenceServer, ServeConfig)
    from sparknet_tpu.zoo import caffenet

    sz, buckets, outputs = ctx.sz, (1, 8), ("prob", "fc7")
    precision.set_policy("float32")  # train() left its own on this thread
    net = JaxNet(caffenet(batch=buckets[-1], crop=sz.crop,
                          n_classes=sz.n_classes), seed=ctx.seed)
    r = np.random.default_rng(ctx.seed)
    bursts = (1, 3, 8)  # a dozen requests, formed into both buckets
    examples = [r.standard_normal((sz.crop, sz.crop, 3)).astype(np.float32)
                for _ in range(sum(bursts))]
    before = compile_stats().get("serve_bucket", {})
    cfg = ServeConfig(model_name="caffenet", max_batch=buckets[-1],
                      buckets=buckets, outputs=outputs, max_wait_ms=50.0,
                      metrics_every_batches=0)
    replies = []
    with reqtrace.request_tracing(head_sample=1.0) as tracer, \
            InferenceServer(net, cfg) as srv:
        front = BinaryFrontend(srv, port=0)
        client = None
        try:
            client = BinaryClient(*front.address, timeout=600.0)
            it = iter(examples)
            for n in bursts:  # pipelined: n requests in flight at once
                rids = [client.submit({"data": next(it)}, model="caffenet")
                        for _ in range(n)]
                replies += [client.collect(rid) for rid in rids]
        finally:
            if client is not None:
                client.close()
            front.stop()
        status = srv.status()
        rows = tracer.drain_rows()
    assert len(replies) == len(examples)
    worst = 0.0
    for x, got in zip(examples, replies):
        want = net.forward({"data": x[None],
                            "label": np.zeros((1, 1), np.int32)},
                           blob_names=list(outputs))
        assert set(got) == set(outputs), set(got)
        for k in outputs:
            assert got[k].shape == want[k][0].shape, (k, got[k].shape)
            np.testing.assert_allclose(got[k], want[k][0], rtol=SERVE_RTOL,
                                       atol=SERVE_ATOL, err_msg=k)
            worst = max(worst, _norm_err(got[k], want[k][0]))
    assert status["bucket_compiles"] == len(buckets), status
    after = compile_stats()["serve_bucket"]
    request_rows = [x for x in rows if x["k"] == "r"]
    assert request_rows and request_rows[0]["stages"], rows[:3]
    return {"requests": len(replies), "bursts": list(bursts),
            "buckets": list(buckets), "outputs": list(outputs),
            "bucket_compiles": status["bucket_compiles"],
            "bucket_cache_hits": int(after["cache_hits"]
                                     - before.get("cache_hits", 0)),
            "bucket_cache_misses": int(after["cache_misses"]
                                       - before.get("cache_misses", 0)),
            "batches": status["batches"],
            "batch_size_hist": status["batch_size_hist"],
            "max_norm_err_vs_direct_forward": worst,
            "p50_ms": status["p50_ms"], "p99_ms": status["p99_ms"],
            "one_request_stage_ms": request_rows[-1]["stages"]}


# -- phase 5: profiler -------------------------------------------------------

def phase_profile(ctx) -> dict:
    """Two rounds through `train()` with RunConfig.profile_dir set: the loop
    runs `utils.profiling.maybe_trace` around the second (steady) one. The
    trace is read back with nothing but jax."""
    import glob

    import jax

    loader, mean, _ = ctx.ingest
    work = os.path.join(ctx.workdir, "profile")
    os.makedirs(work)
    cfg = _run_config(ctx.sz, ctx.seed, work, max_rounds=2, eval_every=0,
                      profile_dir=os.path.join(work, "trace"))
    _, rows, _, _ = _train(ctx, cfg, loader, mean, None, "profile")
    _finite_losses(rows, 2, "profiled run")
    files = glob.glob(os.path.join(cfg.profile_dir, "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    planes = {p.name: p for p in data.planes}
    # the chip's plane; the CPU rehearsal has only the host's
    want = "/device:TPU:0" if ctx.device["platform"] == "tpu" else "/host:CPU"
    assert want in planes, sorted(planes)
    lines = {ln.name: ln for ln in planes[want].lines}
    # the device plane's op line; the host plane has a line per thread
    picked = [lines["XLA Ops"]] if "XLA Ops" in lines else lines.values()
    ops: dict = {}
    for line in picked:
        for ev in line.events:
            ops[ev.name] = ops.get(ev.name, 0.0) + ev.duration_ns
    assert ops, f"no events on {want}: lines {sorted(lines)}"
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:5]
    return {"xplane_bytes": os.path.getsize(files[0]),
            "planes": sorted(planes), "plane": want,
            "lines": sorted(lines)[:12],
            "longest_ops_ms": [[n[:80], round(d / 1e6, 3)] for n, d in top]}


# -- four chips --------------------------------------------------------------

def _host_batches(ctx, n_examples: int) -> dict:
    """Seeded pixel-scale f32 round batches [tau, n_examples, ...]."""
    import numpy as np
    sz = ctx.sz
    r = np.random.default_rng(ctx.seed)
    data = r.integers(0, 256, (sz.tau, n_examples, sz.crop, sz.crop, 3),
                      dtype=np.uint8)
    return {"data": data.astype(np.float32) - 128.0,
            "label": r.integers(0, sz.n_classes, (sz.tau, n_examples, 1)
                                ).astype(np.int32)}


def _timed_rounds(trainer, state, host, rngs, lr, n: int = 3):
    """n more rounds, each on freshly placed batches (the round donates
    them), each waited for: per-round ms."""
    ms = []
    for _ in range(n):
        placed = _block(trainer.place_batches(host))
        t0 = time.perf_counter()
        state, loss, _ = trainer._round(state, placed, rngs, lr)
        _block(loss)
        ms.append(round((time.perf_counter() - t0) * 1e3, 2))
    return state, ms


def phase_four_chips(ctx) -> dict:
    """The paper's round — τ local steps on each chip's own batch, then the
    weight average — on a 4-chip data mesh, against the same four local
    trajectories run one at a time on a 1-chip mesh and averaged on the
    host. Dropout is in the trajectory, so `_round` is fed the rng rows
    directly: 1-chip run i gets the row device i had (bench.py's
    `_time_rounds` feeds `_round` the same way)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from sparknet_tpu import precision
    from sparknet_tpu.apps.train_loop import build_trainer
    from sparknet_tpu.parallel.mesh import (DATA_AXIS, make_mesh,
                                            place_global_state)

    sz, n = ctx.sz, 4
    work = os.path.join(ctx.workdir, "four")
    os.makedirs(work)
    cfg = _run_config(sz, ctx.seed, work, n_devices=n)
    spec = _spec(cfg)
    host = _host_batches(ctx, n * sz.batch)
    key = jax.random.PRNGKey(cfg.seed)
    rng_rows = jax.random.split(jax.random.PRNGKey(cfg.seed ^ 0xABCD), n)
    lr = jnp.asarray(1.0, jnp.float32)
    out: dict = {}

    def one_round(trainer, batches, rows):
        """(state after one round, loss, seconds incl. compile)."""
        state = trainer.init_state(key)
        t0 = time.perf_counter()
        state, loss, _health = trainer._round(
            state, trainer.place_batches(batches),
            place_global_state(rows, trainer.mesh, P(DATA_AXIS)), lr)
        loss = float(loss)
        return state, loss, time.perf_counter() - t0

    # -- the 4-chip round
    t4 = build_trainer(cfg, spec)
    host = precision.cast_host_inputs(host)  # the policy build_trainer set
    init = jax.tree.map(np.asarray, t4.net.init_params(key))
    state4, loss4, first4 = one_round(t4, host, rng_rows)
    assert np.isfinite(loss4), loss4
    leaves = (jax.tree.leaves(state4.params)
              + jax.tree.leaves(state4.momentum))
    ids = sorted({s.device.id for leaf in leaves
                  for s in leaf.addressable_shards})
    assert len(ids) == n and all(
        len({s.device.id for s in leaf.addressable_shards}) == n
        for leaf in leaves), f"state is not on {n} devices: {ids}"
    mem = [d.memory_stats() for d in t4.mesh.devices.flat]
    if not sz.tiny:  # the CPU backend keeps no allocator statistics
        assert all(m and m["bytes_in_use"] > 0 for m in mem), mem
    rows4 = jax.tree.map(np.asarray, state4.params)  # [n, ...] replica rows
    for leaf in jax.tree.leaves(rows4):
        assert all((leaf[i] == leaf[0]).all() for i in range(1, n)), \
            "replicas differ after the boundary pmean"
    avg4 = jax.tree.map(lambda x: x[0], rows4)
    state4, ms4 = _timed_rounds(
        t4, state4, host, place_global_state(rng_rows, t4.mesh,
                                             P(DATA_AXIS)), lr)
    out["four_chip"] = {"loss": loss4, "first_round_s": round(first4, 2),
                        "round_ms": ms4, "device_ids": ids,
                        "bytes_in_use": [m and m["bytes_in_use"]
                                         for m in mem]}
    del state4, t4

    # -- what it is compared with: four 1-chip rounds, one per batch slice
    t1 = build_trainer(dataclasses.replace(cfg, n_devices=1), spec)
    per_slice, losses1, ms1 = [], [], None
    for i in range(n):
        sl = {k: v[:, i * sz.batch:(i + 1) * sz.batch]
              for k, v in host.items()}
        state1, loss1, first1 = one_round(t1, sl, rng_rows[i:i + 1])
        per_slice.append(jax.tree.map(lambda x: np.asarray(x)[0],
                                      state1.params))
        losses1.append(loss1)
        if i == 0:
            out["one_chip"] = {"first_round_s": round(first1, 2)}
            state1, ms1 = _timed_rounds(
                t1, state1, sl, place_global_state(
                    rng_rows[:1], t1.mesh, P(DATA_AXIS)), lr)
        del state1
    out["one_chip"].update(round_ms=ms1, losses=losses1)
    avg1 = jax.tree.map(lambda *xs: np.mean(np.stack(xs), axis=0,
                                            dtype=np.float32), *per_slice)
    assert abs(np.mean(losses1) - loss4) <= 1e-2 * abs(loss4), (losses1,
                                                                loss4)
    # per layer: the two averages' distance as a share of the round's move
    errs = {}
    for lname, lp in avg4.items():
        for pname, p4 in lp.items():
            moved = np.max(np.abs(p4 - init[lname][pname]))
            diff = np.max(np.abs(p4 - avg1[lname][pname]))
            errs[f"{lname}/{pname}"] = float(diff / max(moved, 1e-30))
            assert moved > 0, f"{lname}/{pname} did not move"
    out["match"] = {"tolerance": MULTICHIP_TOL,
                    "worst": max(errs.values()),
                    "worst_layer": max(errs, key=errs.get),
                    "by_layer": {k: round(v, 6) for k, v in errs.items()}}
    assert out["match"]["worst"] <= MULTICHIP_TOL, out["match"]
    del t1

    # -- (data=2, model=2): fc layers column-sharded over the model axis
    tp = build_trainer(cfg, spec, mesh=make_mesh(
        n, axis_names=("data", "model"), shape=(2, 2)))
    assert tp.tp == 2 and tp._tp_sharded_layers()
    state_tp = tp.init_state(key)
    state_tp, loss_tp = tp.train_round(
        state_tp, {k: v[:, :2 * sz.batch] for k, v in host.items()},
        jax.random.PRNGKey(1))
    out["data2_model2"] = {"loss": float(loss_tp),
                           "tp_layers": sorted(tp._tp_sharded_layers())}
    assert np.isfinite(out["data2_model2"]["loss"]), out["data2_model2"]
    return out


# -- driver ------------------------------------------------------------------

def run_phase(ctx, name: str, fn) -> bool:
    """Run one phase and print its JSON line. A failure is caught HERE
    only, recorded in ctx.failed and printed — it can never turn into
    exit 0: main() reports ok only when ctx.failed is empty."""
    t0 = time.perf_counter()
    try:
        info, ok = fn(ctx), True
    except Exception as e:
        traceback.print_exc()
        info, ok = {"error": f"{type(e).__name__}: {str(e)[:2000]}"}, False
        ctx.failed.append(name)
    print(json.dumps({"phase": name, "ok": ok,
                      "seconds": round(time.perf_counter() - t0, 2),
                      **info}), flush=True)
    return ok


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the four-chip phases (needs 4 devices)")
    p.add_argument("--tiny", action="store_true",
                   help="rehearsal size, kernels under the interpreter")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the weights, the corpus and the requests")
    args = p.parse_args(argv)
    ctx = SimpleNamespace(sz=TINY if args.tiny else FULL, seed=args.seed,
                          four=args.four_chips, failed=[], device=None,
                          ingest=None, workdir=None)
    phases = ([("four_chips", phase_four_chips)] if ctx.four else
              [("kernels", phase_kernels), ("train", phase_train),
               ("serve", phase_serve), ("profile", phase_profile)])
    with tempfile.TemporaryDirectory(prefix="chip-smoke-") as ctx.workdir:
        on_chip = run_phase(ctx, "device", phase_device) and \
            ctx.device["platform"] == "tpu"
        # off the chip only the rehearsal goes on: full width on a CPU
        # would take hours and prove nothing about the device
        if not ctx.failed and (on_chip or ctx.sz.tiny):
            for name, fn in phases:
                run_phase(ctx, name, fn)
    ok = on_chip and not ctx.failed
    last = {"ok": ok, "device": ctx.device}
    if not ok:
        last["failed"] = ctx.failed or ["device: no TPU"]
    print(json.dumps(last), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
