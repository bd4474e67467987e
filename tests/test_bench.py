"""Benchmark harness pieces: analytic FLOPs, MFU peak lookup, the shared
round-timing core, and the jax.profiler capture hook (SURVEY §5.1)."""
import glob
import json
import os

import numpy as np
import pytest

from sparknet_tpu import CompiledNet
from sparknet_tpu.utils import flops
from sparknet_tpu.zoo import caffenet, cifar10_quick


def test_caffenet_forward_flops_match_alexnet_ballpark():
    """CaffeNet == AlexNet: published conv+fc forward cost is ~1.4-1.5
    GFLOP/image (2x ~720M MACs). The analytic count must land there —
    a wrong blob-shape or group factor would be off by 2x or more."""
    net = CompiledNet.compile(caffenet(batch=1, crop=227, n_classes=1000))
    f = flops.forward_flops_per_image(net)
    assert 1.3e9 < f < 1.6e9, f
    assert flops.train_flops_per_image(net) == pytest.approx(3 * f)


def test_conv_flops_shape_math():
    """cifar10_quick conv1: 32x32 out, 5x5 kernel, 3->32 channels."""
    net = CompiledNet.compile(cifar10_quick(batch=1))
    f = flops.forward_flops_per_image(net)
    conv1 = 2 * 32 * 32 * 5 * 5 * 3 * 32
    assert f > conv1  # contains at least conv1 + the rest
    # recompute by hand over all conv/ip layers and compare exactly
    total = 0.0
    for layer in net.spec.layers:
        if layer.type == "Convolution":
            _, h, w, co = net.blob_shapes[layer.tops[0]]
            ci = net.blob_shapes[layer.bottoms[0]][-1]
            k, g = layer.conv.kernel_size, layer.conv.group
            total += 2 * h * w * k * k * (ci // g) * co
        elif layer.type == "InnerProduct":
            of = net.blob_shapes[layer.tops[0]][-1]
            inf = int(np.prod(net.blob_shapes[layer.bottoms[0]][1:]))
            total += 2 * inf * of
    assert f == pytest.approx(total)


def test_default_bench_refuses_to_time_a_cpu():
    """`python bench.py` measures the chip: off it, one clear line and a
    non-zero exit — never a CPU number under a device metric's name."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.run([sys.executable, os.path.join(repo, "bench.py")],
                       capture_output=True, text=True, timeout=300,
                       cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0
    assert "times a TPU" in p.stderr and "'cpu'" in p.stderr, p.stderr[-800:]
    assert "images_per_sec" not in p.stdout


def test_peak_lookup():
    assert flops.peak_bf16_flops("TPU v5 lite") == pytest.approx(197e12)
    assert flops.peak_bf16_flops("TPU v4") == pytest.approx(275e12)


@pytest.mark.parametrize("kind", ["cpu", "no such device", "TPU v5",
                                  "tpu v5 lite"])
def test_unknown_device_kind_raises(kind):
    """An unknown device is an error, never a 0.0 that silently drops MFU;
    keys are exact — "v5" inside another kind's name matches nothing."""
    with pytest.raises(KeyError, match="no peak bf16"):
        flops.peak_bf16_flops(kind)


def test_bench_round_timing_core():
    """bench._build/_device_batches/_time_rounds run the real trainer round
    on the test mesh and return a positive time."""
    import bench
    net, trainer, state = bench._build(2, 2, crop=35, n_classes=8,
                                       n_devices=2)
    batches = bench._device_batches(trainer, 2, 2, 35, 8)
    t = bench._time_rounds(trainer, state, batches, trials=1)
    assert t > 0


def test_checkpoint_stall_bench_core(tmp_path):
    """bench.checkpoint_stall runs the real two-stage pipeline against all
    three stores at a tiny state size and reports a sane shape: async
    blocking must come in UNDER sync for every store (the whole point),
    and the artifact rows cover the full store x mode matrix."""
    import bench
    rows = bench.checkpoint_stall(
        mb=2, saves=2, out_path=str(tmp_path / "BENCH_CKPT.json"))
    assert {(r["store"], r["mode"]) for r in rows} == {
        (s, m) for s in ("local", "gs", "s3") for m in ("sync", "async")}
    by = {(r["store"], r["mode"]): r["blocking_ms_per_save"] for r in rows}
    for store in ("local", "gs", "s3"):
        assert by[(store, "async")] < by[(store, "sync")], (store, by)
    assert json.load(open(tmp_path / "BENCH_CKPT.json"))["headline"][
        "metric"] == "checkpoint_blocking_stall_async_over_sync"


def test_serve_bench_smoke(tmp_path):
    """bench.serve_bench drives the REAL server through every load
    regime — in-process trickle/open/saturate, the OPEN-LOOP HTTP rows
    through the real data plane, and the hot-swap + replica-drain chaos
    arm — and writes a complete BENCH_SERVE artifact. The committed
    BENCH_SERVE.json pins the acceptance numbers; this smoke asserts the
    harness itself — rows present, counters sane, zero dropped/hung HTTP
    clients, jit cache steady — at CI-noise-tolerant thresholds."""
    import bench
    out = bench.serve_bench(out_path=str(tmp_path / "BENCH_SERVE.json"),
                            duration_s=0.4, max_batch=4,
                            http_rps=(200.0,),
                            keep=str(tmp_path / "keep"))
    rows = out["rows"]
    assert [r["load"] for r in rows] == [
        "trickle", "open_50rps", "open_200rps", "saturate",
        "http_open_200rps", "binary_open_200rps", "ab_small_http",
        "ab_small_binary", "transport_parity", "binary_stream_blob",
        "http_chaos_swap_drain"]
    for r in rows[:4]:
        assert r["requests_failed"] == 0
        assert r["requests_ok"] > 0
        assert r["p99_ms"] is not None
    assert rows[0]["batch_fill_ratio"] == 1.0  # closed-loop single client
    assert rows[3]["batch_fill_ratio"] > 0.5   # saturation batches up
    # trickle carries the wake-on-submit stamp (the pin itself is
    # test_serve's lone-request bound; here: the artifact records it)
    assert rows[0]["old_poll_quantum_ms"] == 50.0
    assert "p99_below_old_quantum" in rows[0]
    # the open-loop rows, both transports: every request answered, none
    # dropped, silently timed out, or hung
    for row in (rows[4], rows[5]):
        assert row["ok"] > 0
        assert row["dropped"] == 0 and row["hung_clients"] == 0
        assert row["timed_out"] == 0
        assert row["answered"] == row["ok"] + row["shed_429"] + \
            row["shed_503"] + row["errors_other"]
        assert row["errors_other"] == 0
    # the driver-cost A/B rows carry the accounting the headline gates on
    for row in (rows[6], rows[7]):
        assert row["requests"] > 0
        assert row["dropped"] == 0 and row["hung_clients"] == 0
        assert row["errors_other"] == 0
        assert row["cpu_s_per_1k"] is not None
    # identical tensors through both wires (same replica, same bucket)
    assert rows[8]["bitwise_equal"] is True
    # the streaming row: multi-MB blob, bounded per-connection buffering
    stream = rows[9]
    assert stream["blob_mb"] >= 2.0
    assert stream["buffer_bounded_by_chunk"] is True
    assert stream["first_byte_decoupled"] is True
    assert stream["bitwise_equal_stream_vs_full"] is True
    # chaos: mid-traffic swap + drain with zero dropped/corrupted
    chaos = rows[10]
    assert chaos["zero_dropped"] and chaos["swap_ok"]
    assert chaos["bad"] == 0
    art = json.load(open(tmp_path / "BENCH_SERVE.json"))
    assert art["headline"]["metric"] == "serve_saturated_batch_fill_ratio"
    assert art["headline"]["jit_cache_ok"] is True
    assert art["headline"]["http_zero_dropped"] is True
    assert art["headline"]["binary_zero_dropped"] is True
    assert art["headline"]["transport_parity_bitwise"] is True
    assert art["headline"]["transport_ab"]["ab_zero_dropped"] is True
    assert art["headline"]["stream"]["buffer_bounded_by_chunk"] is True
    assert art["headline"]["chaos_zero_dropped"] is True
    # the serve JSONL artifact landed for CI upload-on-failure
    assert (tmp_path / "keep" / "serve_bench.jsonl").exists()


def test_econ_bench_smoke(tmp_path):
    """bench.econ_bench runs the three r9 inference-economics levers
    through the REAL serving stack: quant-vs-f32 saturate + parity, the
    cold/warm subprocess replica against a shared persistent compile
    cache, and the pow2-vs-derived bucket-ladder A/B on a skewed trace.
    The committed BENCH_ECON.json pins the acceptance numbers; this
    smoke asserts the harness and its gates hold at CI scale."""
    import bench
    out = bench.econ_bench(out_path=str(tmp_path / "BENCH_ECON.json"),
                           duration_s=0.4, max_batch=8,
                           keep=str(tmp_path / "keep"))
    head = out["headline"]
    # quant parity: drift within the calibrated tolerance
    assert head["quant_parity_ok"] is True
    # the cold-start acceptance: a warm replica compiles NOTHING fresh
    assert head["coldstart_warm_zero_miss"] is True
    cold_s, warm_s = head["coldstart_cold_vs_warm_s"]
    assert cold_s > 0 and warm_s > 0
    # the ladder acceptance: derived beats pow2 on fill, jit cache pinned
    assert head["ladder_fill_improved"] is True
    assert head["jit_cache_ok"] is True
    assert head["ok"] is True
    rows = {r.get("arm", r.get("load")): r for r in out["rows"]}
    warm_stats = rows["coldstart"]["warm_compile_stats"]
    for what in ("net", "serve_bucket"):
        assert warm_stats.get(what, {}).get("cache_misses", 1) == 0, what
    lad = rows["ladder_ab"]
    # the deterministic half: optimal-by-construction on the observed
    # histogram, never worse than pow2
    assert lad["derived_fill_on_observed"] >= lad["pow2_fill_on_observed"]
    art = json.load(open(tmp_path / "BENCH_ECON.json"))
    assert art["headline"]["metric"] == "serve_econ_levers"
    assert (tmp_path / "keep" / "econ_bench.log").exists()


def test_obs_bench_smoke(tmp_path, monkeypatch):
    """bench.obs_bench runs the REAL train loop in both arms (telemetry
    on with status server + trace + scraper, and off) and writes a
    complete BENCH_OBS artifact. The committed BENCH_OBS.json pins the
    acceptance number (<= 2% overhead); this smoke asserts the harness —
    both arms ran, the artifact is stamped — without asserting the
    noise-sensitive ratio on a contended CI host."""
    import bench
    monkeypatch.setenv("SPARKNET_TPU_HOME", str(tmp_path))
    out_path = str(tmp_path / "BENCH_OBS.json")
    out = bench.obs_bench(out_path=out_path, rounds=6, warmup=2, reps=1)
    assert out["metric"] == "obs_full_telemetry_per_round_overhead"
    assert out["per_mode"]["off_ms"] > 0 and out["per_mode"]["on_ms"] > 0
    art = json.load(open(out_path))
    assert {r["telemetry"] for r in art["rows"]} == {"on", "off"}
    assert art["meta"]["jax_version"]  # run_metadata stamp


def test_sharding_bench_smoke(tmp_path):
    """bench.sharding_bench runs the three r7 trainer arms and writes a
    complete BENCH_r07-style artifact. The deterministic claims are
    asserted here too (they do not depend on CPU timing): the ZeRO-1 arm
    cuts the per-device at-rest momentum bytes by >= (n_data-1)/n_data of
    the replicated arm's, params stay replicated in the momentum mode,
    and the stage-1 collect number is recorded per arm. The 2%-img/s
    acceptance is a committed-BENCH_r07 claim (timing on a shared-core
    CPU mesh is noise), not a tier-1 assertion."""
    import bench
    out_path = str(tmp_path / "BENCH_r07.json")
    out = bench.sharding_bench(out_path=out_path, trials=1, small=True)
    rows = out["rows"]
    assert [r["arm"] for r in rows] == [
        "r6_prefetch_donate", "named_replicated", "named_fused",
        "named_momentum"]
    by = {r["arm"]: r for r in rows}
    for r in rows:
        assert r["images_per_sec"] > 0
        assert r["collect_stage1_ms"] >= 0
    base = by["r6_prefetch_donate"]["per_device_state_bytes"]
    rep = by["named_replicated"]["per_device_state_bytes"]
    zm = by["named_momentum"]["per_device_state_bytes"]
    assert rep == base  # logical replicated == replica layout, byte for byte
    assert zm["params"] == base["params"]
    n = out["headline"]["n_data"]
    # >= (n_data-1)/n_data of the momentum bytes stays the conservative
    # floor even counting indivisible leaves (CaffeNet's momentum mass is
    # in divisible fc/conv weights)
    assert base["momentum"] - zm["momentum"] >= \
        base["momentum"] * (n - 1) / n * 0.95, (base, zm)
    art = json.load(open(out_path))
    assert art["headline"]["metric"] == \
        "per_device_momentum_bytes_sharded_over_replicated"
    assert art["meta"]["jax_version"]
    assert "fetch_async_ms" in art["headline"]
    # r8 arms: the fused-boundary round ratio and the collect A/B (the
    # async-collect main-thread cost must be far below the sync fetch's
    # lower bound of an actual D2H materialization... on CPU both are
    # small; assert presence + sanity, not timing)
    assert art["headline"]["fused_round_ms_vs_unfused"] > 0
    for k in ("collect_sync_ms", "collect_async_blocking_ms",
              "fetch_shards_ms"):
        assert k in art["headline"], k


def test_ckpt_shard_bench_smoke(tmp_path):
    """bench.ckpt_shard_bench writes the r8 BENCH_CKPT_SHARD artifact;
    the DETERMINISTIC claims — restored maps bitwise equal across
    layouts, logical bytes identical (no replicated leaf written twice)
    — are asserted inside the bench per worker count and re-checked on
    the artifact here. The wall-time-decreases claim is the committed
    pod number (CPU rows stamp structure_proof)."""
    import bench
    out_path = str(tmp_path / "BENCH_CKPT_SHARD.json")
    out = bench.ckpt_shard_bench(out_path=out_path, trials=1, mb=2,
                                 workers=(2, 4))
    art = json.load(open(out_path))
    assert art["headline"]["bytes_equal"] is True
    assert [r["workers"] for r in art["rows"]] == [2, 4]
    for r in art["rows"]:
        for layout in ("monolithic", "sharded"):
            assert r[layout]["save_restore_ms"] > 0
    assert art["headline"]["structure_proof"] is True  # CPU build
    assert art["meta"]["jax_version"]


def test_profiler_trace_capture(tmp_path):
    """maybe_trace writes a TensorBoard-loadable capture; None is a no-op."""
    import jax
    import jax.numpy as jnp
    from sparknet_tpu.utils.profiling import maybe_trace
    with maybe_trace(None):
        pass
    d = str(tmp_path / "trace")
    with maybe_trace(d):
        float(jax.jit(lambda x: x * 2)(jnp.ones(8)).sum())
    files = glob.glob(os.path.join(d, "**", "*"), recursive=True)
    assert any(os.path.isfile(f) for f in files), "no trace files written"
