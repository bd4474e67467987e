"""Data layer tests with synthetic fixtures in the exact on-disk formats
(the offline analogue of the reference's loader specs + PreprocessorSpec)."""
import numpy as np
import pytest

from sparknet_tpu.data import cifar, mnist, adult, imagenet
from sparknet_tpu.data.dataset import ArrayDataset, RoundSampler
from sparknet_tpu.data.preprocess import (ImagePreprocessor,
                                          compute_mean_image, to_nhwc,
                                          random_crop_nchw, center_crop_nchw)
from sparknet_tpu.schema import Field, Schema


# -- CIFAR -------------------------------------------------------------------

def test_cifar_loader(tmp_path):
    d = str(tmp_path / "cifar")
    cifar.write_synthetic(d, n_per_file=50)
    loader = cifar.CifarLoader(d, seed=1)
    assert loader.train_images.shape == (250, 3, 32, 32)
    assert loader.test_images.shape == (50, 3, 32, 32)
    assert loader.mean_image.shape == (3, 32, 32)
    assert loader.train_labels.min() >= 0 and loader.train_labels.max() <= 9
    batch = loader.train_batch_dict()
    # mean-subtracted data has ~zero mean
    assert abs(batch["data"].mean()) < 1.0
    assert batch["label"].shape == (250, 1)


def test_cifar_missing_file(tmp_path):
    with pytest.raises(FileNotFoundError, match="data_batch_1.bin"):
        cifar.CifarLoader(str(tmp_path))


def test_cifar_shuffle_deterministic(tmp_path):
    d = str(tmp_path / "c")
    cifar.write_synthetic(d, n_per_file=20)
    a = cifar.CifarLoader(d, seed=5)
    b = cifar.CifarLoader(d, seed=5)
    np.testing.assert_array_equal(a.train_labels, b.train_labels)


# -- MNIST -------------------------------------------------------------------

def test_mnist_loader(tmp_path):
    d = str(tmp_path / "mnist")
    mnist.write_synthetic(d, n_train=64, n_test=16)
    loader = mnist.MnistLoader(d)
    assert loader.train_images.shape == (64, 1, 28, 28)
    # normalized to [-0.5, 0.5] (reference MnistLoader.scala:35)
    assert loader.train_images.min() >= -0.5
    assert loader.train_images.max() <= 0.5
    assert loader.test_labels.dtype == np.int32


def test_mnist_bad_magic(tmp_path):
    p = tmp_path / "bad"
    p.write_bytes(b"\x00\x00\x00\x07" + b"\x00" * 16)
    with pytest.raises(ValueError, match="bad magic"):
        mnist.read_idx_images(str(p))


# -- Adult -------------------------------------------------------------------

def test_adult_loader(tmp_path):
    p = str(tmp_path / "adult.data")
    adult.write_synthetic(p, n=100)
    loader = adult.AdultLoader(p)
    batch = loader.batch_dict()
    assert batch["C0"].shape == (100, 14)
    assert set(np.unique(batch["label"])) <= {0, 1}
    # normalized features
    assert abs(batch["C0"].mean()) < 0.2


# -- ImageNet sharded tar ----------------------------------------------------

def test_sharded_tar_loader(tmp_path):
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(root, n_shards=2, per_shard=6,
                                                 size=48)
    labels = imagenet.load_label_map(label_path)
    shards = imagenet.list_shards(root, prefix="train.")
    assert len(shards) == 2
    loader = imagenet.ShardedTarLoader(shards, labels, height=32, width=32)
    images, lbls = loader.load_all()
    assert images.shape == (12, 3, 32, 32)  # decoded + force-resized, CHW
    assert images.dtype == np.uint8
    assert loader.skipped == 0


def test_sharded_tar_corrupt_images_skipped_not_looped(tmp_path):
    """The reference looped forever on a corrupt image
    (ImageNetLoader.scala:82-85); we must skip and count."""
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(root, n_shards=1,
                                                 per_shard=9, size=48,
                                                 corrupt_every=3)
    loader = imagenet.ShardedTarLoader(
        imagenet.list_shards(root), imagenet.load_label_map(label_path),
        height=32, width=32)
    images, _ = loader.load_all()   # terminates — that's the test
    assert len(images) == 6
    assert loader.skipped == 3


def test_host_shard_assignment():
    shards = [f"s{i}" for i in range(10)]
    a = imagenet.host_shards(shards, 0, 4)
    b = imagenet.host_shards(shards, 1, 4)
    assert a == ["s0", "s4", "s8"] and b == ["s1", "s5", "s9"]
    allsets = [imagenet.host_shards(shards, i, 4) for i in range(4)]
    assert sorted(sum(allsets, [])) == sorted(shards)


def test_streaming_batches(tmp_path):
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(root, n_shards=1, per_shard=7,
                                                 size=48)
    loader = imagenet.ShardedTarLoader(
        imagenet.list_shards(root), imagenet.load_label_map(label_path),
        height=32, width=32)
    batches = list(loader.batches(3))
    assert len(batches) == 2  # 7 images, drop_last
    assert batches[0]["data"].shape == (3, 3, 32, 32)
    assert batches[0]["label"].shape == (3, 1)


# -- Preprocessing -----------------------------------------------------------

def test_random_crop_values_come_from_source(rng):
    imgs = rng.standard_normal((4, 3, 16, 16)).astype(np.float32)
    crop = random_crop_nchw(imgs, 8, np.random.default_rng(0))
    assert crop.shape == (4, 3, 8, 8)
    # every cropped pixel must exist in the source image (set membership,
    # the reference's own crop test strategy, PreprocessorSpec.scala:95-114)
    for i in range(4):
        assert np.isin(crop[i], imgs[i]).all()


def test_center_crop():
    imgs = np.arange(1 * 1 * 6 * 6, dtype=np.float32).reshape(1, 1, 6, 6)
    c = center_crop_nchw(imgs, 4)
    np.testing.assert_array_equal(c[0, 0, 0], imgs[0, 0, 1, 1:5])


def test_image_preprocessor_mean_and_crop(rng):
    schema = Schema(Field("data", "float32", (3, 8, 8)),
                    Field("label", "int32", (1,)))
    imgs = rng.standard_normal((10, 3, 12, 12)).astype(np.float32)
    mean = compute_mean_image(imgs)
    pp = ImagePreprocessor(schema, mean_image=mean, crop=8, seed=3)
    out = pp.convert_batch({"data": imgs,
                            "label": np.zeros((10, 1), np.int64)},
                           train=True)
    assert out["data"].shape == (10, 8, 8, 3)  # cropped + NHWC
    assert out["label"].dtype == np.int32
    # deterministic center crop in eval mode
    e1 = pp.convert_batch({"data": imgs, "label": np.zeros((10, 1))},
                          train=False)
    e2 = pp.convert_batch({"data": imgs, "label": np.zeros((10, 1))},
                          train=False)
    np.testing.assert_array_equal(e1["data"], e2["data"])


def test_preprocessor_throughput_floor():
    """Perf budget the reference CI asserted: 256 images (crop+mean+layout)
    in <= 1.0 s (PreprocessorSpec.scala:75,136). One reading of a wall clock
    means nothing under six test workers on eight cores (2.6 s there, 0.28 s
    on the idle machine), and a stall only ever adds: so the floor is held
    by the BEST of up to ten turns, and met either by the reference's budget
    or by an ORDERING under the same load -- the preprocessor against the
    bare array work it is made of (one subtraction, one crop, one transpose
    of the whole batch), taken turn by turn: 1.8 idle, 4.1 at 0.72 s in a
    whole run of the suite. It fails where the preprocessor is over the
    budget at its best AND over four times the bare work beside it."""
    import time
    schema = Schema(Field("data", "float32", (3, 227, 227)),
                    Field("label", "int32", (1,)))
    imgs = np.random.default_rng(0).integers(
        0, 256, (256, 3, 256, 256)).astype(np.float32)
    mean = imgs.mean(0)
    pp = ImagePreprocessor(schema, mean_image=mean, crop=227)

    def bare():
        return np.ascontiguousarray(
            (imgs - mean)[:, :, 14:241, 14:241].transpose(0, 2, 3, 1))

    def seconds(fn):
        t0 = time.perf_counter()
        out = fn()
        return time.perf_counter() - t0, out

    best, floor = float("inf"), float("inf")
    for _ in range(10):
        dt, out = seconds(lambda: pp.convert_batch(
            {"data": imgs, "label": np.zeros((256, 1))}))
        best = min(best, dt)
        floor = min(floor, seconds(bare)[0])
        if best <= 1.0 or best <= 4.0 * floor:
            break
    assert out["data"].shape == (256, 227, 227, 3) == bare().shape
    assert best <= 1.0 or best <= 4.0 * floor, (
        f"preprocessing 256 images took {best:.3f}s at best (budget 1.0s), "
        f"{best / floor:.1f}x the bare array work ({floor:.3f}s)")


# -- Sampler -----------------------------------------------------------------

def test_round_sampler_windows_stay_in_partition():
    n_workers, local_b, tau = 4, 2, 3
    ds = ArrayDataset({"x": np.arange(80, dtype=np.int64)})
    s = RoundSampler(ds, n_workers, local_b, tau, seed=1)
    for _ in range(5):
        r = s.next_round()
        assert r["x"].shape == (tau, n_workers * local_b)
        for w in range(n_workers):
            block = r["x"][:, w * local_b:(w + 1) * local_b]
            lo, hi = w * 20, (w + 1) * 20
            assert (block >= lo).all() and (block < hi).all()
            # sequential window (reference it.drop(startIdx) semantics)
            flat = block.reshape(-1)
            assert (np.diff(flat) == 1).all()


def test_round_sampler_rejects_oversized_window():
    ds = ArrayDataset({"x": np.arange(16)})
    with pytest.raises(ValueError, match="exceeds partition"):
        RoundSampler(ds, n_workers=4, local_batch=2, tau=3)


def test_eval_batches_cover():
    ds = ArrayDataset({"x": np.arange(17)})
    s = RoundSampler(ds, 1, 1, 1)
    batches = list(s.eval_batches(4))
    assert len(batches) == 4
    assert sum(len(b["x"]) for b in batches) == 16


def test_shard_imagenet_val_split(tmp_path):
    """scripts/shard_imagenet.py val path (reference process_val_files,
    put_imagenet_on_s3.py:64-77): flat val tar + ground-truth labels ->
    val.NNNN.tar shards + val.txt, loadable by ShardedTarLoader."""
    import io
    import os
    import sys
    import tarfile
    from PIL import Image
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import shard_imagenet

    r = np.random.default_rng(0)
    val_tar = str(tmp_path / "ILSVRC2012_img_val.tar")
    truth = str(tmp_path / "truth.txt")
    names = [f"ILSVRC2012_val_{i:08d}.JPEG" for i in range(12)]
    with tarfile.open(val_tar, "w") as tar:
        for name in names:
            arr = r.integers(0, 256, (48, 48, 3), dtype=np.uint8)
            buf = io.BytesIO()
            Image.fromarray(arr).save(buf, format="JPEG")
            info = tarfile.TarInfo(name=name)
            info.size = len(buf.getvalue())
            tar.addfile(info, io.BytesIO(buf.getvalue()))
    with open(truth, "w") as f:
        f.write("\n".join(f"{n} {i % 5}" for i, n in enumerate(names)) + "\n")

    out = str(tmp_path / "out")
    os.makedirs(out)
    shard_imagenet.shard_val(val_tar, truth, out, shards=3, size=32, seed=0)

    shards = imagenet.list_shards(out, prefix="val.")
    assert len(shards) == 3
    labels = imagenet.load_label_map(os.path.join(out, "val.txt"))
    assert len(labels) == 12
    loader = imagenet.ShardedTarLoader(shards, labels, height=32, width=32)
    images, lbls = loader.load_all()
    assert images.shape == (12, 3, 32, 32)
    # labels survive the reshard: every (name, label) pair intact
    assert sorted(lbls.tolist()) == sorted(int(v) for v in labels.values())


# -- Streaming round source --------------------------------------------------

def _stream_fixture(tmp_path, n_shards=2, per_shard=8):
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=n_shards, per_shard=per_shard, size=48)
    return imagenet.ShardedTarLoader(
        imagenet.list_shards(root), imagenet.load_label_map(label_path),
        height=32, width=32)


def test_streaming_round_source_layout(tmp_path):
    """Rounds have the RoundSampler layout ([tau, W*B, ...], batch axis
    blocked by worker) and each worker block is a consecutive stream run —
    verified against the materialized loader order."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    loader = _stream_fixture(tmp_path)  # 16 images
    ref_images, ref_labels = _stream_fixture(tmp_path).load_all()
    w, b, tau = 2, 2, 2  # round = 8 examples
    with StreamingRoundSource(loader, w, b, tau) as src:
        r = src.next_round(round_index=0)
        assert r["data"].shape == (tau, w * b, 3, 32, 32)
        assert r["data"].dtype == np.uint8
        assert r["label"].shape == (tau, w * b, 1)
        # worker 0's block = stream[0:4], worker 1's = stream[4:8]
        for wk in range(w):
            block = np.concatenate(
                [r["data"][t, wk * b:(wk + 1) * b] for t in range(tau)])
            np.testing.assert_array_equal(
                block, ref_images[wk * tau * b:(wk + 1) * tau * b])
            lbl = np.concatenate(
                [r["label"][t, wk * b:(wk + 1) * b, 0] for t in range(tau)])
            np.testing.assert_array_equal(
                lbl, ref_labels[wk * tau * b:(wk + 1) * tau * b])


def test_streaming_round_source_cycles_epochs(tmp_path):
    """16 images / 8 per round: round 3 requires a second pass over the
    shards (the reference requeued tars; no StopIteration mid-training)."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    loader = _stream_fixture(tmp_path)
    with StreamingRoundSource(loader, 2, 2, 2) as src:
        first = src.next_round()
        src.next_round()          # round 2 finishes epoch 1 (16 = 2 rounds)
        again = src.next_round()  # round 3 re-streams the shards
        np.testing.assert_array_equal(first["data"], again["data"])
    assert src.epochs >= 1


def test_streaming_cursor_resume_continues_stream(tmp_path):
    """THE elastic-stream property: a fresh source seeked to the cursor
    recorded after round R produces exactly the rounds an uninterrupted
    stream would have produced from R+1 on — no re-stream from shard 0,
    no skipped window (fixes the r2 data/streaming.py:16-19 limitation)."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    w, b, tau = 2, 2, 2  # 8 examples per round, 16 per epoch
    with StreamingRoundSource(_stream_fixture(tmp_path), w, b, tau) as src:
        uninterrupted = [src.next_round(round_index=i) for i in range(4)]
        cursor_after_r0 = src.cursor_at(0)
    assert cursor_after_r0 is not None
    (shard, entry), epochs = cursor_after_r0
    assert (shard, entry) != (0, 0)

    resumed = StreamingRoundSource(_stream_fixture(tmp_path), w, b, tau)
    resumed.seek((shard, entry), epochs)
    with resumed:
        for want in uninterrupted[1:]:
            got = resumed.next_round()
            np.testing.assert_array_equal(got["data"], want["data"])
            np.testing.assert_array_equal(got["label"], want["label"])


def test_streaming_cursor_at_retention_and_epochs(tmp_path):
    """cursor_at keys by round index (the loop's one-deep prefetch runs one
    round ahead of training); old entries are pruned; epoch counter rides
    the cursor. Seeking after the stream started fails loudly."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    with StreamingRoundSource(_stream_fixture(tmp_path), 2, 2, 2) as src:
        for i in range(8):  # 4 epochs of 2 rounds
            src.next_round(round_index=i)
        assert src.cursor_at(0) is None  # pruned (keeps a small window)
        assert src.cursor_at(7) is not None
        (_, _), ep = src.cursor_at(7)
        assert ep == 3  # 8 rounds of 8 = rounds 7 starts in pass 4
        with pytest.raises(RuntimeError, match="seek"):
            src.seek((0, 0))


def test_iter_with_pos_seek_skips_without_decoding(tmp_path):
    """Seeking skips raw tar entries: the positions reported for the
    continuation match the unseeked stream's, and a cursor past the end
    yields nothing (no false 'no decodable images' error on wrap)."""
    loader = _stream_fixture(tmp_path)
    all_pos = [(lbl, pos) for _, lbl, pos in loader.iter_with_pos()]
    mid = all_pos[5][1]
    cont = [(lbl, pos) for _, lbl, pos
            in _stream_fixture(tmp_path).iter_with_pos(mid)]
    assert cont == all_pos[6:]
    last = all_pos[-1][1]
    assert list(_stream_fixture(tmp_path).iter_with_pos(last)) == []


def test_run_loop_checkpoint_carries_stream_cursor(tmp_path):
    """End to end through run_loop: a streaming training run checkpoints
    its stream cursor, and the resumed run seeks (log line) instead of
    restarting at shard 0."""
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.streaming import StreamingRoundSource
    from sparknet_tpu.utils import checkpoint as ckpt
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet
    import jax

    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=4, per_shard=16, size=28, n_classes=10)
    n_local = jax.local_device_count()

    def make_source():
        loader = imagenet.ShardedTarLoader(
            imagenet.list_shards(root), imagenet.load_label_map(label_path),
            height=28, width=28)
        return StreamingRoundSource(loader, n_local, 2, 2)

    def make_cfg(rounds):
        # health off: this trains a throwaway lenet on RAW 0-255 pixels (a
        # cursor-bookkeeping fixture, not a convergence run) — it diverges
        # violently by design, and the supervisor would (correctly) step in
        from sparknet_tpu.utils.health import HealthConfig
        return RunConfig(model="lenet", tau=2, local_batch=2,
                         max_rounds=rounds, workdir=str(tmp_path), seed=0,
                         eval_every=0, checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=2,
                         health=HealthConfig(enabled=False))

    class GrayTo28:
        def convert_batch(self, batch, train=True, rng=None):
            x = batch["data"].astype(np.float32).mean(axis=1)  # CHW->HW
            return {"data": x[..., None], "label": batch["label"]}

    spec = lenet(batch=2)
    train(make_cfg(2), spec, make_source(), None,
          logger=Logger(str(tmp_path / "l1.txt"), echo=False),
          batch_transform=GrayTo28())
    _, _, extra = ckpt.restore_flat(str(tmp_path / "ck"))
    # one host, one reader: [[ [shard, entry, epochs] ]]
    assert "stream" in extra and len(extra["stream"]) == 1
    (host_rows,) = extra["stream"]
    assert len(host_rows) == 1
    shard, entry, epochs = host_rows[0]
    assert (shard, entry) != (0, 0)

    train(make_cfg(4), spec, make_source(), None,
          logger=Logger(str(tmp_path / "l2.txt"), echo=False),
          batch_transform=GrayTo28())
    text = open(str(tmp_path / "l2.txt")).read()
    assert f"stream resumed at shard {shard} entry {entry}" in text

    # relaunching the COMPLETED run must not overwrite the final
    # checkpoint with a cursor-less one (the loop runs zero rounds and
    # has no cursor to record — r3 review finding)
    _, _, extra2 = ckpt.restore_flat(str(tmp_path / "ck"))
    assert "stream" in extra2
    train(make_cfg(4), spec, make_source(), None,
          logger=Logger(str(tmp_path / "l3.txt"), echo=False),
          batch_transform=GrayTo28())
    _, _, extra3 = ckpt.restore_flat(str(tmp_path / "ck"))
    assert extra3.get("stream") == extra2.get("stream")


def test_mean_image_sidecar_skips_second_pass(tmp_path, monkeypatch):
    """Streaming mean image is computed once and persisted next to the
    checkpoints; later launches load it WITHOUT another decode pass over
    the corpus (fixes the r2 apps/imagenet_app.py:164-168 re-pass)."""
    from sparknet_tpu.apps import imagenet_app
    from sparknet_tpu.utils.config import RunConfig

    loader = _stream_fixture(tmp_path)
    cfg = RunConfig(checkpoint_dir=str(tmp_path / "ck"),
                    data_dir=str(tmp_path / "shards"))
    first = imagenet_app._load_or_compute_mean(cfg, loader, 0, 1, "t")
    assert (tmp_path / "ck" / "mean_image.npz").exists()

    def boom(*_a, **_k):
        raise AssertionError("second launch re-streamed the corpus")

    monkeypatch.setattr(imagenet_app, "streaming_sum_count", boom)
    second = imagenet_app._load_or_compute_mean(cfg, loader, 0, 1, "t")
    np.testing.assert_allclose(second, first, atol=1e-6)
    # no checkpoint_dir -> no sidecar, compute every launch
    with pytest.raises(AssertionError, match="re-streamed"):
        imagenet_app._load_or_compute_mean(
            RunConfig(checkpoint_dir=None,
                      data_dir=str(tmp_path / "shards")), loader, 0, 1, "t")
    # a CHANGED corpus must not silently reuse the sidecar: growing a
    # shard changes the corpus id, so the loader recomputes (r3 review)
    with open(loader.shard_paths[0], "ab") as f:
        f.write(b"\0" * 1024)
    with pytest.raises(AssertionError, match="re-streamed"):
        imagenet_app._load_or_compute_mean(cfg, loader, 0, 1, "t")
    # legacy un-id'd mean_image.npy migrates to the stamped .npz without
    # a decode pass (r3 review: no silent repay of the corpus pass)
    import os
    os.remove(tmp_path / "ck" / "mean_image.npz")
    with open(tmp_path / "ck" / "mean_image.npy", "wb") as f:
        np.save(f, first)
    migrated = imagenet_app._load_or_compute_mean(cfg, loader, 0, 1, "t")
    np.testing.assert_allclose(migrated, first, atol=1e-6)
    assert (tmp_path / "ck" / "mean_image.npz").exists()


def test_streaming_round_source_error_propagates(tmp_path):
    """A decode-thread failure must fail the training loop, not hang it."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    loader = _stream_fixture(tmp_path)
    loader.shard_paths = [str(tmp_path / "missing.tar")]
    src = StreamingRoundSource(loader, 2, 2, 2)
    with pytest.raises(RuntimeError, match="streaming decode thread"):
        src.next_round()
    src.close()


def test_streaming_sum_count_matches_materialized(tmp_path):
    from sparknet_tpu.data.streaming import streaming_sum_count
    loader = _stream_fixture(tmp_path)
    images, _ = _stream_fixture(tmp_path).load_all()
    s, n = streaming_sum_count(loader)
    assert n == len(images)
    np.testing.assert_allclose(s / n, compute_mean_image(images), atol=1e-5)


def test_shard_val_rejects_label_only_file(tmp_path):
    """A devkit-style ground-truth file (labels only, no filenames) must
    fail with a clear message, not an unpack traceback (r2 review)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import shard_imagenet
    bad = str(tmp_path / "truth.txt")
    with open(bad, "w") as f:
        f.write("490\n361\n171\n")
    with pytest.raises(SystemExit, match="filename label"):
        shard_imagenet.shard_val("unused.tar", bad, str(tmp_path), 2, 32, 0)


def test_load_all_limit_caps_decoding(tmp_path):
    """load_all(limit=n) stops DECODING at n examples (a real RAM cap, not
    a slice of a fully materialized corpus — r2 review)."""
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(root, n_shards=2,
                                                 per_shard=8, size=48)
    loader = imagenet.ShardedTarLoader(
        imagenet.list_shards(root), imagenet.load_label_map(label_path),
        height=32, width=32)
    images, labels = loader.load_all(5)
    assert len(images) == 5 and len(labels) == 5


# -- Parallel multi-reader streaming (r4: per-source ceiling killer) ---------

def _parallel_fixture(tmp_path, n_sources, n_shards=4, per_shard=8,
                      w=2, b=2, tau=2):
    from sparknet_tpu.data.streaming import make_parallel_source
    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=n_shards, per_shard=per_shard, size=48)
    return make_parallel_source(
        imagenet.list_shards(root), imagenet.load_label_map(label_path),
        w, b, tau, n_sources, height=32, width=32)


def test_parallel_source_layout_blocks_by_reader(tmp_path):
    """Round layout matches StreamingRoundSource ([tau, W*B, ...], batch
    axis blocked by worker); with N == n_workers each worker's window is
    exactly one reader's consecutive stream run over shards j::N."""
    w, b, tau = 2, 2, 2  # round = 8, block = 4 per reader
    src = _parallel_fixture(tmp_path, n_sources=2, w=w, b=b, tau=tau)
    per_reader = [ld.__class__(ld.shard_paths, ld.label_map,
                               height=32, width=32).load_all()
                  for ld in src.loaders]
    with src:
        r = src.next_round(round_index=0)
    assert r["data"].shape == (tau, w * b, 3, 32, 32)
    assert r["label"].shape == (tau, w * b, 1)
    for wk in range(w):  # worker wk's window = reader wk's stream[0:4]
        block = np.concatenate(
            [r["data"][t, wk * b:(wk + 1) * b] for t in range(tau)])
        np.testing.assert_array_equal(block, per_reader[wk][0][:tau * b])
        lbl = np.concatenate(
            [r["label"][t, wk * b:(wk + 1) * b, 0] for t in range(tau)])
        np.testing.assert_array_equal(lbl, per_reader[wk][1][:tau * b])


def test_parallel_source_n1_matches_single_source(tmp_path):
    """make_parallel_source(n=1) reproduces StreamingRoundSource's rounds
    exactly — the parallel layout is a strict generalization."""
    from sparknet_tpu.data.streaming import StreamingRoundSource
    w, b, tau = 2, 2, 2
    psrc = _parallel_fixture(tmp_path, n_sources=1, w=w, b=b, tau=tau)
    loader = imagenet.ShardedTarLoader(
        list(psrc.loaders[0].shard_paths), psrc.loaders[0].label_map,
        height=32, width=32)
    with psrc, StreamingRoundSource(loader, w, b, tau) as ssrc:
        for _ in range(3):
            pr, sr = psrc.next_round(), ssrc.next_round()
            np.testing.assert_array_equal(pr["data"], sr["data"])
            np.testing.assert_array_equal(pr["label"], sr["label"])


def test_parallel_source_exactly_once_per_epoch(tmp_path):
    """Every example is consumed exactly once per reader-epoch: 4 shards x
    8 images, 2 readers of 16 each, 8-example rounds -> 4 rounds cover the
    corpus exactly once (labels compared as multisets per reader)."""
    src = _parallel_fixture(tmp_path, n_sources=2)  # block = 4
    per_reader = [ld.__class__(ld.shard_paths, ld.label_map,
                               height=32, width=32).load_all()
                  for ld in src.loaders]
    seen = [[] for _ in range(2)]
    with src:
        for i in range(4):
            r = src.next_round(round_index=i)
            for wk in range(2):
                seen[wk].extend(np.concatenate(
                    [r["label"][t, wk * 2:(wk + 1) * 2, 0]
                     for t in range(2)]).tolist())
        cursors = src.cursor_at(3)
    for j in range(2):
        assert sorted(seen[j]) == sorted(per_reader[j][1].tolist())
    # end-of-pass cursor: position at the subset's last entry, epoch count
    # still 0 until the wrap is observed (same semantics as the single
    # source's cursor_at)
    assert all(ep == 0 for (_, _), ep in cursors)


def test_parallel_source_resume_continues_stream(tmp_path):
    """The elastic-stream property with N readers: a fresh source
    seek_rows'd to the cursors recorded after round R reproduces the
    uninterrupted rounds R+1.. exactly — per-reader cursors, no re-stream,
    no replay."""
    src = _parallel_fixture(tmp_path, n_sources=2)
    with src:
        uninterrupted = [src.next_round(round_index=i) for i in range(5)]
        cur = src.cursor_at(1)
    assert cur is not None and len(cur) == 2
    rows = [[s, e, ep] for (s, e), ep in cur]

    resumed = _parallel_fixture(tmp_path, n_sources=2)
    assert resumed.seek_rows(rows)
    with resumed:
        for want in uninterrupted[2:]:
            got = resumed.next_round()
            np.testing.assert_array_equal(got["data"], want["data"])
            np.testing.assert_array_equal(got["label"], want["label"])


def test_parallel_source_reader_count_change_refuses_cursors(tmp_path):
    """A checkpoint from a different reader count reassigned the shards:
    seek_rows must refuse (False) so the caller restarts cleanly."""
    src = _parallel_fixture(tmp_path, n_sources=2)
    assert not src.seek_rows([[0, 0, 0]])          # 1 row into 2 readers
    assert not src.seek_rows([[0, 0, 0]] * 3)      # 3 rows into 2 readers
    assert src.seek_rows([[0, 0, 0], [0, 0, 0]])   # matching count is fine
    src.close()


def test_parallel_source_invalid_construction(tmp_path):
    """More sources than shards clamps (make_parallel_source); a round not
    divisible by N fails loudly; an empty reader fails loudly."""
    from sparknet_tpu.data.streaming import ParallelStreamingSource
    src = _parallel_fixture(tmp_path, n_sources=99, n_shards=4)
    assert src.n_sources == 4
    src.close()
    loaders = _parallel_fixture(tmp_path, n_sources=2).loaders
    with pytest.raises(ValueError, match="not divisible"):
        ParallelStreamingSource(loaders + [loaders[0]], 2, 2, 2)  # 8 % 3
    empty = imagenet.ShardedTarLoader([], loaders[0].label_map)
    with pytest.raises(ValueError, match="no shards"):
        ParallelStreamingSource([loaders[0], empty], 2, 2, 2)


def test_parallel_source_error_propagates(tmp_path):
    """One reader failing must fail the consumer, not hang the round
    barrier."""
    src = _parallel_fixture(tmp_path, n_sources=2)
    src.loaders[1].shard_paths = [str(tmp_path / "missing.tar")]
    with pytest.raises(RuntimeError, match="streaming decode thread"):
        for i in range(8):  # reader 0 alone can never complete a round
            src.next_round(round_index=i)
    src.close()


def test_run_loop_checkpoint_carries_parallel_cursors(tmp_path):
    """End to end through run_loop with 2 readers: the checkpoint carries
    one cursor row PER READER, and the resumed run seeks all of them; a
    resume with a different reader count restarts at shard 0 (logged)."""
    from sparknet_tpu.apps.train_loop import train
    from sparknet_tpu.data.streaming import make_parallel_source
    from sparknet_tpu.utils import checkpoint as ckpt
    from sparknet_tpu.utils.config import RunConfig
    from sparknet_tpu.utils.logger import Logger
    from sparknet_tpu.zoo import lenet
    import jax

    root = str(tmp_path / "shards")
    label_path = imagenet.write_synthetic_shards(
        root, n_shards=4, per_shard=16, size=28, n_classes=10)
    n_local = jax.local_device_count()

    def make_source(n):
        return make_parallel_source(
            imagenet.list_shards(root), imagenet.load_label_map(label_path),
            n_local, 2, 2, n, height=28, width=28)

    def make_cfg(rounds):
        # health off: this trains a throwaway lenet on RAW 0-255 pixels (a
        # cursor-bookkeeping fixture, not a convergence run) — it diverges
        # violently by design, and the supervisor would (correctly) step in
        from sparknet_tpu.utils.health import HealthConfig
        return RunConfig(model="lenet", tau=2, local_batch=2,
                         max_rounds=rounds, workdir=str(tmp_path), seed=0,
                         eval_every=0, checkpoint_dir=str(tmp_path / "ck"),
                         checkpoint_every=2,
                         health=HealthConfig(enabled=False))

    class GrayTo28:
        def convert_batch(self, batch, train=True, rng=None):
            x = batch["data"].astype(np.float32).mean(axis=1)  # CHW->HW
            return {"data": x[..., None], "label": batch["label"]}

    spec = lenet(batch=2)
    train(make_cfg(2), spec, make_source(2), None,
          logger=Logger(str(tmp_path / "l1.txt"), echo=False),
          batch_transform=GrayTo28())
    _, _, extra = ckpt.restore_flat(str(tmp_path / "ck"))
    (host_rows,) = extra["stream"]
    assert len(host_rows) == 2  # one cursor row per reader

    train(make_cfg(4), spec, make_source(2), None,
          logger=Logger(str(tmp_path / "l2.txt"), echo=False),
          batch_transform=GrayTo28())
    text = open(str(tmp_path / "l2.txt")).read()
    assert "stream resumed at" in text
    for s, e, ep in host_rows:
        assert f"shard {s} entry {e}" in text

    # reader-count change: cursors refused, stream restarts at zero
    train(make_cfg(6), spec, make_source(4), None,
          logger=Logger(str(tmp_path / "l3.txt"), echo=False),
          batch_transform=GrayTo28())
    text = open(str(tmp_path / "l3.txt")).read()
    assert "restarting" in text and "stream resumed at" not in text


# -- C tar member index (r4: GIL-free local shard walk) ----------------------

def test_tar_index_matches_tarfile_path_exactly(tmp_path):
    """The C member index must reproduce the tarfile path bit for bit:
    same bytes, same labels, same cursor numbering (resume depends on it),
    including unlabeled-entry skips and mid-shard seeks."""
    from sparknet_tpu.data import jpeg_plane
    if not jpeg_plane.available():
        pytest.skip("native plane unavailable")
    loader_idx = _stream_fixture(tmp_path, n_shards=2, per_shard=8)
    loader_tar = _stream_fixture(tmp_path, n_shards=2, per_shard=8)
    # drop one label so the unlabeled-skip path is exercised
    victim = sorted(loader_idx.label_map)[3]
    del loader_idx.label_map[victim]
    del loader_tar.label_map[victim]
    for p in loader_tar.shard_paths:
        loader_tar._tar_indices[p] = None  # force the tarfile path
    a = [(img.tobytes(), lbl, pos)
         for img, lbl, pos in loader_idx.iter_with_pos()]
    b = [(img.tobytes(), lbl, pos)
         for img, lbl, pos in loader_tar.iter_with_pos()]
    assert a == b and len(a) == 15
    assert loader_idx.skipped == loader_tar.skipped == 1
    mid = a[5][2]
    c = [(img.tobytes(), lbl, pos) for img, lbl, pos
         in _stream_fixture(tmp_path, n_shards=2,
                            per_shard=8).iter_with_pos(mid)]
    # fixture labels differ (fresh loader keeps victim's label): compare
    # positions only for the seek check
    assert [x[2] for x in c][:5] == [x[2] for x in a[6:11]]


def test_tar_index_extension_headers_fall_back(tmp_path):
    """A GNU long-name member desynchronizes C-vs-tarfile numbering, so
    the indexer must refuse (None) and the loader silently use tarfile."""
    import io as _io
    import tarfile as _tarfile
    from PIL import Image
    from sparknet_tpu.data import jpeg_plane
    if not jpeg_plane.available():
        pytest.skip("native plane unavailable")
    root = tmp_path / "ln"
    root.mkdir()
    long_name = "x" * 120 + ".JPEG"  # > 100 chars: GNU 'L' header
    tar_path = str(root / "train.0000.tar")
    buf = _io.BytesIO()
    Image.fromarray(np.zeros((32, 32, 3), np.uint8)).save(buf, format="JPEG")
    data = buf.getvalue()
    with _tarfile.open(tar_path, "w", format=_tarfile.GNU_FORMAT) as tar:
        info = _tarfile.TarInfo(name=long_name)
        info.size = len(data)
        tar.addfile(info, _io.BytesIO(data))
    assert jpeg_plane.tar_index(tar_path) is None
    loader = imagenet.ShardedTarLoader(
        [tar_path], {long_name: 3}, height=32, width=32)
    images, labels = loader.load_all()
    assert len(images) == 1 and labels[0] == 3


def test_truncated_shard_fails_loudly(tmp_path):
    """A shard truncated mid-member (interrupted copy) must raise, not
    silently drop the tail: the C index refuses (last member extends past
    EOF) and the tarfile fallback then reports the corruption."""
    from sparknet_tpu.data import jpeg_plane
    if not jpeg_plane.available():
        pytest.skip("native plane unavailable")
    loader = _stream_fixture(tmp_path, n_shards=1, per_shard=8)
    path = loader.shard_paths[0]
    offsets, sizes, _, _ = jpeg_plane.tar_index(path)
    with open(path, "r+b") as f:
        # cut INTO the last member's data (tar pads archives with ~10KB of
        # trailing zero blocks, so an end-relative truncate misses)
        f.truncate(int(offsets[-1] + sizes[-1] // 2))
    with pytest.raises(jpeg_plane.TruncatedTarError):
        jpeg_plane.tar_index(path)
    with pytest.raises(Exception):  # surfaced, not swallowed
        loader.load_all()

    # truncation exactly AT a member boundary is the sneaky case: the
    # archive looks complete to a naive walk (and to Python's tarfile,
    # which iterates the partial archive silently) — the missing zero
    # end-of-archive block is the tell, and it must NOT fall back
    loader2 = _stream_fixture(tmp_path.joinpath("b"), n_shards=1,
                              per_shard=8)
    path2 = loader2.shard_paths[0]
    o2, s2, _, _ = jpeg_plane.tar_index(path2)
    with open(path2, "r+b") as f:
        f.truncate(int(o2[-1] + ((s2[-1] + 511) & ~511)))
    with pytest.raises(jpeg_plane.TruncatedTarError):
        jpeg_plane.tar_index(path2)
    with pytest.raises(jpeg_plane.TruncatedTarError):
        loader2.load_all()  # no silent tarfile fallback
    # the PURE-tarfile path (no native plane / extension archives) has its
    # own terminator check and must also refuse
    loader3 = imagenet.ShardedTarLoader([path2], loader2.label_map, 32, 32)
    loader3._tar_indices[path2] = None  # force the tarfile branch
    with pytest.raises(jpeg_plane.TruncatedTarError):
        loader3.load_all()


def test_streaming_sum_count_parallel_matches_serial(tmp_path):
    """The fanned-out mean pass is float64 partial sums over shard subsets
    — identical to the serial pass, any worker count."""
    from sparknet_tpu.data.streaming import streaming_sum_count
    serial = streaming_sum_count(_stream_fixture(tmp_path, n_shards=4))
    for w in (2, 3, 99):
        par = streaming_sum_count(_stream_fixture(tmp_path, n_shards=4),
                                  workers=w)
        assert par[1] == serial[1]
        np.testing.assert_array_equal(par[0], serial[0])
