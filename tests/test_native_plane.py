"""C++ data plane tests (skipped if the native lib can't build)."""
import io
import os

import numpy as np
import pytest

from sparknet_tpu.data import jpeg_plane

pytestmark = pytest.mark.skipif(not jpeg_plane.available(),
                                reason="native plane unavailable")


def test_library_is_named_by_its_source_and_this_host(tmp_path,
                                                       monkeypatch):
    """A binary is loaded only if it was built from the committed
    native/jpeg_plane.cpp + build.sh on THIS host: the name carries a hash
    of all three, so another machine's or revision's .so is never found."""
    import re
    import shutil
    path = jpeg_plane.so_path()
    assert re.fullmatch(r"libjpeg_plane-[0-9a-f]{16}\.so",
                        os.path.basename(path))
    assert jpeg_plane._load()._name == path  # what is loaded is that file
    with monkeypatch.context() as m:
        m.setattr(jpeg_plane, "_cpu_identity", lambda: b"another host")
        assert os.path.basename(jpeg_plane.so_path()) != \
            os.path.basename(path)
    edited = tmp_path / "native"  # the same sources, one byte more
    shutil.copytree(os.path.dirname(path), edited,
                    ignore=shutil.ignore_patterns("*.so"))
    with open(edited / "jpeg_plane.cpp", "ab") as f:
        f.write(b"\n")
    monkeypatch.setattr(jpeg_plane, "_NATIVE_DIR", str(edited))
    assert os.path.basename(jpeg_plane.so_path()) != os.path.basename(path)


def make_jpeg(arr):
    from PIL import Image
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="JPEG", quality=95)
    return buf.getvalue()


def test_decode_resize_matches_pil_on_smooth_image():
    y, x = np.mgrid[0:61, 0:83]
    arr = np.stack([(y * 2) % 256, (x * 3) % 256, (x + y) % 256],
                   -1).astype(np.uint8)
    data = make_jpeg(arr)
    got = jpeg_plane.decode_resize_chw(data, 48, 48)
    from sparknet_tpu.data.imagenet import _decode_pil
    ref = _decode_pil(data, 48, 48)
    assert got.shape == (3, 48, 48)
    assert np.abs(got.astype(int) - ref.astype(int)).mean() < 2.0


def test_decode_corrupt_raises():
    with pytest.raises(ValueError, match="decode failed"):
        jpeg_plane.decode_resize_chw(b"not a jpeg", 32, 32)


def test_batch_decode_flags_corrupt_entries():
    arr = np.zeros((40, 40, 3), np.uint8)
    good = make_jpeg(arr)
    imgs, ok = jpeg_plane.decode_resize_chw_batch(
        [good, good[: len(good) // 2], good, b""], 32, 32)
    assert ok.tolist() == [True, False, True, False]
    assert imgs.shape == (4, 3, 32, 32)
    np.testing.assert_array_equal(imgs[0], imgs[2])


def test_fused_crop_mean_nhwc_matches_numpy(rng):
    imgs = rng.integers(0, 256, (5, 3, 20, 24), dtype=np.uint8)
    mean = rng.standard_normal((3, 20, 24)).astype(np.float32)
    ys = np.array([0, 1, 2, 3, 4], np.int32)
    xs = np.array([4, 3, 2, 1, 0], np.int32)
    got = jpeg_plane.crop_mean_nhwc(imgs, mean, ys, xs, 16)
    for i in range(5):
        want = (imgs[i].astype(np.float32) - mean)[
            :, ys[i]:ys[i] + 16, xs[i]:xs[i] + 16].transpose(1, 2, 0)
        np.testing.assert_allclose(got[i], want, rtol=1e-6)


def test_fused_no_mean(rng):
    imgs = rng.integers(0, 256, (2, 3, 8, 8), dtype=np.uint8)
    got = jpeg_plane.crop_mean_nhwc(imgs, None, np.zeros(2, np.int32),
                                    np.zeros(2, np.int32), 8)
    np.testing.assert_array_equal(got[0],
                                  imgs[0].astype(np.float32).transpose(1, 2, 0))


def test_preprocessor_uses_fused_path(rng):
    """ImagePreprocessor with uint8 CHW input routes through the native
    kernel and matches the pure-numpy float path."""
    from sparknet_tpu.data.preprocess import ImagePreprocessor
    from sparknet_tpu.schema import Field, Schema
    schema = Schema(Field("data", "float32", (3, 10, 10)),
                    Field("label", "int32", (1,)))
    imgs = rng.integers(0, 256, (6, 3, 14, 14), dtype=np.uint8)
    mean = rng.standard_normal((3, 14, 14)).astype(np.float32)
    a = ImagePreprocessor(schema, mean_image=mean, crop=10, seed=7)
    b = ImagePreprocessor(schema, mean_image=mean, crop=10, seed=7)
    lab = np.zeros((6, 1))
    fused = a.convert_batch({"data": imgs, "label": lab}, train=True)
    plain = b.convert_batch({"data": imgs.astype(np.float32), "label": lab},
                            train=True)
    np.testing.assert_allclose(fused["data"], plain["data"], atol=1e-5)


def test_bf16_out_bit_identical_to_ml_dtypes(rng):
    """The bf16 emit path must match ml_dtypes' round-to-nearest-even cast
    BIT-for-bit — including NaN (a low-payload NaN must stay NaN, not carry
    into +/-Inf through the RNE add), Inf, and values that round up to Inf."""
    import ml_dtypes

    imgs = rng.integers(0, 256, (1, 1, 16, 16), dtype=np.uint8)
    mean = rng.standard_normal((1, 16, 16)).astype(np.float32) * 300
    # plant specials: out = u8 - mean, so mean=NaN -> NaN, mean=-Inf -> Inf,
    # mean near -f32max -> rounds to Inf, exact-tie mantissas for RNE
    mean.reshape(-1)[:6] = [np.nan, -np.inf, np.inf, -3.4e38, 3.4e38,
                            -2.00390625]
    got = jpeg_plane.crop_mean_nhwc(imgs, mean, np.zeros(1, np.int32),
                                    np.zeros(1, np.int32), 16,
                                    out_dtype="bfloat16")
    want = (imgs[0].astype(np.float32) - mean).transpose(1, 2, 0) \
        .astype(ml_dtypes.bfloat16)
    g16 = got[0].view(np.uint16)
    w16 = want.view(np.uint16)
    nan_g = np.isnan(got[0].astype(np.float32))
    nan_w = np.isnan(want.astype(np.float32))
    np.testing.assert_array_equal(nan_g, nan_w)  # NaN stays NaN
    # non-NaN lanes: exact bit identity (NaN payload bits may differ)
    np.testing.assert_array_equal(g16[~nan_g], w16[~nan_w])
