"""ctypes binding for the native C++ data plane (native/jpeg_plane.cpp).

Covers the reference's native-imaging role (JVM libjpeg via twelvemonkeys,
reference `preprocessing/ScaleAndConvert.scala`): JPEG decode + force-resize
+ planar CHW, plus a fused crop/mean-subtract/NHWC batch kernel.

The shared library is built with g++ on first use and named by a hash of
the two committed files it is built from (`native/jpeg_plane.cpp`,
`native/build.sh`) and of this host's CPU (`build.sh` compiles with
`-march=native`). So a binary is loaded only if it was built from the
committed source on the running host: a checkout copied from another
machine, or whose source changed, finds no library under its name and
rebuilds. `available()` gates all callers, with PIL/numpy fallbacks
elsewhere; a build that fails says so once, loudly.
"""
from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import platform
import subprocess
import warnings
from typing import Optional, Tuple

import numpy as np

_NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "native"))
_SOURCES = ("jpeg_plane.cpp", "build.sh")

_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _cpu_identity() -> bytes:
    """What `-march=native` keys on: the architecture and the first CPU's
    model and feature flags."""
    lines = [platform.machine()]
    try:
        with open("/proc/cpuinfo") as f:
            for ln in f:
                if ln.startswith(("model name", "flags", "Features")):
                    lines.append(ln.strip())
                elif not ln.strip():
                    break  # end of the first CPU's block
    except OSError:
        lines.append(platform.processor())
    return "\n".join(lines).encode()


def so_path() -> str:
    """The library's path for THIS source revision on THIS host."""
    h = hashlib.sha256(_cpu_identity())
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_NATIVE_DIR,
                        f"libjpeg_plane-{h.hexdigest()[:16]}.so")


def _build(path: str) -> None:
    """Run native/build.sh for `path`, then drop the libraries of other
    revisions/hosts. Raises on any failure."""
    p = subprocess.run(["sh", os.path.join(_NATIVE_DIR, "build.sh"), path],
                       capture_output=True, text=True, timeout=300)
    if p.returncode != 0:
        raise OSError(f"native/build.sh exited {p.returncode}: "
                      f"{p.stderr.strip()[-2000:]}")
    for old in glob.glob(os.path.join(_NATIVE_DIR, "libjpeg_plane*.so")):
        if old != path:
            os.remove(old)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None or _build_failed:
        return _lib
    try:
        path = so_path()
        if not os.path.exists(path):
            _build(path)
        lib = ctypes.CDLL(path)
    except (OSError, subprocess.SubprocessError) as e:
        _build_failed = True
        warnings.warn(
            f"NATIVE JPEG PLANE UNAVAILABLE — ingest falls back to the "
            f"PIL/numpy path (several times slower per core): {e}",
            RuntimeWarning, stacklevel=2)
        return None
    lib.jp_decode_resize_chw.restype = ctypes.c_int
    lib.jp_decode_resize_chw.argtypes = [
        ctypes.c_char_p, ctypes.c_long, ctypes.c_int, ctypes.c_int,
        ctypes.POINTER(ctypes.c_uint8)]
    lib.jp_decode_resize_chw_batch.restype = None
    lib.jp_decode_resize_chw_batch.argtypes = [
        ctypes.c_char_p, ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_long), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.POINTER(ctypes.c_uint8),
        ctypes.POINTER(ctypes.c_int)]
    crop_args = [
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_int, ctypes.c_int,
        ctypes.c_int, ctypes.c_int, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int), ctypes.POINTER(ctypes.c_int),
        ctypes.c_int]
    lib.jp_crop_mean_nhwc.restype = None
    lib.jp_crop_mean_nhwc.argtypes = crop_args + [
        ctypes.POINTER(ctypes.c_float)]
    lib.jp_crop_mean_nhwc_bf16.restype = None
    lib.jp_crop_mean_nhwc_bf16.argtypes = crop_args + [
        ctypes.POINTER(ctypes.c_uint16)]
    lib.jp_tar_index.restype = ctypes.c_long
    lib.jp_tar_index.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
        ctypes.POINTER(ctypes.c_uint8), ctypes.c_char_p, ctypes.c_long]
    _lib = lib
    return _lib


def available() -> bool:
    return _load() is not None


def decode_resize_chw(data: bytes, height: int, width: int) -> np.ndarray:
    """One JPEG -> CHW uint8 at (height, width). Raises ValueError on corrupt
    input (same contract as the PIL fallback)."""
    lib = _load()
    assert lib is not None, "native plane unavailable"
    out = np.empty((3, height, width), dtype=np.uint8)
    rc = lib.jp_decode_resize_chw(
        data, len(data), height, width,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    if rc != 0:
        raise ValueError(f"jpeg decode failed (rc={rc})")
    return out


def decode_resize_chw_batch(jpegs: list, height: int, width: int
                            ) -> Tuple[np.ndarray, np.ndarray]:
    """Parallel batch decode. Returns (images (N,3,H,W) uint8, ok (N,) bool);
    corrupt entries have ok=False and undefined pixels."""
    lib = _load()
    assert lib is not None, "native plane unavailable"
    n = len(jpegs)
    blob = b"".join(jpegs)
    offsets = np.zeros(n, dtype=np.int64)
    lengths = np.array([len(j) for j in jpegs], dtype=np.int64)
    np.cumsum(lengths[:-1], out=offsets[1:])
    out = np.empty((n, 3, height, width), dtype=np.uint8)
    ok = np.zeros(n, dtype=np.int32)
    lib.jp_decode_resize_chw_batch(
        blob, offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        lengths.ctypes.data_as(ctypes.POINTER(ctypes.c_long)), n, height,
        width, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_int)))
    return out, ok == 0


def crop_mean_nhwc(images_chw_u8: np.ndarray,
                   mean_chw: Optional[np.ndarray],
                   ys: np.ndarray, xs: np.ndarray, crop: int,
                   out_dtype: str = "float32") -> np.ndarray:
    """Fused mean-subtract + crop + NHWC for a CHW uint8 batch.
    out_dtype 'bfloat16' writes device-ready bf16 straight from the
    OpenMP loop (round-to-nearest-even, bit-identical to ml_dtypes'
    cast) — the training apps feed bf16, so emitting f32 then casting
    on the single-threaded prefetch path was ~19% of the whole ingest
    pipeline (bench.py --e2e, r3)."""
    lib = _load()
    assert lib is not None, "native plane unavailable"
    images_chw_u8 = np.ascontiguousarray(images_chw_u8, dtype=np.uint8)
    n, c, h, w = images_chw_u8.shape
    ys = np.ascontiguousarray(ys, dtype=np.int32)
    xs = np.ascontiguousarray(xs, dtype=np.int32)
    mean_ptr = None
    if mean_chw is not None:
        mean_chw = np.ascontiguousarray(mean_chw, dtype=np.float32)
        assert mean_chw.shape == (c, h, w), (mean_chw.shape, (c, h, w))
        mean_ptr = mean_chw.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    args = (images_chw_u8.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
            n, c, h, w, mean_ptr,
            ys.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
            xs.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), crop)
    if out_dtype == "bfloat16":
        import ml_dtypes
        out = np.empty((n, crop, crop, c), dtype=ml_dtypes.bfloat16)
        lib.jp_crop_mean_nhwc_bf16(
            *args, out.view(np.uint16).ctypes.data_as(
                ctypes.POINTER(ctypes.c_uint16)))
        return out
    assert out_dtype == "float32", out_dtype
    out = np.empty((n, crop, crop, c), dtype=np.float32)
    lib.jp_crop_mean_nhwc(
        *args, out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return out


class TruncatedTarError(OSError):
    """A shard is missing data (truncated mid-member or missing the tar
    end-of-archive terminator). Distinct from plain OSError so callers
    that fall back to tarfile on INDEXING problems still surface this
    loudly — Python's tarfile iterates a boundary-truncated archive
    silently, so falling back would train on partial data."""


def tar_index(path: str, name_cap: int = 128):
    """Parse a local tar's member table in C (no GIL-held Python walk):
    returns (data_offsets int64[n], sizes int64[n], isfile bool[n],
    basenames list[str]) with member numbering identical to Python
    tarfile iteration, or None when the archive uses extension headers
    (GNU long names / pax) — callers fall back to tarfile."""
    lib = _load()
    assert lib is not None, "native plane unavailable"
    max_n = max(64, os.path.getsize(path) // 512 // 2 + 2)
    offsets = np.zeros(max_n, dtype=np.int64)
    sizes = np.zeros(max_n, dtype=np.int64)
    isfile = np.zeros(max_n, dtype=np.uint8)
    names = np.zeros(max_n * name_cap, dtype=np.uint8)
    n = lib.jp_tar_index(
        path.encode(), max_n,
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_long)),
        isfile.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        names.ctypes.data_as(ctypes.c_char_p), name_cap)
    if n == -1:
        return None  # extension headers: numbering would diverge
    if n == -4:
        raise TruncatedTarError(
            f"tar {path!r} ended without the zero end-of-archive block — "
            f"truncated at a member boundary?")
    if n < 0:
        raise OSError(f"tar index of {path!r} failed (rc={n})")
    if n and int(offsets[n - 1] + sizes[n - 1]) > os.path.getsize(path):
        # truncated archive: fseek past EOF "succeeds", so the C walk can
        # index members whose data is missing
        raise TruncatedTarError(
            f"tar {path!r} is truncated (last member extends past EOF)")
    name_list = [bytes(names[i * name_cap:(i + 1) * name_cap]
                       ).split(b"\0", 1)[0].decode("utf-8", "replace")
                 for i in range(n)]
    return offsets[:n], sizes[:n], isfile[:n].astype(bool), name_list
