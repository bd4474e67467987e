"""Everything a run draws from `--seed`, besides the weights (those are the
configuration's, see `configs/<config>.reference.py`): round stacks made on
the device, the host corpus of the cached loop, and the round keys.

The same seed gives the same rows; every row of every step differs. Any slice
of a stack (one step, one worker) can be made alone, so the reference never
needs the stack.
"""
from __future__ import annotations

import concurrent.futures

import numpy as np

_GOLD, _MIX1, _MIX2 = 0x9E3779B1, 0x85EBCA6B, 0xC2B2AE35


def _mix(h):
    """A cheap 32-bit mixer (two multiplies): the stack is the benchmark's own
    device work inside the window, so it has to cost next to nothing."""
    import jax.numpy as jnp
    h = h ^ (h >> 15)
    h = h * jnp.uint32(_MIX1)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(_MIX2)
    return h ^ (h >> 16)


def stack_slice(seed: int, round_index, t0, nt: int, row0, nr: int, *,
                global_batch: int, tau: int, crop: int, n_classes: int, dtype):
    """`nt` steps from step `t0` x `nr` rows from row `row0` of round
    `round_index`'s stack: data [nt, nr, crop, crop, 3] of mean-subtracted
    pixels (byte - 127.5, uniform, exact in bfloat16) in `dtype`, and labels
    [nt, nr, 1] int32. `round_index`, `t0` and `row0` may be traced scalars;
    the counts are static."""
    import jax.numpy as jnp
    from jax import lax

    u32 = lambda v: jnp.asarray(v).astype(jnp.uint32)
    t = lax.broadcasted_iota(jnp.uint32, (nt, nr), 0) + u32(t0)
    r = lax.broadcasted_iota(jnp.uint32, (nt, nr), 1) + u32(row0)
    rnd = u32(round_index)
    image = (rnd * jnp.uint32(tau) + t) * jnp.uint32(global_batch) + r
    h = _mix(image * jnp.uint32(_GOLD) + jnp.uint32(seed & 0xFFFFFFFF))
    label = (_mix(h + jnp.uint32(7)) % jnp.uint32(n_classes)).astype(jnp.int32)
    # made in its final shape: a reshape of the flat pixels would cost the
    # chip a relayout copy of the whole stack
    shape = (nt, nr, crop, crop, 3)
    y, x, c = (lax.broadcasted_iota(jnp.uint32, shape, d) for d in (2, 3, 4))
    pix = (y * jnp.uint32(crop) + x) * jnp.uint32(3) + c
    byte = _mix(h[:, :, None, None, None] ^ (pix * jnp.uint32(_MIX1))) >> 24
    data = (byte.astype(jnp.float32) - 127.5).astype(dtype)
    return data, label[:, :, None]


def round_key(seed: int, round_index: int):
    """The key a round's dropout masks come from. It is what `run_loop` hands
    `train_round` (`fold_in(PRNGKey(seed ^ 0xABCD), round)`), restated here so
    that both drivers and the reference use one."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed ^ 0xABCD), round_index)


def corpus(seed: int, n_images: int, size: int, n_classes: int,
           threads: int = 8):
    """The cached-loop cell's partition as the reference app holds it: uint8
    [n, 3, size, size] images and int32 [n, 1] labels in host memory. Made in
    `threads` blocks side by side (numpy's generators release the GIL);
    block b depends on (seed, b) only."""
    images = np.empty((n_images, 3, size, size), np.uint8)
    edges = np.linspace(0, n_images, threads + 1).astype(int)

    def fill(b):
        rng, per = np.random.default_rng((seed, b)), 3 * size * size
        for i in range(edges[b], edges[b + 1], 64):  # small pieces: numpy
            n = min(64, edges[b + 1] - i)            # draws wider than uint8
            images[i:i + n] = np.frombuffer(rng.bytes(n * per), np.uint8).reshape(
                n, 3, size, size)

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        list(pool.map(fill, range(threads)))
    labels = np.random.default_rng((seed, threads)).integers(
        0, n_classes, (n_images, 1)).astype(np.int32)
    return images, labels


def mean_image(seed: int, size: int) -> np.ndarray:
    """A float32 [3, size, size] mean image, as a deployment loads one."""
    return (np.random.default_rng((seed, 0xEA)).uniform(
        100.0, 150.0, (3, size, size))).astype(np.float32)
