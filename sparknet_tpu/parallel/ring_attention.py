"""Sequence/context parallelism: ring attention + all-to-all (Ulysses) forms.

Long sequences are sharded across a mesh axis ("seq"); two standard TPU
strategies are provided (absent from the reference — SURVEY §5.7 — but
first-class here):

  - **ring attention** (`ring_attention`): KV shards rotate around the ring
    via `lax.ppermute` while each device's Q shard accumulates attention
    with a stable online softmax (flash-style running max/denominator).
    Communication rides the ICI ring; memory per device is O(L/n), enabling
    contexts n× longer than a single chip could hold.

  - **Ulysses / all-to-all** (`ulysses_attention`): `lax.all_to_all` swaps
    sequence sharding for head sharding, runs exact local attention over the
    full sequence per head group, and swaps back. Cheaper at moderate L when
    heads ≥ mesh axis size.

Both are written against a mesh axis name and run inside `shard_map`;
`make_ring_attention(mesh)` wraps one for host-level convenience.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .mesh import shard_map

from ..ops.attention import (block_accumulate, finalize_accumulator,
                             init_accumulator)

SEQ_AXIS = "seq"


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   axis_name: str = SEQ_AXIS,
                   causal: bool = False) -> jnp.ndarray:
    """Runs INSIDE shard_map. Per-device shapes [B, L/n, H, D] (seq-sharded).

    Device i initially holds KV shard i; after step t it holds shard
    (i - t) mod n — offsets for causal masking are derived from that.
    """
    n = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    lq = q.shape[1]
    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(carry, t):
        o, m, l, k_cur, v_cur = carry
        src = (me - t) % n  # whose shard we hold at step t
        o, m, l = block_accumulate(o, m, l, q, k_cur, v_cur,
                                   k_offset=src * lq, q_offset=me * lq,
                                   causal=causal)
        # rotate AFTER use; skipping the final rotate would save one hop but
        # make the carry shape conditional — XLA overlaps this with compute.
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o, m, l, k_nxt, v_nxt), None

    o, m, l = init_accumulator(q.shape)
    # zeros/full constants are replicated; mark them device-varying so the
    # scan carry type matches the per-device accumulation results
    o, m, l = lax.pcast((o, m, l), (axis_name,), to="varying")
    (o, m, l, _, _), _ = lax.scan(body, (o, m, l, k, v), jnp.arange(n))
    return finalize_accumulator(o, m, l, q.dtype)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      axis_name: str = SEQ_AXIS,
                      causal: bool = False) -> jnp.ndarray:
    """Runs INSIDE shard_map. Per-device [B, L/n, H, D] with H % n == 0.

    all_to_all: seq-sharded -> head-sharded (full L per device, H/n heads),
    exact attention locally, then back.
    """
    from ..ops.attention import attention
    n = lax.axis_size(axis_name)
    assert q.shape[2] % n == 0, (
        f"heads {q.shape[2]} not divisible by seq-axis size {n}")
    # [B, L/n, H, D] -> gather seq, scatter heads -> [B, L, H/n, D]
    def a2a(x, concat, split):
        return lax.all_to_all(x, axis_name, split_axis=split,
                              concat_axis=concat, tiled=True)
    qh = a2a(q, 1, 2)
    kh = a2a(k, 1, 2)
    vh = a2a(v, 1, 2)
    oh = attention(qh, kh, vh, causal=causal)
    return a2a(oh, 2, 1)


def make_ring_attention(mesh: Mesh, *, axis_name: str = SEQ_AXIS,
                        causal: bool = False, impl: str = "ring"):
    """Host-level wrapper: takes GLOBAL [B, L, H, D] arrays sharded (or
    shardable) over `axis_name` on the length dim; returns global output."""
    fn = ring_attention if impl == "ring" else ulysses_attention
    inner = functools.partial(fn, axis_name=axis_name, causal=causal)
    spec = P(None, axis_name, None, None)
    mapped = jax.jit(shard_map(
        lambda q, k, v: inner(q, k, v),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))

    def apply(q, k, v):
        sharding = NamedSharding(mesh, spec)
        return mapped(jax.device_put(q, sharding),
                      jax.device_put(k, sharding),
                      jax.device_put(v, sharding))

    return apply
