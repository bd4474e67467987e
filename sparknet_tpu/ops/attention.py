"""Attention ops: single-device reference + building blocks.

The reference framework predates attention entirely (SURVEY §5.7: CNNs/MLPs
only, RNNs unrealized roadmap). This module exists because long-context is
first-class in the new framework: `sparknet_tpu.parallel.ring_attention`
shards sequences across the mesh; this file provides the exact-math
single-device implementation those kernels are verified against, plus a
stable online-softmax block accumulator shared by the ring pass.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import precision


@dataclasses.dataclass(frozen=True)
class SlidingWindowMask:
    """Which keys a query reads under a sliding window: its own position and
    the `window` - 1 before it. A function of (query positions, key columns)
    that broadcasts, over numpy arrays (a table made ahead) and over traced
    ones (inside a kernel) alike; hashable, so a kernel built for it is
    built once (as `ops.eva.WindowSummaryMask`, and used as it is)."""

    positions: int
    window: int

    @property
    def shape(self):
        """(queries, key columns)."""
        return (self.positions, self.positions)

    def __call__(self, q_ids, kv_ids):
        return (kv_ids <= q_ids) & (q_ids - kv_ids < self.window)

    def dense(self):
        """[queries, key columns] bool, for the exact path."""
        n = np.arange(self.positions)
        return self(n[:, None], n[None, :])


def attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
              causal: bool = False,
              bias: Optional[jnp.ndarray] = None,
              scale: Optional[float] = None) -> jnp.ndarray:
    """Exact multi-head attention. Shapes [B, L, H, D] (length-major).
    The scores are divided by sqrt(D), or multiplied by `scale` where one
    is given (1.0: q comes scaled)."""
    s = jnp.einsum("blhd,bmhd->bhlm", precision.cast_in(q),
                   precision.cast_in(k),
                   precision=precision.matmul_precision())
    s = s / np.sqrt(q.shape[-1]) if scale is None else s * scale
    s = s.astype(jnp.float32)
    if bias is not None:
        s = s + bias
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhlm,bmhd->blhd", p.astype(v.dtype),
                      precision.cast_in(v),
                      precision=precision.matmul_precision())


def block_accumulate(o, m, l, q, k_blk, v_blk, k_offset: jnp.ndarray,
                     q_offset: jnp.ndarray, causal: bool):
    """One online-softmax accumulation step against a KV block.

    Running state: o [B,Lq,H,D] (unnormalized), m [B,H,Lq] (running max),
    l [B,H,Lq] (running denominator). Offsets are the GLOBAL positions of
    q[0] / k_blk[0] — used for causal masking across shards.
    Returns updated (o, m, l).
    """
    d = q.shape[-1]
    s = jnp.einsum("blhd,bmhd->bhlm", precision.cast_in(q),
                   precision.cast_in(k_blk),
                   precision=precision.matmul_precision()) / np.sqrt(d)
    s = s.astype(jnp.float32)
    if causal:
        lq, lk = s.shape[-2], s.shape[-1]
        qpos = q_offset + jnp.arange(lq)
        kpos = k_offset + jnp.arange(lk)
        s = jnp.where(qpos[:, None] >= kpos[None, :], s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # fully-masked rows: keep m finite so exp() stays defined
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - m_safe[..., None])
    corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhlm,bmhd->blhd", p.astype(v_blk.dtype),
                    precision.cast_in(v_blk),
                    precision=precision.matmul_precision()).astype(jnp.float32)
    o_new = o * jnp.transpose(corr, (0, 2, 1))[..., None] + pv
    return o_new, m_new, l_new


def init_accumulator(q_shape: Tuple[int, ...]):
    b, lq, h, d = q_shape
    o = jnp.zeros((b, lq, h, d), jnp.float32)
    m = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, lq), jnp.float32)
    return o, m, l


def finalize_accumulator(o, m, l, out_dtype):
    denom = jnp.transpose(jnp.where(l == 0.0, 1.0, l), (0, 2, 1))[..., None]
    return (o / denom).astype(out_dtype)
