"""EVA attention's own parts (Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542, as EvaByte simplifies it for causal byte-level
modelling): the mask that sets an exact causal window beside the summaries of
the chunks before it, and the summaries themselves.

A row of `positions` keys is followed by one summary of every `chunk`
positions: key column j < positions is position j's own key, column
positions + c is chunk c's summary. Position i reads, under one softmax,
the keys of its own aligned window of `window` positions up to itself and
the summaries of the chunks that lie in the windows before its own -- so
every earlier position is read once, exactly or through its chunk.

The summaries are memory-bound work beside the compute-bound products: a
softmax over each chunk's positions and two weighted sums, float32, one pass
over k and v forward and (autodiff's) one backward. The attention core
itself is `model.seq_layers.attention_core` under this mask.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class WindowSummaryMask:
    """Which key columns a query reads: a function of (query positions, key
    columns) that broadcasts, over numpy arrays (a table made ahead) and
    over traced ones (inside a kernel) alike; hashable, so a kernel built
    for it is built once."""

    positions: int
    window: int
    chunk: int

    def __post_init__(self):
        if self.positions % self.window or self.window % self.chunk:
            raise ValueError(
                f"{self.positions} positions are no whole windows of "
                f"{self.window}, or a window no whole chunks of {self.chunk}: "
                f"partial windows and chunks are not built")

    @property
    def shape(self):
        """(queries, key columns)."""
        return (self.positions, self.positions + self.positions // self.chunk)

    def __call__(self, q_ids, kv_ids):
        q_window = q_ids // self.window
        own = (kv_ids // self.window == q_window) & (kv_ids <= q_ids)
        chunk_window = (kv_ids - self.positions) // (self.window // self.chunk)
        is_key = kv_ids < self.positions
        return (is_key & own) | (~is_key & (chunk_window < q_window))

    def dense(self):
        """[queries, key columns] bool, for the exact path."""
        n, n_kv = self.shape
        return self(np.arange(n)[:, None], np.arange(n_kv)[None, :])


def chunk_summaries(k, v, mu, phi, chunk: int):
    """(summary keys, summary values), each [rows, heads, positions / chunk,
    d] in k's dtype, of k, v [rows, heads, positions, d] and the learned mu,
    phi [heads, d]: chunk c's key is the mean of its keys + mu; its value is
    sum_j a_j v_j with a = softmax over the chunk's positions of
    phi . k_j / sqrt(d). Float32 throughout, on the vector unit (products
    and sums written out: no matrix unit rounds an operand)."""
    r, h, n, d = k.shape
    chunked = lambda t: t.astype(jnp.float32).reshape(r, h, n // chunk, chunk, d)
    kc, vc = chunked(k), chunked(v)
    logits = jnp.sum(kc * phi.astype(jnp.float32)[None, :, None, None, :],
                     axis=-1) / np.sqrt(d)
    a = jax.nn.softmax(logits, axis=-1)
    v_s = jnp.sum(a[..., None] * vc, axis=3)
    k_s = jnp.mean(kc, axis=3) + mu.astype(jnp.float32)[None, :, None, :]
    return k_s.astype(k.dtype), v_s.astype(v.dtype)
