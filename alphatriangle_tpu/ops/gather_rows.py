"""Batched row gather: out[b, w, :] = stats[b, idx[b, w], :].

The MCTS descent reads W tree rows per game per level
(`mcts/search.py:_descend_wave`) as a one-hot matmul `(B,W,N) x
(B,N,K)`: it rides the MXU, burns 2*W*N*K FLOPs per game per level and
avoids TPU gather lowerings. HIGHEST precision keeps the f32 row select
exact (tests/test_ops.py holds it to numpy's indexing).
"""

import jax
import jax.numpy as jnp


def gather_rows(stats: jax.Array, idx: jax.Array) -> jax.Array:
    """(B, N, K), (B, W) int32 -> (B, W, K) via one-hot matmul."""
    n = stats.shape[1]
    onehot = (idx[..., None] == jnp.arange(n, dtype=idx.dtype)).astype(
        stats.dtype
    )
    return jnp.einsum(
        "bwn,bnk->bwk", onehot, stats, precision=jax.lax.Precision.HIGHEST
    )
