"""Batched row gather: out[b, w, :] = stats[b, idx[b, w], :].

The MCTS descent reads W tree rows per game per level
(`mcts/search.py:_descend_wave`). Three interchangeable lowerings:

- "einsum": one-hot matmul `(B,W,N) x (B,N,K)` — rides the MXU, burns
  2*W*N*K FLOPs per game per level but avoids TPU gather lowerings.
- "pallas": a Pallas kernel that DMAs each game's stat block into VMEM
  once and copies the W selected rows — same HBM traffic as the
  einsum's stat read, zero MXU work (this file).
- "take": `jnp.take_along_axis` — XLA's native gather lowering.

All three are numerically exact row selects (the einsum uses HIGHEST
precision, f32 row-select is exact), so parity tests pin them against
each other; `MCTSConfig.descent_gather` selects the implementation and
benchmarking on real hardware decides the default.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import f32_block_bytes, vmem_params


def gather_rows_einsum(stats: jax.Array, idx: jax.Array) -> jax.Array:
    """(B, N, K), (B, W) int32 -> (B, W, K) via one-hot matmul."""
    n = stats.shape[1]
    onehot = (idx[..., None] == jnp.arange(n, dtype=idx.dtype)).astype(
        stats.dtype
    )
    return jnp.einsum(
        "bwn,bnk->bwk", onehot, stats, precision=jax.lax.Precision.HIGHEST
    )


def gather_rows_take(stats: jax.Array, idx: jax.Array) -> jax.Array:
    """(B, N, K), (B, W) -> (B, W, K) via XLA gather."""
    return jnp.take_along_axis(stats, idx[..., None], axis=1)


def _gather_kernel(idx_ref, stats_ref, out_ref):
    """One grid program per game: copy W dynamically-indexed rows."""
    w = out_ref.shape[1]
    for j in range(w):  # static unroll; W is small (<= wave size)
        row = idx_ref[0, 0, j]
        out_ref[0, pl.ds(j, 1), :] = stats_ref[0, pl.ds(row, 1), :]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows_pallas(
    stats: jax.Array, idx: jax.Array, interpret: bool = False
) -> jax.Array:
    """(B, N, K), (B, W) -> (B, W, K) with a per-game VMEM-block kernel.

    Each program streams its game's (N, K) stat block HBM->VMEM once
    (what the einsum also reads) and emits the W selected rows without
    touching the MXU. `interpret=True` runs the kernel in the Pallas
    interpreter (CPU tests).
    """
    b, n, k = stats.shape
    w = idx.shape[1]
    return pl.pallas_call(
        _gather_kernel,
        grid=(b,),
        in_specs=[
            # (B, 1, W): the TPU lowering wants a block's last two
            # dims to be whole array dims (or multiples of 8 x 128).
            pl.BlockSpec(
                (1, 1, w),
                lambda i: (i, 0, 0),
                memory_space=pltpu.SMEM,
            ),
            pl.BlockSpec(
                (1, n, k),
                lambda i: (i, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, w, k),
            lambda i: (i, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b, w, k), stats.dtype),
        compiler_params=vmem_params(
            f32_block_bytes(n, k) + f32_block_bytes(w, k)
        ),
        interpret=interpret,
        name="gather_rows",
    )(idx.astype(jnp.int32).reshape(b, 1, w), stats)


def gather_rows(
    stats: jax.Array, idx: jax.Array, mode: str = "einsum"
) -> jax.Array:
    """Dispatch by mode ("einsum" | "pallas" | "take")."""
    if mode == "einsum":
        return gather_rows_einsum(stats, idx)
    if mode == "pallas":
        # Compiled on a TPU backend, interpreted everywhere else (CPU
        # tests).
        interpret = jax.default_backend() != "tpu"
        return gather_rows_pallas(stats, idx, interpret=interpret)
    if mode == "take":
        return gather_rows_take(stats, idx)
    raise ValueError(f"unknown gather mode: {mode!r}")
