"""Stratified proportional PER draw: idx[s, j] ~ priorities / total.

The megastep samples its K learner batches on device with an
inclusive-cumsum + stratified searchsorted over the priority array
(rl/megastep.py `_sample_indices`, rl/sharded_device_buffer.py
`sample_local`) — the vectorized equivalent of the host SumTree's
stratified descent. Two interchangeable lowerings for the index
search:

- "xla": `jnp.searchsorted(cum, u)` — XLA's native binary-search
  lowering over the (cap,) cumsum.
- "pallas": a Pallas kernel computing the identical quantity through
  the exact identity `searchsorted(cum, u, side="left") ==
  #{i : cum[i] < u}` — one grid program per step row streams the
  cumsum through VMEM in lane-width tiles and counts elements below
  each stratum draw (this file). Float compares are exact, so the two
  lowerings agree bit-for-bit.

The cumsum and the stratum draws themselves are computed ONCE in the
shared wrapper (not per lowering): strata boundaries depend on
f32 summation order, so sharing the prefix-sum is what makes the
index parity exact by construction rather than tolerance-based.

`TrainConfig.PER_SAMPLE_BACKEND` selects the lowering; parity tests
pin them against each other (tests/test_ops.py) and benchmarking on
real hardware decides the default.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Cumsum tile streamed per inner step: lane-width multiple so the
# (b, _TILE) compare block stays small regardless of ring capacity.
_TILE = 512


def count_below_xla(cum: jax.Array, u: jax.Array) -> jax.Array:
    """(n,) sorted, (k, b) -> (k, b) int32 first-index-not-less-than."""
    return jnp.searchsorted(cum, u).astype(jnp.int32)


def _count_below_kernel(cum_ref, u_ref, out_ref):
    """One grid program per step row: out[j] = #{i : cum[i] < u[j]}.

    Every value stays 2-D for the TPU: the b draws sit on sublanes as a
    (b, 1) column, each cumsum tile on lanes as a (1, _TILE) row, and
    the count is a lane reduction of their (b, _TILE) compare."""
    b = u_ref.shape[1]
    u = u_ref[0]  # (b, 1)

    def tile(t, acc):
        seg = cum_ref[pl.ds(t, 1), :]  # (1, _TILE)
        return acc + jnp.sum(
            (seg < u).astype(jnp.int32), axis=1, keepdims=True
        )

    out_ref[0] = jax.lax.fori_loop(
        0, cum_ref.shape[0], tile, jnp.zeros((b, 1), jnp.int32)
    )


@functools.partial(jax.jit, static_argnames=("interpret",))
def count_below_pallas(
    cum: jax.Array, u: jax.Array, interpret: bool = False
) -> jax.Array:
    """(n,) sorted, (k, b) -> (k, b) int32 via a tiled compare-count.

    The cumsum is padded with +inf to a tile multiple (inf < u is
    always False, so padding contributes zero), folded to
    (n_pad / _TILE, _TILE) and kept whole in VMEM; each program handles
    one step row's b strata. `interpret=True` runs the kernel in the
    Pallas interpreter (CPU tests).
    """
    n = cum.shape[0]
    k, b = u.shape
    tiles = -(-n // _TILE)
    cum_p = jnp.pad(
        cum, (0, tiles * _TILE - n), constant_values=jnp.inf
    ).reshape(tiles, _TILE)
    # (k, b, 1): the TPU lowering wants a block's last two dims to be
    # whole array dims (or multiples of 8 x 128).
    row = pl.BlockSpec(
        (1, b, 1), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    return pl.pallas_call(
        _count_below_kernel,
        grid=(k,),
        in_specs=[
            pl.BlockSpec(
                (tiles, _TILE), lambda i: (0, 0), memory_space=pltpu.VMEM
            ),
            row,
        ],
        out_specs=row,
        out_shape=jax.ShapeDtypeStruct((k, b, 1), jnp.int32),
        interpret=interpret,
        name="per_count_below",
    )(cum_p, u.reshape(k, b, 1)).reshape(k, b)


def per_sample(
    priorities: jax.Array,
    cap: int,
    k: int,
    b: int,
    key: jax.Array,
    mode: str = "xla",
) -> tuple[jax.Array, jax.Array]:
    """Stratified proportional draw of (k, b) slots from
    `priorities[:cap]`; returns (idx int32, probs f32).

    Stratum j of step row s draws uniformly from
    [j/b * total, (j+1)/b * total) — zero-priority (empty/trash) slots
    have empty cumsum segments and are never selected. Importance
    weights stay at the call sites (beta annealing and normalization
    scope differ between the single-device and dp-sharded paths).
    """
    cum = jnp.cumsum(priorities[:cap])
    total = cum[-1]
    u = (
        (
            jnp.arange(b, dtype=jnp.float32)[None, :]
            + jax.random.uniform(key, (k, b))
        )
        / b
        * total
    )
    if mode == "xla":
        idx = count_below_xla(cum, u)
    elif mode == "pallas":
        # Compiled on a TPU backend, interpreted everywhere else (CPU
        # tests).
        interpret = jax.default_backend() != "tpu"
        idx = count_below_pallas(cum, u, interpret=interpret)
    else:
        raise ValueError(f"unknown PER sample mode: {mode!r}")
    idx = jnp.clip(idx, 0, cap - 1).astype(jnp.int32)
    probs = jnp.maximum(priorities[idx], 1e-12) / jnp.maximum(total, 1e-12)
    return idx, probs
