"""Stratified proportional PER draw: idx[s, j] ~ priorities / total.

The megastep samples its K learner batches on device with an
inclusive-cumsum + stratified searchsorted over the priority array
(rl/megastep.py `_sample_indices`, rl/sharded_device_buffer.py
`sample_local`) — the vectorized equivalent of the host SumTree's
stratified descent. The index search is `jnp.searchsorted(cum, u)`,
XLA's native binary-search lowering over the (cap,) cumsum.
"""

import jax
import jax.numpy as jnp


def per_sample(
    priorities: jax.Array,
    cap: int,
    k: int,
    b: int,
    key: jax.Array,
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
    idx = jnp.searchsorted(cum, u).astype(jnp.int32)
    idx = jnp.clip(idx, 0, cap - 1).astype(jnp.int32)
    probs = jnp.maximum(priorities[idx], 1e-12) / jnp.maximum(total, 1e-12)
    return idx, probs
