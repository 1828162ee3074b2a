"""Replay rows made from the seed, one row a key.

Row `i` of a run is a function of (`--seed`, `i`) alone, so the ring is
filled block by block on the device and the reference makes again just
the rows a batch sampled, by their slot numbers, without the ring.

A row is what self-play writes (`rl/device_buffer.py`): a board plane of
{-1 death, 0 empty, 1 occupied}, the other features in [0, 1), a policy
target that is a distribution over a random set of valid actions, an
n-step return inside the C51 support, and policy weight 1 (the preset
does not record fast-search rows). No two rows are alike.

The older half of the ring differs from the newer half, as the rows of
a ring do that self-play filled while its policy moved: returns about
-4 in the older half and about +4 in the newer, policy targets on the
lower half of the action space in the older half and on the upper in
the newer. The ring's sampler is stratified: row j of a batch comes from
the j-th slice of the ring, so the two halves of a batch differ as the
two halves of the ring do, and a step that leaves half of its batch out
shows in its gradient on every seed, not only where the draw happens to
make the halves differ (with rows drawn alike everywhere such a step
read within a sound run's rounding on one seed of six).
"""

import jax
import jax.numpy as jnp

from .reference_env import death_mask


def seed_key(seed: int):
    """A key from any whole number up to 2**63: `--seed` passes 2**31."""
    seed = int(seed)
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def make_rows(
    key, index, env: dict, other_dim: int, action_dim: int, span: int
) -> dict:
    """Rows `index` ((N,) int32) of the run whose key is `key`, in a
    ring of `span` rows."""
    death = jnp.asarray(death_mask(env))

    def one(i):
        k = jax.random.split(jax.random.fold_in(key, i), 5)
        occupied = jax.random.bernoulli(k[0], 0.35, death.shape)
        grid = jnp.where(death, -1.0, occupied.astype(jnp.float32))[None]
        other = jax.random.uniform(k[1], (other_dim,))
        newer = i >= span // 2
        own = (jnp.arange(action_dim) >= action_dim // 2) == newer
        own_one = i % (action_dim // 2) + jnp.where(newer, action_dim // 2, 0)
        valid = jax.random.bernoulli(k[2], 0.3, (action_dim,)) & own
        valid = valid.at[own_one].set(True)
        logits = jnp.where(
            valid, 2.0 * jax.random.normal(k[3], (action_dim,)), -jnp.inf
        )
        return {
            "grid": grid,
            "other": other,
            "policy": jax.nn.softmax(logits),
            "ret": jax.random.uniform(k[4], (), minval=-2.0, maxval=2.0)
            + jnp.where(newer, 4.0, -4.0),
            "pw": jnp.float32(1.0),
        }

    return jax.vmap(one)(index.astype(jnp.int32))
