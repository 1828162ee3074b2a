"""Fused MCTS edge-plane update: insertion + discounted backup.

`mcts/search.py:_wave` ends every wave with a round of (B, W)-sized
scatter updates into the (B, N, A) edge planes: child-slot insertion
(`children.at[].max`, `e_reward.at[].set`) followed by max_depth
rounds of visit/value scatter-adds along the recorded descent paths.
XLA lowers each `.at[]` op as its own scatter over the full plane —
2*depth+2 passes over (B, N, A) HBM per wave. Two interchangeable
lowerings:

- "xla": the scatter chain exactly as `_wave` originally spelled it
  (this is the reference lowering — bit-identical to the pre-kernel
  code by construction).
- "pallas": ONE kernel pass per game. Each grid program keeps its
  game's four edge planes in VMEM, applies the W insertions and the
  W x depth backup updates as sequential one-hot row
  read-modify-writes, and emits the updated planes (this file). The
  per-(level, member) update order matches XLA:CPU's scatter order, so
  duplicate-edge accumulation associates identically there; on a TPU
  XLA orders a scatter-add's duplicates its own way and the value sums
  agree to f32 rounding (docs/KERNELS.md).

`MCTSConfig.backup_update` selects the lowering; parity tests pin
them against each other on CPU interpret mode, including a
fixed-seed self-play chunk (tests/test_ops.py).

Shapes: planes (B, N, A) f32; `parents`/`actions`/`new_child`/
`rewards` (B, W); `rec_node`/`rec_action`/`rec_active`/`returns`
(B, W, D). `new_child` is the pre-computed insertion value
`where(is_new, slot_id, -1.0)` and `returns[:, :, lvl]` the
discounted suffix return at level lvl, so both lowerings are pure
scatter math over identical operands.
"""

import functools

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import f32_block_bytes, vmem_params


def backup_update_xla(
    e_visits: jax.Array,
    e_value: jax.Array,
    children: jax.Array,
    e_reward: jax.Array,
    parents: jax.Array,
    actions: jax.Array,
    new_child: jax.Array,
    rewards: jax.Array,
    rec_node: jax.Array,
    rec_action: jax.Array,
    rec_active: jax.Array,
    returns: jax.Array,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """The original `_wave` scatter chain, verbatim."""
    batch = e_visits.shape[0]
    depth = rec_node.shape[-1]
    bcol = jnp.arange(batch)[:, None]
    children = children.at[bcol, parents, actions].max(new_child)
    e_reward = e_reward.at[bcol, parents, actions].set(rewards)
    for lvl in range(depth):
        act_mask = rec_active[:, :, lvl]
        nd = jnp.maximum(rec_node[:, :, lvl], 0)
        ac = jnp.maximum(rec_action[:, :, lvl], 0)
        e_visits = e_visits.at[bcol, nd, ac].add(
            act_mask.astype(jnp.float32)
        )
        e_value = e_value.at[bcol, nd, ac].add(
            jnp.where(act_mask, returns[:, :, lvl], 0.0)
        )
    return e_visits, e_value, children, e_reward


def _backup_kernel(
    parents_ref,
    actions_ref,
    new_child_ref,
    rewards_ref,
    rec_node_ref,
    rec_action_ref,
    rec_active_ref,
    returns_ref,
    e_visits_ref,
    e_value_ref,
    children_ref,
    e_reward_ref,
    out_visits_ref,
    out_value_ref,
    out_children_ref,
    out_reward_ref,
):
    """One grid program per game: copy the planes, then apply the W
    insertions and W x depth backup updates as one-hot row RMWs.

    Update order (members ascending within each level, levels
    ascending) reproduces the XLA scatters' duplicate-index semantics:
    `.set` last-write-wins to the highest member, `.max` is
    order-free, and the visit/value adds associate in the same order
    as the reference scatter-adds.
    """
    w = parents_ref.shape[2]
    depth = rec_node_ref.shape[2]
    a = out_visits_ref.shape[2]
    out_visits_ref[...] = e_visits_ref[...]
    out_value_ref[...] = e_value_ref[...]
    out_children_ref[...] = children_ref[...]
    out_reward_ref[...] = e_reward_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, a), 1)
    for j in range(w):  # static unroll; W is small (<= wave size)
        p = parents_ref[0, 0, j]
        onehot = lane == actions_ref[0, 0, j]
        row = out_children_ref[0, pl.ds(p, 1), :]
        out_children_ref[0, pl.ds(p, 1), :] = jnp.where(
            onehot, jnp.maximum(row, new_child_ref[0, 0, j]), row
        )
        row = out_reward_ref[0, pl.ds(p, 1), :]
        out_reward_ref[0, pl.ds(p, 1), :] = jnp.where(
            onehot, rewards_ref[0, 0, j], row
        )
    for lvl in range(depth):
        for j in range(w):
            active = rec_active_ref[0, j, lvl] > 0
            nd = jnp.maximum(rec_node_ref[0, j, lvl], 0)
            onehot = lane == jnp.maximum(rec_action_ref[0, j, lvl], 0)
            cnt = jnp.where(active, 1.0, 0.0)
            val = jnp.where(active, returns_ref[0, j, lvl], 0.0)
            row = out_visits_ref[0, pl.ds(nd, 1), :]
            out_visits_ref[0, pl.ds(nd, 1), :] = row + jnp.where(
                onehot, cnt, 0.0
            )
            row = out_value_ref[0, pl.ds(nd, 1), :]
            out_value_ref[0, pl.ds(nd, 1), :] = row + jnp.where(
                onehot, val, 0.0
            )


@functools.partial(jax.jit, static_argnames=("interpret",))
def backup_update_pallas(
    e_visits: jax.Array,
    e_value: jax.Array,
    children: jax.Array,
    e_reward: jax.Array,
    parents: jax.Array,
    actions: jax.Array,
    new_child: jax.Array,
    rewards: jax.Array,
    rec_node: jax.Array,
    rec_action: jax.Array,
    rec_active: jax.Array,
    returns: jax.Array,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Per-game fused insertion + backup over VMEM-resident planes.

    Each program streams its game's four (N, A) planes HBM->VMEM once
    and applies every update for the wave in place — one pass instead
    of 2*depth+2 full-plane scatters. `interpret=True` runs the
    kernel in the Pallas interpreter (CPU tests).
    """
    b, n, a = e_visits.shape
    w = parents.shape[1]
    depth = rec_node.shape[-1]
    # (B, 1, W): the TPU lowering wants a block's last two dims to be
    # whole array dims (or multiples of 8 x 128).
    smem_row = pl.BlockSpec(
        (1, 1, w), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
    smem_rec = pl.BlockSpec(
        (1, w, depth), lambda i: (i, 0, 0), memory_space=pltpu.SMEM
    )
    vmem_plane = pl.BlockSpec(
        (1, n, a), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    plane = jax.ShapeDtypeStruct((b, n, a), jnp.float32)
    return pl.pallas_call(
        _backup_kernel,
        grid=(b,),
        in_specs=[smem_row] * 4 + [smem_rec] * 4 + [vmem_plane] * 4,
        out_specs=(vmem_plane,) * 4,
        out_shape=(plane,) * 4,
        compiler_params=vmem_params(8 * f32_block_bytes(n, a)),
        interpret=interpret,
        name="mcts_backup",
    )(
        parents.astype(jnp.int32).reshape(b, 1, w),
        actions.astype(jnp.int32).reshape(b, 1, w),
        new_child.astype(jnp.float32).reshape(b, 1, w),
        rewards.astype(jnp.float32).reshape(b, 1, w),
        rec_node.astype(jnp.int32),
        rec_action.astype(jnp.int32),
        rec_active.astype(jnp.int32),
        returns.astype(jnp.float32),
        e_visits,
        e_value,
        children,
        e_reward,
    )


def backup_update(
    e_visits: jax.Array,
    e_value: jax.Array,
    children: jax.Array,
    e_reward: jax.Array,
    parents: jax.Array,
    actions: jax.Array,
    new_child: jax.Array,
    rewards: jax.Array,
    rec_node: jax.Array,
    rec_action: jax.Array,
    rec_active: jax.Array,
    returns: jax.Array,
    mode: str = "xla",
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Dispatch by mode ("xla" | "pallas"); returns the four updated
    edge planes (e_visits, e_value, children, e_reward)."""
    if mode == "xla":
        return backup_update_xla(
            e_visits, e_value, children, e_reward, parents, actions,
            new_child, rewards, rec_node, rec_action, rec_active, returns,
        )
    if mode == "pallas":
        # Compiled on a TPU backend, interpreted everywhere else (CPU
        # tests).
        interpret = jax.default_backend() != "tpu"
        return backup_update_pallas(
            e_visits, e_value, children, e_reward, parents, actions,
            new_child, rewards, rec_node, rec_action, rec_active, returns,
            interpret=interpret,
        )
    raise ValueError(f"unknown backup mode: {mode!r}")
