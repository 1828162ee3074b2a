"""Static-shape MCTS root promotion: subtree reuse across moves.

After a move plays action `a`, the chosen child `c0 = children[b, 0, a]`
roots the subtree worth keeping; everything else (the old root, the
siblings' subtrees, orphan slots) is dead weight. The reference keeps
that subtree behind an opaque C++ tree handle
(`alphatriangle/rl/self_play/worker.py:273-280`); here the same reuse
is a batched, jittable *relabeling* over the fixed `(B, N, A)` edge
planes — no dynamic shapes, no host round trip:

1. **Reachability + BFS rank** (shared plan, plain XLA): seed depth 0
   at `c0`, then `bfs_rounds` rounds of scatter-min relaxation over the
   `children` edges give each node its BFS depth from `c0` (the
   expanded tree is a forest — every slot has at most one parent edge —
   so depths are exact after as many rounds as the tree is deep).
   Sorting `depth * N + node_id` yields a stable BFS-order compaction:
   rank 0 is `c0` itself, parents always rank before their children.
2. **Budget truncation**: ranks >= `max_retained` are dropped (their
   parent edges revert to unexpanded `-1`, keeping the edge statistics
   — the slot is simply re-expandable). Parent-before-child ranking
   makes the truncation frontier consistent: a kept node's parent is
   always kept.
3. **Row reorder**: the six f32 edge planes are gathered into BFS-rank
   order (`take_along_axis`) with freed rows re-zeroed (children rows
   to -1).

The caller (`mcts/search.py`) re-seats root statistics by construction
— the promoted row 0 *is* the chosen child's edge row — and re-applies
fresh root priors + Dirichlet noise on the next search's init.

Shapes: planes `(B, N, A)` f32, `terminal` `(B, N)` bool, `actions`
`(B,)` int32. Returns the promoted planes plus `state_index` `(B, N)`
int32 (the old-layout row each promoted `node_state` row should be
gathered from; freed rows point at `c0` so they mirror
`_init_tree`'s root broadcast), `promo_valid` `(B,)` bool (False when
the chosen child was never expanded — nothing to reuse) and
`retained` `(B,)` int32 (rows kept = the next search's per-game
insertion base).
"""

import jax
import jax.numpy as jnp


def _promotion_plan(
    children: jax.Array,
    actions: jax.Array,
    max_retained: int,
    bfs_rounds: int,
):
    """BFS-rank compaction plan over the `children` forest.

    Returns `(order, state_index, keep_mask, new_children, promo_valid,
    retained)`: `order[b, r]` is the old row id at BFS rank r,
    `keep_mask[b, r]` whether output row r is live (`r < retained[b]`),
    `new_children` the children plane remapped to new ids in the OLD
    row layout (gathered by `order` in the reorder step).
    """
    b, n, a = children.shape
    barange = jnp.arange(b)
    node_ids = jnp.arange(n, dtype=jnp.int32)[None, :]
    b3 = barange[:, None, None]

    c0 = children[barange, 0, actions].astype(jnp.int32)  # (B,)
    promo_valid = c0 >= 0
    c0c = jnp.maximum(c0, 0)

    child_ids = children.astype(jnp.int32)  # (B, N, A); -1 = none
    has_child = child_ids >= 0
    tgt = jnp.maximum(child_ids, 0)

    # BFS depth from c0 by scatter-min relaxation. `n` is the
    # unreachable sentinel (any real depth is < n). Invalid lanes seed
    # nothing and retain nothing.
    big = jnp.int32(n)
    depth = jnp.full((b, n), big, jnp.int32).at[barange, c0c].set(
        jnp.where(promo_valid, 0, big)
    )

    def relax(_, d):
        pd = d[:, :, None]  # (B, N, 1) parent depth
        cand = jnp.where(has_child & (pd < big), pd + 1, big)
        return d.at[b3, tgt].min(cand)

    depth = jax.lax.fori_loop(0, bfs_rounds, relax, depth)

    reached = depth < big  # (B, N)
    # Stable BFS order: depth-major, old node id minor (keys unique).
    key = jnp.where(reached, depth * n + node_ids, jnp.int32(n * n))
    order = jnp.argsort(key, axis=1).astype(jnp.int32)  # (B, N)
    # Inverse permutation: rank[old_id] = new row id.
    rank = (
        jnp.zeros((b, n), jnp.int32)
        .at[barange[:, None], order]
        .set(jnp.broadcast_to(node_ids, (b, n)))
    )
    retained = jnp.where(
        promo_valid,
        jnp.minimum(
            reached.sum(axis=1, dtype=jnp.int32), jnp.int32(max_retained)
        ),
        0,
    )
    keep_old = reached & (rank < max_retained) & promo_valid[:, None]

    # Remap child pointers to new ids in the old layout; edges to
    # dropped children revert to unexpanded (-1) but keep their stats.
    keep_c = keep_old[barange[:, None, None], tgt] & has_child
    new_children = jnp.where(
        keep_c, rank[barange[:, None, None], tgt].astype(jnp.float32), -1.0
    )

    keep_mask = node_ids < retained[:, None]  # (B, N) over NEW rows
    # node_state gather targets: freed rows mirror the root broadcast.
    state_index = jnp.where(keep_mask, order, c0c[:, None])
    return order, state_index, keep_mask, new_children, promo_valid, retained


def _reorder_planes(order, keep_mask, planes, fills):
    """out[b, r] = planes[b, order[b, r]] where keep, else fill."""
    idx = jnp.where(keep_mask, order, 0)[:, :, None]
    out = []
    for plane, fill in zip(planes, fills):
        gathered = jnp.take_along_axis(plane, idx, axis=1)
        out.append(jnp.where(keep_mask[:, :, None], gathered, fill))
    return tuple(out)


def subtree_promote(
    e_visits: jax.Array,
    e_value: jax.Array,
    e_reward: jax.Array,
    children: jax.Array,
    prior: jax.Array,
    valid: jax.Array,
    terminal: jax.Array,
    actions: jax.Array,
    max_retained: int,
    bfs_rounds: int,
) -> tuple[
    jax.Array, jax.Array, jax.Array, jax.Array, jax.Array, jax.Array,
    jax.Array, jax.Array, jax.Array, jax.Array,
]:
    """Promote each game's chosen child to the root row (see module doc).

    Returns
    `(e_visits, e_value, e_reward, children, prior, valid, terminal,
    state_index, promo_valid, retained)` — the six planes + terminal in
    BFS-rank layout with freed rows zeroed, plus the node_state gather
    plan and per-game validity/row counts.
    """
    order, state_index, keep_mask, new_children, promo_valid, retained = (
        _promotion_plan(children, actions, max_retained, bfs_rounds)
    )
    planes = (e_visits, e_value, e_reward, new_children, prior, valid)
    out = _reorder_planes(
        order, keep_mask, planes, (0.0, 0.0, 0.0, -1.0, 0.0, 0.0)
    )
    # terminal is bool: gathered the same way, freed rows False.
    term = jnp.take_along_axis(
        terminal, jnp.where(keep_mask, order, 0), axis=1
    )
    term = keep_mask & term
    return out + (term, state_index, promo_valid, retained)
