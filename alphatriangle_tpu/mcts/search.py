"""Batched wave-parallel PUCT search as dense MXU linear algebra.

Functional equivalent of the observed trimcts surface
(`alphatriangle/config/mcts_config.py:67-77`,
`alphatriangle/rl/self_play/worker.py:273-280`): PUCT selection with
cpuct, Dirichlet root noise, max-depth cutoff, discounted value backup,
dense visit-count extraction, and batched leaf collection (the
reference's `mcts_batch_size` C++ leaf batching,
`mcts_config.py:57-62`).

TPU-first design, not a translation of the C++ pointer tree:
- A search over B games is ONE jitted computation. Tree statistics are
  **edge-indexed** struct-of-arrays with dims (B, N, A): visit counts,
  return sums, rewards, priors, validity, and child ids all live on
  edges (node x action), so everything PUCT needs at a node is one
  contiguous row — never a per-action pointer chase.
- Simulations run in **waves of W members** (W = `mcts_batch_size`
  clamped to a divisor of max_simulations). Each wave:
    1. W parallel PUCT descents per tree, a static `fori_loop` over
       max_depth levels. Each level reads its tree rows with ONE
       batched one-hot matmul `(B,W,N) x (B,N,6A)` against a per-wave
       concatenation of the six stat planes — an MXU contraction, not
       a gather. Descents are diversified by per-member Gumbel
       perturbation (`wave_noise_scale`) instead of sequential
       virtual loss, and record their (node, action, reward) path.
    2. one batched env.step over the B*W selected edges (bitboards);
    3. ONE fused network evaluation of all B*W leaves;
    4. block insertion of the W new node slots via dynamic-slice
       updates; within-wave duplicate edges are canonicalized to a
       single child (duplicates and re-expanded edges become orphan
       slots, counted in `wasted_slots`);
    5. discounted backup along the recorded paths: max_depth static
       rounds of (B, W)-sized scatter-adds into the edge planes — no
       data-dependent `while` walk, no parent pointers.
- All shapes static; no Python control flow inside jit. Sequential
  dispatch rounds per search scale with (sims/W) * max_depth, and the
  per-round work is dense f32 vector/matrix math.
- Terminal nodes evaluate to value 0 and step as no-ops (the engine
  freezes finished games), so finished games in a batch stay in
  lockstep at zero extra cost.
- Subtree reuse (the reference's opaque tree handle) is OFF by
  default and static-shape when on (`MCTSConfig.tree_reuse`): the
  fresh-root default keeps the original v1 behavior bit-identical —
  re-searching from the root each move, the root-prior encoding the
  network's (fresher) knowledge, `wasted_slots` quantifying the
  orphan overhead. With reuse on, the node budget widens to
  `max_simulations + tree_reuse_budget + 1` slots and a batched
  root-promotion pass (`ops/subtree_reuse.py`) compacts the chosen
  child's subtree into the leading rows after each move; the next
  search merges those carried edge statistics under a *fresh* root
  evaluation (exact network value, re-applied Dirichlet noise) and
  inserts new waves at a per-game base — `CarriedTree` rides the
  caller's scan/session carry, so reuse costs zero extra dispatches.
"""

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct

from ..config.mcts_config import MCTSConfig
from ..env.engine import EnvState, TriangleEnv
from ..features.core import FeatureExtractor
from ..ops import backup_update, gather_rows, subtree_promote
from ..telemetry.device_stats import (
    DEPTH_BINS,
    beacon_every,
    device_stats_enabled,
    emit_beacon,
)


@struct.dataclass
class Tree:
    """Edge-indexed search arrays, batched over B games."""

    node_state: EnvState  # (B, N, ...) game state at each node slot
    e_visits: jax.Array  # (B, N, A) f32 edge visit counts
    e_value: jax.Array  # (B, N, A) f32 sum of discounted returns G(edge)
    e_reward: jax.Array  # (B, N, A) f32 reward on the edge (set at expand)
    children: jax.Array  # (B, N, A) f32 child slot id; -1 = unexpanded
    prior: jax.Array  # (B, N, A) f32 masked policy priors
    valid: jax.Array  # (B, N, A) f32 1.0 where the action is valid
    terminal: jax.Array  # (B, N) bool
    root_value0: jax.Array  # (B,) f32 network value of the root at init
    # What the net counted over this search's evaluations (a routed
    # trunk's expert assignments, nn/trunk.py); None for a net that
    # counts nothing — an empty pytree node, the program unchanged.
    net_counters: Any = None


@struct.dataclass
class CarriedTree:
    """A promoted search tree carried across moves (subtree reuse).

    `tree` holds the chosen child's subtree compacted into the leading
    rows (BFS order, freed rows zeroed) by `BatchedMCTS.promote`;
    `valid[b]` gates the merge (False = next search starts fresh:
    unexpanded chosen child, episode reset, weight reload, serve lane
    churn); `base[b]` = retained row count = the next search's
    insertion base. Rides the caller's carry (rollout scan, megastep
    program, serve lane state) so reuse never adds a dispatch.
    """

    tree: Tree
    valid: jax.Array  # (B,) bool
    base: jax.Array  # (B,) int32


@struct.dataclass
class SearchOutput:
    """Result of one batched search."""

    visit_counts: jax.Array  # (B, A) float32 root child visit counts
    root_value: jax.Array  # (B,) float32 mean backed-up root value
    root_prior: jax.Array  # (B, A) float32 noisy root prior (debug)
    total_simulations: jax.Array  # () int32
    wasted_slots: jax.Array  # (B,) int32 orphan node slots (see module doc)
    # Gumbel root search outputs (mcts/gumbel.py). PUCT fills
    # sentinels so both search kinds share one pytree structure (the
    # playout-cap lax.cond needs matching branches):
    # selected_action -1 = "select from visit counts on the host path";
    # improved_policy zeros = "build the target from visit counts".
    selected_action: jax.Array  # (B,) int32
    improved_policy: jax.Array  # (B, A) float32
    # Device telemetry stat-pack (telemetry/device_stats.py): a small
    # dict of fixed-shape f32 search-health statistics (leaf-depth
    # histogram, root-visit entropy/concentration, max |value|, slot
    # occupancy, reuse retained-fraction), or None when
    # TelemetryConfig.DEVICE_STATS is off — it rides the caller's
    # existing fetch, costing zero extra dispatches. Both search kinds
    # (PUCT and Gumbel) produce the same structure so the playout-cap
    # lax.cond branches keep matching pytrees.
    stats: Any = None
    # `Tree.net_counters` at the search's end (None: nothing counted).
    net_counters: Any = None


def tree_geometry(config: MCTSConfig) -> tuple[int, int]:
    """(node slots per tree, wave size) the search allocates for
    `config` — the N and W of every (B, N, A) plane and (B, W) wave.

    Subtree reuse widens the node budget: up to budget + 1 retained
    rows (promoted subtree incl. its root) plus a full search's worth
    of fresh insertions. Fresh-root (the default) keeps the original
    max_simulations + 1 exactly. The wave is the largest divisor of
    max_simulations <= mcts_batch_size, so waves tile the simulation
    budget exactly.
    """
    sims = config.max_simulations
    if config.tree_reuse:
        reuse_slots = (config.tree_reuse_budget or sims) + 1
    else:
        reuse_slots = 1
    w = max(1, min(config.mcts_batch_size, sims))
    while sims % w:
        w -= 1
    return sims + reuse_slots, w


class BatchedMCTS:
    """PUCT search bound to (env, features, model); `search` is jitted.

    `evaluate` contract: the Flax model applied to extracted features,
    returning (policy_logits, value_logits -> scalar values) — the same
    role as the reference's `AlphaZeroNetworkInterface.evaluate_batch`
    (`alphatriangle/nn/network.py:242-318`) but traced into the search.
    """

    def __init__(
        self,
        env: TriangleEnv,
        extractor: FeatureExtractor,
        model: Any,
        config: MCTSConfig,
        value_support: jax.Array,
    ):
        self.env = env
        self.extractor = extractor
        self.model = model
        self.config = config
        self.support = value_support
        self.num_nodes, self.wave_size = tree_geometry(config)
        self.reuse_slots = self.num_nodes - config.max_simulations
        self.action_dim = env.action_dim
        self.num_waves = config.max_simulations // self.wave_size
        # Snapshot of the device-stats flag at construction: it shapes
        # the compiled programs (SearchOutput.stats leaf), so engines
        # fold it into their AOT cache extras and never flip it on a
        # live instance.
        self.device_stats = device_stats_enabled()
        self.search = jax.jit(self._search)

    # --- network evaluation ----------------------------------------------

    @jax.named_scope("search/evaluate")
    def _evaluate(self, variables, states: EnvState):
        """Batched leaf eval: states (B-leading) -> (priors (B,A), values
        (B,), valid (B,A), what the net counted or None).

        Priors are masked to valid actions and renormalized (uniform over
        valid when the network mass on valid actions vanishes — the
        reference's fallback, `nn/network.py:200-215`).
        """
        from ..nn.precision import dequantize_params

        # The leaves' input features belong to expanding them, not to
        # the net (innermost scope wins in the phase table).
        with jax.named_scope("search/expand"):
            grids, others = jax.vmap(self.extractor.extract)(states)
        # Int8 weight-only inference (nn/precision.py): marker-dict
        # leaves dequantize to bf16 here, at the one place every search
        # family evaluates the net; unquantized trees pass through.
        counters = None
        if self.model.config.TRUNK is None:
            policy_logits, value_logits = self.model.apply(
                dequantize_params(variables), grids, others, train=False
            )
        else:
            # A routed trunk (nn/trunk.py) counts its expert assignments,
            # and at its width a whole leaf batch does not fit: the net
            # takes `block_boards` boards at a time inside the program.
            from ..nn.trunk import block_size, counters_of

            def net(block):
                out, sown = self.model.apply(
                    dequantize_params(variables),
                    *block,
                    train=False,
                    mutable=["counters"],
                )
                return out, counters_of(sown)

            batch = grids.shape[0]
            size = block_size(batch, self.model.config.TRUNK.block_boards)
            if size == batch:
                (policy_logits, value_logits), counters = net((grids, others))
            else:
                (policy_logits, value_logits), counters = jax.lax.map(
                    net,
                    jax.tree_util.tree_map(
                        lambda x: x.reshape(batch // size, size, *x.shape[1:]),
                        (grids, others),
                    ),
                )
                policy_logits = policy_logits.reshape(batch, -1)
                value_logits = value_logits.reshape(batch, -1)
                counters = jax.tree_util.tree_map(
                    lambda x: x.sum(axis=0), counters
                )
        valid = jax.vmap(self.env.valid_action_mask)(states)  # (B, A)
        masked_logits = jnp.where(valid, policy_logits, -jnp.inf)
        # Softmax over valid actions only; all-invalid rows -> zeros.
        any_valid = valid.any(axis=-1, keepdims=True)
        safe_logits = jnp.where(any_valid, masked_logits, 0.0)
        priors = jax.nn.softmax(safe_logits, axis=-1)
        priors = jnp.where(valid, priors, 0.0)
        norm = priors.sum(axis=-1, keepdims=True)
        uniform = valid.astype(jnp.float32) / jnp.maximum(
            valid.sum(axis=-1, keepdims=True), 1
        )
        priors = jnp.where(norm > 1e-9, priors / jnp.maximum(norm, 1e-9), uniform)
        value_probs = jax.nn.softmax(value_logits, axis=-1)
        values = jnp.sum(value_probs * self.support, axis=-1)
        return priors, values, valid, counters

    # --- the search -------------------------------------------------------

    @jax.named_scope("search/init")
    def _init_tree(self, variables, root_states: EnvState, rng) -> Tree:
        """Batched tree init: root eval + Dirichlet noise."""
        cfg = self.config
        batch = root_states.done.shape[0]
        n, a = self.num_nodes, self.action_dim

        priors, values, valid, counters = self._evaluate(variables, root_states)
        root_terminal = root_states.done
        root_value = jnp.where(root_terminal, 0.0, values)

        # Dirichlet root noise over valid actions (eps=0 or alpha=0 -> off).
        if cfg.dirichlet_epsilon > 0 and cfg.dirichlet_alpha > 0:
            gammas = jax.random.gamma(
                rng, cfg.dirichlet_alpha, shape=(batch, a)
            )
            gammas = jnp.where(valid, gammas, 0.0)
            noise = gammas / jnp.maximum(
                gammas.sum(axis=-1, keepdims=True), 1e-9
            )
            priors = (1.0 - cfg.dirichlet_epsilon) * priors + (
                cfg.dirichlet_epsilon
            ) * noise
            priors = jnp.where(valid, priors, 0.0)

        def broadcast_to_nodes(x):
            """Tile each game's root state across its N node slots."""
            return jnp.broadcast_to(x[:, None], (batch, n) + x.shape[1:])

        node_state = jax.tree_util.tree_map(broadcast_to_nodes, root_states)
        zeros_na = jnp.zeros((batch, n, a), dtype=jnp.float32)
        return Tree(
            node_state=node_state,
            e_visits=zeros_na,
            e_value=zeros_na,
            e_reward=zeros_na,
            children=jnp.full((batch, n, a), -1.0, dtype=jnp.float32),
            prior=zeros_na.at[:, 0].set(priors),
            valid=zeros_na.at[:, 0].set(valid.astype(jnp.float32)),
            terminal=jnp.zeros((batch, n), dtype=bool).at[:, 0].set(root_terminal),
            root_value0=root_value,
            net_counters=counters,
        )

    @jax.named_scope("search/descend")
    def _descend_wave(
        self,
        tree: Tree,
        wave_rng: jax.Array,
        batch: int,
        root_action: jax.Array | None = None,
    ):
        """W parallel recorded descents per tree.

        Returns a dict of (B, W[, D]) arrays: final (parent, action,
        existing child), and the recorded path (nodes, actions,
        traversal rewards, active mask) for backup. Gumbel score noise
        (`wave_noise_scale`) is sampled per level from `wave_rng` so
        no (B, W, D, A) tensor is ever materialized.

        `root_action` (B, W) int32, when given, forces each member's
        depth-0 action (the Gumbel sequential-halving allocation,
        mcts/gumbel.py); -1 entries are unforced (ordinary PUCT), and
        deeper levels always select by PUCT.
        """
        cfg = self.config
        w, a = self.wave_size, self.action_dim
        depth = cfg.max_depth

        # Per-wave dense stat block: one (B, N, 6A) tensor so each
        # descent level is a single batched matmul row-read.
        stats = jnp.concatenate(
            [
                tree.e_visits,
                tree.e_value,
                tree.e_reward,
                tree.prior,
                tree.valid,
                tree.children,
            ],
            axis=-1,
        )  # (B, N, 6A)

        def level(d, carry):
            node, action, stop, rec_node, rec_action, rec_reward, rec_active = carry
            # (B, W, 6A) exact row select (one-hot MXU matmul).
            rows = gather_rows(stats, node)
            visits_r = rows[..., 0 * a : 1 * a]
            value_r = rows[..., 1 * a : 2 * a]
            reward_r = rows[..., 2 * a : 3 * a]
            prior_r = rows[..., 3 * a : 4 * a]
            valid_r = rows[..., 4 * a : 5 * a]
            child_r = rows[..., 5 * a : 6 * a]

            n_node = 1.0 + visits_r.sum(axis=-1, keepdims=True)
            q = jnp.where(
                visits_r > 0, value_r / jnp.maximum(visits_r, 1e-9), 0.0
            )
            u = (
                cfg.cpuct
                * prior_r
                * jnp.sqrt(n_node)
                / (1.0 + visits_r)
            )
            # Noise only matters with >1 wave member; at W=1 keep exact
            # PUCT so sequential configs reproduce reference selection.
            if w > 1 and cfg.wave_noise_scale > 0:
                noise = cfg.wave_noise_scale * jax.random.gumbel(
                    jax.random.fold_in(wave_rng, d), (batch, w, a)
                )
            else:
                noise = 0.0
            scores = jnp.where(valid_r > 0, q + u, -jnp.inf) + noise
            act = jnp.argmax(scores, axis=-1).astype(jnp.int32)  # (B, W)
            if root_action is not None:
                # -1 releases a member to ordinary PUCT selection.
                act = jnp.where((d == 0) & (root_action >= 0), root_action, act)
            act_oh = jax.nn.one_hot(act, a, dtype=jnp.float32)
            child = (
                (child_r * act_oh).sum(axis=-1).astype(jnp.int32)
            )  # (B, W); -1 = unexpanded
            r_edge = (reward_r * act_oh).sum(axis=-1)
            term = jnp.take_along_axis(tree.terminal, node, axis=1)
            stop_now = (child < 0) | (d + 1 >= depth) | term

            active = ~stop
            rec_node = rec_node.at[:, :, d].set(jnp.where(active, node, -1))
            rec_action = rec_action.at[:, :, d].set(
                jnp.where(active, act, -1)
            )
            rec_reward = rec_reward.at[:, :, d].set(
                jnp.where(active, r_edge, 0.0)
            )
            rec_active = rec_active.at[:, :, d].set(active)

            action = jnp.where(stop, action, act)
            node = jnp.where(stop | stop_now, node, child)
            return (
                node,
                action,
                stop | stop_now,
                rec_node,
                rec_action,
                rec_reward,
                rec_active,
            )

        node0 = jnp.zeros((batch, w), jnp.int32)
        carry = (
            node0,
            jnp.zeros((batch, w), jnp.int32),
            jnp.zeros((batch, w), bool),
            jnp.full((batch, w, depth), -1, jnp.int32),
            jnp.full((batch, w, depth), -1, jnp.int32),
            jnp.zeros((batch, w, depth), jnp.float32),
            jnp.zeros((batch, w, depth), bool),
        )
        parents, actions, _, rec_node, rec_action, rec_reward, rec_active = (
            jax.lax.fori_loop(0, depth, level, carry, unroll=True)
        )
        existing = (
            jnp.take_along_axis(
                tree.children.reshape(batch, -1),
                (parents * a + actions),
                axis=1,
            )
        ).astype(jnp.int32)  # (B, W)
        return {
            "parents": parents,
            "actions": actions,
            "existing": existing,
            "rec_node": rec_node,
            "rec_action": rec_action,
            "rec_reward": rec_reward,
            "rec_active": rec_active,
        }

    def _wave(self, variables, batch: int, carry, wave_rng, root_action=None):
        """One wave: W parallel sims across all B trees.

        `carry` is `(tree, wasted, base)` plus — when `device_stats` is
        on — a trailing `(DEPTH_BINS,) f32` leaf-depth histogram the
        wave accumulates into; the return matches the input arity.
        """
        cfg = self.config
        tree, wasted, base = carry[:3]
        hist = carry[3] if self.device_stats and len(carry) > 3 else None
        w, a = self.wave_size, self.action_dim
        depth = cfg.max_depth
        barange = jnp.arange(batch)
        warange = jnp.arange(w)
        bcol = barange[:, None]

        # 1. W parallel recorded descents per tree.
        d = self._descend_wave(tree, wave_rng, batch, root_action)
        parents, actions, existing = d["parents"], d["actions"], d["existing"]
        is_new = existing < 0

        with jax.named_scope("search/expand"):
            # Canonicalize within-wave duplicates: members that chose the
            # same edge share one child node — the one belonging to the
            # highest member index (matching the `.max()` scatter below).
            key = parents * a + actions  # (B, W)
            same = key[:, :, None] == key[:, None, :]  # (B, W, W)
            later = warange[None, None, :] > warange[None, :, None]
            is_canon = ~(same & later).any(axis=-1)  # (B, W)

            # 2. Expansion: one batched env.step over all B*W edges.
            # (The engine is deterministic given the node's PRNG state, so
            # duplicate/revisited edges reproduce the same child state.)
            parent_states = jax.tree_util.tree_map(
                lambda x: x[bcol, parents].reshape((batch * w,) + x.shape[2:]),
                tree.node_state,
            )
            new_states, rewards, dones = jax.vmap(self.env.step)(
                parent_states, actions.reshape(-1)
            )
            rewards = rewards.reshape(batch, w)
            dones = dones.reshape(batch, w)

        # 3. Evaluation: ONE fused network call for all B*W leaves.
        priors, values, valid, counters = self._evaluate(variables, new_states)
        leaf_values = jnp.where(dones, 0.0, values.reshape(batch, w))

        with jax.named_scope("search/expand"):
            # 4. Insert the wave's W node slots as one block at [base, base+W).
            if jnp.ndim(base) == 0:
                # Shared scalar base (fresh-root search): a dynamic-slice
                # block write, the original lowering verbatim.
                def insert(buf, block):
                    return jax.lax.dynamic_update_slice_in_dim(
                        buf, block.astype(buf.dtype), base, axis=1
                    )

                slot_ids = (base + warange[None, :]).astype(jnp.float32)  # (1, W)
            else:
                # Per-game base (subtree reuse: each game retained a
                # different row count): scatter rows [base_b, base_b + W).
                slots = base[:, None] + warange[None, :]  # (B, W)

                def insert(buf, block):
                    return buf.at[bcol, slots].set(block.astype(buf.dtype))

                slot_ids = slots.astype(jnp.float32)

            ns = jax.tree_util.tree_map(
                lambda buf, x: insert(buf, x.reshape((batch, w) + x.shape[1:])),
                tree.node_state,
                new_states,
            )
            live = is_new & is_canon
            tree = tree.replace(
                node_state=ns,
                prior=insert(tree.prior, priors.reshape(batch, w, a)),
                valid=insert(
                    tree.valid, valid.reshape(batch, w, a).astype(jnp.float32)
                ),
                terminal=insert(tree.terminal, dones),
                net_counters=jax.tree_util.tree_map(
                    jnp.add, tree.net_counters, counters
                ),
            )

        with jax.named_scope("search/backup"):
            # 5. Insertion + backup along the recorded paths as one fused
            # edge-plane update (ops/mcts_backup.py; lowering per config).
            # Suffix returns first: G_d = r_d + discount * G_{d+1}, where
            # the deepest active level's reward is the fresh step reward (a
            # new edge has no stored reward yet; for revisits the stored
            # value is identical by determinism).
            rec_node, rec_action = d["rec_node"], d["rec_action"]
            rec_active = d["rec_active"]  # (B, W, D)
            last_idx = rec_active.sum(axis=-1) - 1  # (B, W) deepest level
            if hist is not None:
                # Leaf-depth histogram: one count per simulation at its
                # descent depth (terminal-root sims land in bin 0; depths
                # past the last bin clip into it). A (B*W, BINS) one-hot
                # sum — vector math on data already in registers.
                d_bin = jnp.clip(last_idx, 0, DEPTH_BINS - 1).reshape(-1)
                hist = hist + jax.nn.one_hot(
                    d_bin, DEPTH_BINS, dtype=jnp.float32
                ).sum(axis=0)
            g = leaf_values  # (B, W)
            contrib = []
            for lvl in range(depth - 1, -1, -1):
                is_last = rec_active[:, :, lvl] & (last_idx == lvl)
                r_lvl = jnp.where(
                    is_last, rewards, d["rec_reward"][:, :, lvl]
                )
                g = jnp.where(
                    rec_active[:, :, lvl], r_lvl + cfg.discount * g, g
                )
                contrib.append(g)
            contrib.reverse()  # contrib[lvl] = G at level lvl, (B, W)

            e_visits, e_value, children, e_reward = backup_update(
                tree.e_visits,
                tree.e_value,
                tree.children,
                tree.e_reward,
                parents,
                actions,
                jnp.where(is_new, slot_ids, -1.0),
                rewards,
                rec_node,
                rec_action,
                rec_active,
                jnp.stack(contrib, axis=-1),
                mode=cfg.backup_update,
            )
            tree = tree.replace(
                e_visits=e_visits,
                e_value=e_value,
                children=children,
                e_reward=e_reward,
            )

        wasted = wasted + (w - live.sum(axis=1, dtype=jnp.int32))
        if hist is not None:
            return tree, wasted, base + w, hist
        return tree, wasted, base + w

    def _stats_seed(self) -> tuple:
        """The extra carry tail `_wave` accumulates when device stats
        are on: a zeroed leaf-depth histogram. Empty tuple when off, so
        unchanged configs carry exactly the original 3-tuple."""
        if not self.device_stats:
            return ()
        return (jnp.zeros((DEPTH_BINS,), jnp.float32),)

    def _run_waves(self, variables, batch: int, tree: Tree, wave_rng, base0):
        """`num_waves` waves from `tree`; `base0` is the first insertion
        base — scalar 1 (fresh root) or a per-game (B,) vector (reuse).
        Returns `(tree, wasted, base)` plus the depth histogram when
        device stats are on (`_stats_seed`)."""

        def wave_body(k, carry):
            emit_beacon("search_wave", k, every=beacon_every())
            return self._wave(
                variables,
                batch,
                carry,
                jax.random.fold_in(wave_rng, k),
            )

        return jax.lax.fori_loop(
            0,
            self.num_waves,
            wave_body,
            (tree, jnp.zeros((batch,), jnp.int32), base0)
            + self._stats_seed(),
        )

    def _stat_pack(
        self,
        tree: Tree,
        wasted: jax.Array,
        final_base,
        hist: jax.Array,
        batch: int,
        reused: jax.Array | None = None,
    ) -> dict:
        """KataGo-style search-health statistics (arXiv:1902.10565)
        from arrays already on device — a handful of (B, A)-sized
        reductions appended to the program, returned through the
        caller's existing fetch.

        All leaves are fixed-shape f32 scalars except `depth_hist`
        ((DEPTH_BINS,)); the structure is identical across search kinds
        and reuse modes so downstream pytrees always match."""
        visits = tree.e_visits[:, 0, :]  # (B, A) root edge visits
        total = visits.sum(axis=-1)  # (B,)
        p = visits / jnp.maximum(total[:, None], 1.0)
        entropy = -jnp.where(p > 0, p * jnp.log(jnp.maximum(p, 1e-12)), 0.0).sum(
            axis=-1
        )
        # Mean |Q| excursion over visited root edges, and the root
        # value itself: a diverging value head shows up here waves
        # before it poisons the iteration-mean loss metrics.
        q_abs = jnp.where(
            visits > 0,
            jnp.abs(tree.e_value[:, 0, :]) / jnp.maximum(visits, 1e-9),
            0.0,
        )
        value_abs_max = jnp.maximum(
            q_abs.max(), jnp.abs(tree.root_value0).max()
        )
        live = (
            jnp.broadcast_to(
                jnp.asarray(final_base, jnp.float32), (batch,)
            )
            - wasted.astype(jnp.float32)
        )
        if reused is None:
            reuse_frac = jnp.float32(0.0)
        else:
            reuse_frac = (reused / jnp.maximum(total, 1.0)).mean()
        return {
            "depth_hist": hist,
            "root_entropy": entropy.mean(),
            "root_concentration": p.max(axis=-1).mean(),
            "value_abs_max": value_abs_max,
            "occupancy": (live / float(self.num_nodes)).mean(),
            "reuse_frac": reuse_frac,
        }

    def _output_from_tree(
        self, tree: Tree, wasted: jax.Array, batch: int
    ) -> SearchOutput:
        """Root stats are just row 0 of the edge planes."""
        cfg = self.config
        visit_counts = tree.e_visits[:, 0, :]
        root_visits = 1.0 + visit_counts.sum(axis=-1)
        root_value = (
            tree.root_value0 + tree.e_value[:, 0, :].sum(axis=-1)
        ) / root_visits
        return SearchOutput(
            visit_counts=visit_counts,
            root_value=root_value,
            root_prior=tree.prior[:, 0],
            total_simulations=jnp.int32(cfg.max_simulations * batch),
            wasted_slots=wasted,
            selected_action=jnp.full((batch,), -1, jnp.int32),
            improved_policy=jnp.zeros_like(visit_counts),
            net_counters=tree.net_counters,
        )

    def _search(
        self, variables, root_states: EnvState, rng: jax.Array
    ) -> SearchOutput:
        """Run `max_simulations` batched simulations from `root_states`."""
        batch = root_states.done.shape[0]
        rng, noise_rng, wave_rng = jax.random.split(rng, 3)
        tree = self._init_tree(variables, root_states, noise_rng)
        tree, wasted, base, *rest = self._run_waves(
            variables, batch, tree, wave_rng, jnp.int32(1)
        )
        out = self._output_from_tree(tree, wasted, batch)
        if self.device_stats:
            out = out.replace(
                stats=self._stat_pack(tree, wasted, base, rest[0], batch)
            )
        return out

    # --- subtree reuse (MCTSConfig.tree_reuse; ops/subtree_reuse.py) ---

    def _search_carried(
        self,
        variables,
        root_states: EnvState,
        rng: jax.Array,
        carried: CarriedTree,
    ) -> tuple[SearchOutput, Tree, jax.Array]:
        """`_search` seeded with a promoted tree where `carried.valid`.

        The root row is ALWAYS re-taken from a fresh root evaluation —
        exact network value (`root_value0`), fresh masked priors with
        Dirichlet noise re-applied, current-state validity/terminal —
        so reuse carries only *edge statistics* (visits, returns,
        rewards, child links) plus interior priors/states. Lanes with
        `valid=False` reproduce the fresh-root search exactly. Returns
        `(output, final_tree, reused)` where `reused[b]` counts the
        root visits inherited from the carry (the leaf evaluations this
        move did not have to spend).
        """
        batch = root_states.done.shape[0]
        rng, noise_rng, wave_rng = jax.random.split(rng, 3)
        fresh = self._init_tree(variables, root_states, noise_rng)
        ct = carried.tree
        ok = carried.valid  # (B,)
        okr = ok[:, None, None]

        def merge(c_plane, f_plane):
            return jnp.where(okr, c_plane, f_plane)

        def merge_state(c, f):
            okx = ok.reshape((batch,) + (1,) * (c.ndim - 1))
            m = jnp.where(okx, c, f)
            # Row 0 always holds the exact current root state (the
            # promoted row 0 equals it by env determinism; this pins it
            # structurally rather than by argument).
            return m.at[:, 0].set(f[:, 0])

        tree = Tree(
            node_state=jax.tree_util.tree_map(
                merge_state, ct.node_state, fresh.node_state
            ),
            e_visits=merge(ct.e_visits, fresh.e_visits),
            e_value=merge(ct.e_value, fresh.e_value),
            e_reward=merge(ct.e_reward, fresh.e_reward),
            children=merge(ct.children, fresh.children),
            prior=merge(ct.prior.at[:, 0].set(fresh.prior[:, 0]), fresh.prior),
            valid=merge(ct.valid.at[:, 0].set(fresh.valid[:, 0]), fresh.valid),
            terminal=jnp.where(
                ok[:, None],
                ct.terminal.at[:, 0].set(fresh.terminal[:, 0]),
                fresh.terminal,
            ),
            root_value0=fresh.root_value0,
            net_counters=fresh.net_counters,
        )
        reused = jnp.where(ok, ct.e_visits[:, 0, :].sum(axis=-1), 0.0)
        base0 = jnp.where(ok, jnp.maximum(carried.base, 1), 1).astype(
            jnp.int32
        )
        tree, wasted, base, *rest = self._run_waves(
            variables, batch, tree, wave_rng, base0
        )
        out = self._output_from_tree(tree, wasted, batch)
        if self.device_stats:
            out = out.replace(
                stats=self._stat_pack(
                    tree, wasted, base, rest[0], batch, reused=reused
                )
            )
        return out, tree, reused

    @jax.named_scope("rollout/promote")
    def promote(self, tree: Tree, actions: jax.Array) -> CarriedTree:
        """Batched root promotion: compact each game's chosen child's
        subtree into the leading rows (ops/subtree_reuse.py). `valid` is
        False where the chosen child was never expanded; callers
        additionally clear lanes on episode reset / churn."""
        cfg = self.config
        (
            e_visits, e_value, e_reward, children, prior, valid,
            terminal, state_index, promo_valid, retained,
        ) = subtree_promote(
            tree.e_visits,
            tree.e_value,
            tree.e_reward,
            tree.children,
            tree.prior,
            tree.valid,
            tree.terminal,
            actions.astype(jnp.int32),
            max_retained=self.reuse_slots,
            bfs_rounds=cfg.max_depth,
        )
        batch = actions.shape[0]
        bcol = jnp.arange(batch)[:, None]
        node_state = jax.tree_util.tree_map(
            lambda x: x[bcol, state_index], tree.node_state
        )
        promoted = Tree(
            node_state=node_state,
            e_visits=e_visits,
            e_value=e_value,
            e_reward=e_reward,
            children=children,
            prior=prior,
            valid=valid,
            terminal=terminal,
            # Overwritten by the fresh root evaluation on the next
            # `_search_carried`; zero keeps the carry deterministic.
            root_value0=jnp.zeros_like(tree.root_value0),
        )
        return CarriedTree(
            tree=promoted,
            valid=promo_valid,
            base=jnp.maximum(retained, 1),
        )

    def zero_carried(self, root_states: EnvState) -> CarriedTree:
        """An all-invalid carry with the right static shapes (scan /
        session-lane initialization; `root_states` only donates shapes)."""
        batch = root_states.done.shape[0]
        n, a = self.num_nodes, self.action_dim

        def broadcast_to_nodes(x):
            # .copy() forces a fresh buffer per leaf: the carry is
            # donated by the rollout chunk, and donating one aliased
            # buffer through two arguments is an XLA error.
            return jnp.broadcast_to(x[:, None], (batch, n) + x.shape[1:]).copy()

        def zeros_na():
            return jnp.zeros((batch, n, a), dtype=jnp.float32)

        return CarriedTree(
            tree=Tree(
                node_state=jax.tree_util.tree_map(
                    broadcast_to_nodes, root_states
                ),
                e_visits=zeros_na(),
                e_value=zeros_na(),
                e_reward=zeros_na(),
                children=jnp.full((batch, n, a), -1.0, dtype=jnp.float32),
                prior=zeros_na(),
                valid=zeros_na(),
                terminal=jnp.zeros((batch, n), dtype=bool),
                root_value0=jnp.zeros((batch,), dtype=jnp.float32),
            ),
            valid=jnp.zeros((batch,), dtype=bool),
            base=jnp.ones((batch,), dtype=jnp.int32),
        )
