"""Batched self-play: fused lockstep rollouts + vectorized n-step pipeline.

Capability parity with the reference's `SelfPlayWorker.run_episode`
(`alphatriangle/rl/self_play/worker.py:166-513`): MCTS per move,
temperature-scheduled action selection, policy targets from visit
counts, n-step returns with value bootstrap, trailing flush of
unmatured experiences at episode end, staleness tagging.

TPU-native redesign (SURVEY.md §7 step 9):
- One `SelfPlayEngine` steps `B` games in lockstep. A whole rollout
  chunk (`play_chunk`) — search -> select -> env step -> n-step window
  update, times `num_moves` — is ONE jitted dispatch: a `lax.scan` over
  moves whose carry holds the env states *and* the n-step window as
  device arrays. The host sees exactly one transfer per chunk (the
  stacked, masked experience outputs), replacing the >=6 blocking
  transfers per move of the round-2 engine.
- There are no per-game actors and no weight broadcast; the engine
  reads the `NeuralNetwork` wrapper's current variables at each chunk,
  so a learner `sync_to_network()` is visible on the next chunk
  (replaces `worker_manager.py:169-209`).
- The n-step machinery is a **vectorized sliding window**: (B, n)
  device arrays of pending experiences with incrementally-maintained
  discounted partial returns, instead of per-game Python deques
  (`worker.py:410-485`). An experience added at move t matures at move
  t+n and is bootstrapped with that search's root value — the
  MCTS-improved estimate of V(s_{t+n}), a strict upgrade over the
  reference's raw network bootstrap (`worker.py:418`).
- Games that finish flush their window without bootstrap (trailing
  flush, `worker.py:466-485`) and are reset in place, so the batch
  never shrinks and shapes stay static. Emissions use fixed-shape
  (moves, B[, n]) buffers with boolean masks; the host compacts them
  after the single device_get.
- Staleness is tracked per episode: each game carries the weights
  version it started under; episode-end records it (finer than the
  reference's per-episode tag at `worker.py:136-139`, which tags with
  the version at *episode start* too — parity, but batched).
"""

import functools
import logging
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..compile_cache import config_digest, get_compile_cache
from ..config.mcts_config import MCTSConfig
from ..config.train_config import TrainConfig
from ..env.engine import EnvState, TriangleEnv
from ..features.core import FeatureExtractor
from ..mcts.gumbel import GumbelMCTS
from ..mcts.helpers import policy_target_from_visits, select_action_from_visits
from ..telemetry.device_stats import (
    beacon_signature,
    beacons_armed,
    device_stats_signature,
    fold_search_stats,
    note_dispatch,
    rollout_chunk_stats,
)
from ..telemetry.flight import flight_span
from ..telemetry.tracer import default_tracer
from ..mcts.search import BatchedMCTS
from ..nn.network import NeuralNetwork
from ..nn.precision import cast_params_for_inference, inference_dtype
from .types import SelfPlayResult

logger = logging.getLogger(__name__)


@struct.dataclass
class RolloutCarry:
    """Device-resident rollout state carried across chunks."""

    env: EnvState  # (B, ...) lockstep game states
    rng: jax.Array  # PRNG key
    pend_grid: jax.Array  # (B, n, C, H, W) float32 pending features
    pend_other: jax.Array  # (B, n, F) float32
    pend_policy: jax.Array  # (B, n, A) float32 pending policy targets
    pend_pweight: jax.Array  # (B, n) float32 policy-loss weight (PCR)
    pend_return: jax.Array  # (B, n) float32 discounted partial returns
    pend_discount: jax.Array  # (B, n) float32 next-reward discounts
    pend_active: jax.Array  # (B, n) bool slot occupancy
    episode_start_version: jax.Array  # (B,) int32 weights version at ep start
    move_index: jax.Array  # () int32 global move counter
    # Promoted search tree carried across moves (mcts/search.py
    # CarriedTree) when MCTSConfig.tree_reuse is on. None (the default)
    # is an EMPTY pytree node: the reuse-off carry flattens to exactly
    # the same leaves as before this field existed, so fresh-root
    # programs, shardings and donation layouts are bit-identical.
    tree: Any = None


class SelfPlayEngine:
    """B games played in lockstep, emitting n-step experiences."""

    def __init__(
        self,
        env: TriangleEnv,
        extractor: FeatureExtractor,
        net: NeuralNetwork,
        mcts_config: MCTSConfig,
        train_config: TrainConfig,
        batch_size: int | None = None,
        seed: int = 0,
        share_compiled: "SelfPlayEngine | None" = None,
        mesh: "jax.sharding.Mesh | None" = None,
        data_axes: tuple = ("dp",),
    ):
        """`share_compiled`: another engine whose jitted chunk programs
        this one reuses (multi-stream rollouts, training/loop.py). The
        rollout computation depends only on configs — carry, weights
        and version are arguments — so identically-configured streams
        must not compile the heaviest program in the codebase N times.

        `mesh`: shard the lockstep lanes over the mesh's `data_axes`
        (B games -> B/n per device, ONE jitted program spanning the
        mesh) so rollouts occupy every chip — the TPU counterpart of
        the reference fanning self-play actors across hardware
        (`alphatriangle/training/worker_manager.py:39-75`). Every lane
        is independent, so GSPMD partitions the chunk program with no
        cross-device collectives; network weights ride replicated (or
        tensor-sharded, if the caller hands mesh-sharded variables —
        the specs compose). None = single-device engine (unchanged).
        """
        self.env = env
        self.extractor = extractor
        self.net = net
        search_cls = (
            GumbelMCTS if mcts_config.root_selection == "gumbel" else BatchedMCTS
        )
        self.mcts = search_cls(
            env, extractor, net.model, mcts_config, net.support
        )
        # Playout cap randomization (KataGo, arXiv:1902.10565 §3.1):
        # a second, cheap search program for the non-policy-training
        # moves — fewer sims, no root noise (exploit, don't explore).
        self.mcts_fast: BatchedMCTS | None = None
        if mcts_config.fast_simulations is not None:
            fast_cfg = mcts_config.model_copy(
                update={
                    "max_simulations": mcts_config.fast_simulations,
                    "dirichlet_epsilon": 0.0,
                }
            )
            fast_kw = (
                # Fast Gumbel searches must exploit, not explore: the
                # PUCT path gets this via temperature 0 at selection,
                # the Gumbel path by zeroing the root Gumbel sample.
                {"exploit": True}
                if search_cls is GumbelMCTS
                else {}
            )
            self.mcts_fast = search_cls(
                env, extractor, net.model, fast_cfg, net.support, **fast_kw
            )
        self.config = train_config
        self.mcts_config = mcts_config
        self.batch_size = batch_size or train_config.SELF_PLAY_BATCH_SIZE
        self.n_step = train_config.N_STEP_RETURNS
        self.gamma = train_config.GAMMA

        b, n = self.batch_size, self.n_step
        c = extractor.model_config.GRID_INPUT_CHANNELS
        f = extractor.other_dim
        a = env.action_dim
        self._grid_shape = (c, env.rows, env.cols)
        self._other_dim = f
        self._action_dim = a

        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self._lane_sharding = None
        self._replicated = None
        # (weights_version, mesh-replicated variables) memo for
        # _place_variables — held on the PRIMARY engine so N rollout
        # streams sharing one net share one replicated copy instead of
        # uploading (and pinning in HBM) N of them.
        self._placed_variables: tuple | None = None
        # (weights_version, inference-cast variables) memo for
        # _inference_variables — same owner-chain sharing.
        self._cast_variables: tuple | None = None
        self._placed_owner: "SelfPlayEngine" = (
            # Follow the chain so every stream lands on one root owner.
            share_compiled._placed_owner
            if share_compiled is not None
            else self
        )
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec

            from ..config.mesh_config import lane_shard_count

            shards = lane_shard_count(mesh, self.data_axes)
            if b % shards != 0:
                raise ValueError(
                    f"SELF_PLAY_BATCH_SIZE={b} must divide evenly over "
                    f"the mesh data axes {self.data_axes} "
                    f"({shards} shards)."
                )
            self._lane_sharding = NamedSharding(
                mesh, PartitionSpec(self.data_axes)
            )
            self._replicated = NamedSharding(mesh, PartitionSpec())

        rng = jax.random.PRNGKey(seed)
        rng, reset_key = jax.random.split(rng)
        version0 = self.net.weights_version
        self._carry = RolloutCarry(
            env=env.reset_batch(jax.random.split(reset_key, b)),
            rng=rng,
            pend_grid=jnp.zeros((b, n, c, env.rows, env.cols), jnp.float32),
            pend_other=jnp.zeros((b, n, f), jnp.float32),
            pend_policy=jnp.zeros((b, n, a), jnp.float32),
            pend_pweight=jnp.ones((b, n), jnp.float32),
            pend_return=jnp.zeros((b, n), jnp.float32),
            pend_discount=jnp.ones((b, n), jnp.float32),
            pend_active=jnp.zeros((b, n), bool),
            episode_start_version=jnp.full((b,), version0, jnp.int32),
            move_index=jnp.int32(0),
        )
        if mcts_config.tree_reuse:
            # Subtree reuse: the promoted tree rides the chunk carry
            # (zero extra dispatches). Starts all-invalid — move 1 of
            # every lane is a fresh-root search.
            self._carry = self._carry.replace(
                tree=self.mcts.zero_carried(self._carry.env)
            )
        if self._lane_sharding is not None:
            self._carry = jax.device_put(
                self._carry, self._carry_shardings()
            )

        # One compiled program per distinct chunk length, carry donated
        # so XLA reuses the window buffers in place.
        if share_compiled is not None:
            if (
                share_compiled.batch_size != self.batch_size
                or share_compiled.mcts_config != self.mcts_config
                or share_compiled.config != self.config
                or share_compiled.mesh is not self.mesh
                or share_compiled.data_axes != self.data_axes
            ):
                raise ValueError(
                    "share_compiled requires identically-configured "
                    "engines (batch size / MCTS / train configs / "
                    "mesh + data axes — jit specializes per input "
                    "sharding, so a mismatch would recompile anyway)."
                )
            self._chunk_fn = share_compiled._chunk_fn
        else:
            # Each distinct chunk length wraps its jitted program in
            # the AOT compile cache: a warm cache (cli warm, a prior
            # run with these configs) deserializes the serialized
            # executable instead of paying the full first-chunk compile
            # — the heaviest program in the codebase, and the one that
            # burned every short healthy chip window in rounds 1-5.
            # The config digest keys everything that shapes the program
            # but is invisible in its input avals (sim counts, n-step,
            # reward params, net architecture).
            chunk_extra = (
                config_digest(
                    self.mcts_config,
                    self.config,
                    extractor.model_config,
                    env.cfg,
                )
                + f"|lanes{self.data_axes if mesh is not None else ()}"
                # Device telemetry shapes the program: the stat-pack
                # adds output leaves, beacons embed host callbacks
                # (which also make the executable non-serializable).
                + device_stats_signature()
                + beacon_signature()
            )

            def chunk_program(num_moves: int):
                def self_play_chunk(variables, carry, version):
                    return self._chunk(num_moves, variables, carry, version)

                # The HLO module's name: what a device trace calls the
                # program (a `functools.partial` reads `jit__unknown`).
                self_play_chunk.__name__ = f"self_play_chunk_t{num_moves}"
                return get_compile_cache().wrap(
                    f"self_play_chunk/t{num_moves}",
                    jax.jit(self_play_chunk, donate_argnums=(1,)),
                    extra=chunk_extra,
                    serialize=not beacons_armed(),
                )

            self._chunk_fn = functools.lru_cache(maxsize=None)(chunk_program)

        # Oldest weights version contributing to the current harvest
        # window (conservative chunk-level tag; per-episode tags ride in
        # episode_start_versions). None = window not started.
        self._min_weights_version: int | None = None
        self._out: list[tuple[np.ndarray, ...]] = []
        self._episode_scores: list[float] = []
        self._episode_lengths: list[int] = []
        self._episode_start_versions: list[int] = []
        self._episodes_played = 0
        self._episodes_truncated = 0
        self._total_simulations = 0
        # Root visits inherited from carried subtrees (tree_reuse);
        # summed with simulations this gives leaf-equivalent search
        # effort (leaf-evals/s in telemetry/perf.py).
        self._total_reused_visits = 0
        # A routed trunk's counters (nn/trunk.py), summed like the
        # simulations: assignments each held expert computed, (sparse
        # layers, held), the assignments routed to any expert, and the
        # tokens the linear layers' recurrence took.
        self._expert_tokens: np.ndarray | None = None
        self._routed_assignments = 0
        self._linear_tokens = 0
        # Cumulative host-blocking harvest-fetch seconds (the chunk's
        # device_get — includes any wait for the chunk to finish, i.e.
        # the host-visible round-trip cost telemetry/perf.py reports).
        # Lock-guarded: producer threads fetch concurrently.
        self.transfer_d2h_seconds = 0.0
        self._transfer_lock = threading.Lock()
        # Rollout program dispatches (telemetry: the loop's dispatches-
        # per-iteration gauge; lock-guarded with the transfer time).
        self.dispatch_count = 0
        # Dispatch flight recorder (telemetry/flight.py), attached by
        # training/setup.py; None = no intent/seal records written.
        self.flight = None
        # (T, B) per-move diagnostics of the most recent chunk.
        self.last_trace: dict[str, np.ndarray] | None = None
        # Device telemetry (telemetry/device_stats.py): the searches'
        # stat-pack flag, snapshotted at construction like the MCTS
        # instances themselves. When on, `last_device_stats` holds the
        # most recent chunk's folded search + rollout legs.
        self.device_stats = self.mcts.device_stats
        self.last_device_stats: dict | None = None

    # --- multi-chip lane sharding -----------------------------------------

    def _carry_shardings(self) -> RolloutCarry:
        """Sharding pytree matching the carry: every (B, ...) leaf
        shards its lane dim over the mesh's data axes; the single PRNG
        key and the scalar move counter replicate."""
        lane, rep = self._lane_sharding, self._replicated
        return RolloutCarry(
            env=jax.tree_util.tree_map(lambda _: lane, self._carry.env),
            rng=rep,
            pend_grid=lane,
            pend_other=lane,
            pend_policy=lane,
            pend_pweight=lane,
            pend_return=lane,
            pend_discount=lane,
            pend_active=lane,
            episode_start_version=lane,
            move_index=rep,
            # Every CarriedTree leaf is (B, ...): lane-sharded like the
            # env states. None (reuse off) stays the empty pytree node.
            tree=(
                None
                if self._carry.tree is None
                else jax.tree_util.tree_map(lambda _: lane, self._carry.tree)
            ),
        )

    def _place_variables(self, variables, version: int):
        """Place net weights for a mesh-spanning chunk dispatch.

        Weights already sharded on THIS mesh (e.g. the trainer's
        tensor-parallel specs after a zero-copy sync) pass through —
        their specs compose with the lane sharding, giving TP network
        evals inside the search. Anything else (fresh init committed to
        one device, checkpoint restore) is replicated across the mesh;
        mixing single-device-committed and mesh-sharded args in one jit
        is an error JAX refuses at dispatch time. The replicated copy
        is cached per weights version — without it every chunk of a
        pre-first-sync run would re-upload the full network.
        """
        if self.mesh is None:
            return variables
        from jax.sharding import NamedSharding

        leaf = jax.tree_util.tree_leaves(variables)[0]
        sh = getattr(leaf, "sharding", None)
        owner = self._placed_owner
        if isinstance(sh, NamedSharding) and sh.mesh == self.mesh:
            # Trainer-sharded weights took over: drop any pre-sync
            # replicated copy so it doesn't pin a dead full-model
            # buffer in HBM for the rest of the run.
            owner._placed_variables = None
            return variables
        if owner._placed_variables is not None:
            cached_version, placed = owner._placed_variables
            if cached_version == version:
                return placed
        placed = jax.device_put(variables, self._replicated)
        owner._placed_variables = (version, placed)
        return placed

    def _inference_variables(self, variables, version: int):
        """Apply the inference precision policy (nn/precision.py) to
        the net variables before a chunk dispatch: a bf16 copy under
        INFERENCE_PRECISION="bfloat16", the original object under f32.
        Memoized per weights version on the primary engine (the
        `_place_variables` owner chain) so N rollout streams share one
        cast copy; `astype` preserves NamedShardings, so the cast
        composes with mesh placement."""
        if inference_dtype(self.extractor.model_config) == jnp.float32:
            return variables
        owner = self._placed_owner
        if owner._cast_variables is not None:
            cached_version, cast = owner._cast_variables
            if cached_version == version:
                return cast
        cast = cast_params_for_inference(
            variables, self.extractor.model_config
        )
        owner._cast_variables = (version, cast)
        return cast

    # --- device-side chunk ------------------------------------------------

    def _temperatures(self, step_counts: jax.Array) -> jax.Array:
        """Per-game move-indexed temperature (reference `worker.py:311-332`)."""
        cfg = self.config
        frac = jnp.minimum(
            step_counts.astype(jnp.float32) / cfg.TEMPERATURE_ANNEAL_MOVES, 1.0
        )
        return cfg.TEMPERATURE_INITIAL + frac * (
            cfg.TEMPERATURE_FINAL - cfg.TEMPERATURE_INITIAL
        )

    def _move_body(self, variables, version, carry: RolloutCarry, _):
        """One lockstep move of all B games (scan body)."""
        n = self.n_step
        w = carry.move_index % n
        states = carry.env
        rng, k_search, k_select, k_reset, k_mode = jax.random.split(
            carry.rng, 5
        )

        # 1-2. Features for replay + batched search (one MXU leaf batch
        # per simulation across all B games). Under playout cap
        # randomization the whole lockstep move is a full search with
        # prob `full_search_prob`, else the cheap fast search — a
        # per-move (not per-game) draw, which keeps the batch lanes in
        # lockstep while matching KataGo's per-move distribution.
        with jax.named_scope("rollout/features"):
            grids, others = jax.vmap(self.extractor.extract)(states)
        final_tree = None
        reused = None
        if self.mcts_config.tree_reuse:
            # Subtree reuse (incompatible with PCR/Gumbel — config-
            # validated): seed this move's search with the carried
            # promoted tree; lanes with an invalid carry run fresh.
            out, final_tree, reused = self.mcts._search_carried(
                variables, states, k_search, carry.tree
            )
            is_full = jnp.bool_(True)
            sims_this_move = jnp.int32(self.mcts_config.max_simulations)
        elif self.mcts_fast is None:
            out = self.mcts._search(variables, states, k_search)
            is_full = jnp.bool_(True)
            sims_this_move = jnp.int32(self.mcts_config.max_simulations)
        else:
            is_full = jax.random.bernoulli(
                k_mode, self.mcts_config.full_search_prob
            )
            out = jax.lax.cond(
                is_full,
                lambda: self.mcts._search(variables, states, k_search),
                lambda: self.mcts_fast._search(variables, states, k_search),
            )
            sims_this_move = jnp.where(
                is_full,
                self.mcts_config.max_simulations,
                self.mcts_config.fast_simulations,
            ).astype(jnp.int32)
        with jax.named_scope("rollout/targets"):
            valid = jax.vmap(self.env.valid_action_mask)(states)
            if self.mcts_config.root_selection == "gumbel":
                # Completed-Q improved policy (mcts/gumbel.py) — a
                # policy-improvement operator, not a visit histogram.
                policy = out.improved_policy
            else:
                policy = policy_target_from_visits(out.visit_counts, valid)
            pweight = jnp.where(is_full, 1.0, 0.0)

            # 3. Mature the slot added n moves ago: bootstrap with this
            # search's root value (the MCTS estimate of V(s_t) =
            # V(s_{t-n+n})).
            mat_mask = carry.pend_active[:, w]
            if (
                self.mcts_fast is not None
                and not self.mcts_config.pcr_record_fast_rows
            ):
                # KataGo-faithful playout cap randomization: positions
                # searched cheaply never become training rows (their
                # targets — noisy fast-search policy AND the n-step
                # value whose bootstrap is a fast root — are below
                # training quality; measured in docs/MCTS_DESIGN.md §e).
                mat_mask = mat_mask & (carry.pend_pweight[:, w] > 0.5)
            mat = {
                "grid": carry.pend_grid[:, w],
                "other": carry.pend_other[:, w],
                "policy": carry.pend_policy[:, w],
                "pw": carry.pend_pweight[:, w],
                "ret": carry.pend_return[:, w]
                + carry.pend_discount[:, w] * out.root_value,
                "mask": mat_mask,
            }
            pend_active = carry.pend_active.at[:, w].set(False)

        with jax.named_scope("rollout/env_step"):
            # 4. Select actions and step all games in one vmapped
            # transition. PUCT: temperature-scheduled sampling from visit
            # counts; Gumbel: the search already resolved the argmax of
            # g + logits + sigma(q) (exploration IS the Gumbel sample).
            if self.mcts_config.root_selection == "gumbel":
                actions = out.selected_action
            else:
                temps = self._temperatures(states.step_count)
                if self.mcts_fast is not None:
                    # Playout-cap fast moves play GREEDILY (KataGo §3.1):
                    # they exist to advance the game with the best cheap
                    # decision, not to explore — temperature on a handful
                    # of visits is near-uniform noise, and training on the
                    # resulting near-random trajectories degrades the value
                    # head (measured: greedy eval 7.53 -> 6.82 before this
                    # guard). Exploration stays on full-search moves.
                    temps = jnp.where(is_full, temps, 0.0)
                actions = select_action_from_visits(
                    out.visit_counts, temps, k_select
                )
            # Sentinel guard: -1 (zero root visits) only happens for finished
            # games, where step() is a no-op; count live-game sentinels so the
            # host can surface the anomaly instead of silently clamping.
            sentinel_live = ((actions < 0) & ~states.done).sum(dtype=jnp.int32)
            actions = jnp.maximum(actions, 0)
            new_states, rewards, dones = jax.vmap(self.env.step)(states, actions)

        with jax.named_scope("rollout/targets"):
            # 5. Add this move's experience into window slot w.
            pend_grid = carry.pend_grid.at[:, w].set(grids)
            pend_other = carry.pend_other.at[:, w].set(others)
            pend_policy = carry.pend_policy.at[:, w].set(policy)
            pend_pweight = carry.pend_pweight.at[:, w].set(pweight)
            pend_return = carry.pend_return.at[:, w].set(0.0)
            pend_discount = carry.pend_discount.at[:, w].set(1.0)
            pend_active = pend_active.at[:, w].set(True)

            # 6. Fold this move's reward into every pending experience.
            pend_return = pend_return + jnp.where(
                pend_active, pend_discount * rewards[:, None], 0.0
            )
            pend_discount = jnp.where(
                pend_active, pend_discount * self.gamma, 1.0
            )

            # 7. Trailing flush for finished (or move-capped) games: emit all
            # pending slots without bootstrap (`worker.py:466-485`).
            step_counts = new_states.step_count
            truncated = (~dones) & (step_counts >= self.config.MAX_EPISODE_MOVES)
            ending = dones | truncated
            flush_mask = pend_active & ending[:, None]
            if (
                self.mcts_fast is not None
                and not self.mcts_config.pcr_record_fast_rows
            ):
                flush_mask = flush_mask & (pend_pweight > 0.5)
            flush = {
                "grid": pend_grid,
                "other": pend_other,
                "policy": pend_policy,
                "pw": pend_pweight,
                "ret": pend_return,
                "mask": flush_mask,
            }
            pend_active = pend_active & ~ending[:, None]

            episode = {
                "ending": ending,
                # Truncated = hit MAX_EPISODE_MOVES rather than a natural
                # game over; a high fraction means the cap is biting (the
                # health signal the reference's get_game_over_reason
                # served, `worker.py:196`).
                "truncated": truncated,
                "score": new_states.score,
                "length": step_counts,
                "start_version": carry.episode_start_version,
            }

        with jax.named_scope("rollout/reset"):
            # 8. Reset finished games in place; batch shape never changes.
            new_states = new_states.replace(done=ending)
            reset_states = self.env.reset_where_done(new_states, k_reset)
            episode_start_version = jnp.where(
                ending, version, carry.episode_start_version
            )

        # 9. Root promotion for the next move (subtree reuse): compact
        # the played action's subtree into the leading rows; ending
        # lanes reset to a fresh search (their next root is a new game).
        new_tree = carry.tree
        if final_tree is not None:
            new_tree = self.mcts.promote(final_tree, actions)
            new_tree = new_tree.replace(valid=new_tree.valid & ~ending)

        new_carry = RolloutCarry(
            env=reset_states,
            rng=rng,
            pend_grid=pend_grid,
            pend_other=pend_other,
            pend_policy=pend_policy,
            pend_pweight=pend_pweight,
            pend_return=pend_return,
            pend_discount=pend_discount,
            pend_active=pend_active,
            episode_start_version=episode_start_version,
            move_index=carry.move_index + 1,
            tree=new_tree,
        )
        outputs = {
            "mat": mat,
            "flush": flush,
            "episode": episode,
            "sentinel_live": sentinel_live,
            # Per-move diagnostics (tiny (B,) rows): lets tests validate
            # the windowed n-step math against an independent reference
            # without reaching inside the traced computation.
            "trace": {
                "root_value": out.root_value,
                "reward": rewards,
                "ending": ending,
                # Orphan node slots this search (duplicate/revisited
                # edges) — the waste the no-tree-reuse design accepts.
                "wasted_slots": out.wasted_slots,
                # Playout-cap accounting: sims actually run this move
                # and whether it was a full (policy-training) search.
                "sims": sims_this_move,
                "is_full": is_full,
                # Root visits inherited from the carried subtree this
                # move (0 with reuse off) — the leaf evaluations the
                # search did not have to spend; feeds leaf-evals/s.
                "reused": (
                    reused
                    if reused is not None
                    else jnp.zeros_like(out.root_value)
                ),
            },
            # Search stat-pack (None when DEVICE_STATS is off — an
            # empty pytree node, so the off-path program is unchanged).
            # (T,·)-stacked by the scan; rides the chunk's one fetch.
            "device_stats": out.stats,
        }
        if out.net_counters is not None:
            # A routed trunk's counters for this move's search
            # (nn/trunk.py): the assignments each held expert computed,
            # (sparse layers, held) int32, all the router made, and
            # with linear or state-space layers the tokens their
            # recurrence took (`linear_tokens`, `ssm_tokens`).
            outputs["trace"].update(out.net_counters)
        return new_carry, outputs

    def _chunk(self, num_moves: int, variables, carry: RolloutCarry, version):
        """`num_moves` lockstep moves as one scanned computation."""
        body = functools.partial(self._move_body, variables, version)
        return jax.lax.scan(body, carry, None, length=num_moves)

    # --- host API ---------------------------------------------------------

    @property
    def states(self) -> EnvState:
        """Current (device-resident) batched game states."""
        return self._carry.env

    def play_chunk(
        self, num_moves: int | None = None, fetch_experiences: bool = True
    ) -> "dict | None":
        """Advance every game `num_moves` moves in ONE jitted dispatch.

        `fetch_experiences=False` is the device-replay path: the dense
        masked experience outputs (the overwhelming bulk of a chunk's
        payload) are NOT transferred — they return as device arrays for
        `DeviceReplayBuffer.ingest_payload` to scatter into the
        on-device ring; only episode stats + diagnostics (KBs) are
        fetched. Returns that device payload, or None in fetch mode.
        """
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        version = self.net.weights_version
        self._min_weights_version = (
            version
            if self._min_weights_version is None
            else min(self._min_weights_version, version)
        )
        tracer = default_tracer()
        lanes = self.batch_size
        with flight_span(
            self.flight,
            "rollout",
            f"self_play_chunk/t{t}",
            avals=f"B{self.batch_size}xT{t}",
        ):
            with tracer.span("rollout.dispatch", t=t, lanes=lanes):
                note_dispatch(f"self_play_chunk/t{t}")
                self._carry, outputs = self._chunk_fn(t)(
                    self._place_variables(
                        self._inference_variables(self.net.variables, version),
                        version,
                    ),
                    self._carry,
                    jnp.int32(version),
                )
            payload: dict | None = None
            t0 = time.perf_counter()
            with tracer.span("rollout.wait", t=t, lanes=lanes):
                if fetch_experiences:
                    host = jax.device_get(outputs)  # graftlint: allow(host-sync-in-hot-path) the one transfer per chunk
                else:
                    payload = {
                        "mat": outputs.pop("mat"),
                        "flush": outputs.pop("flush"),
                    }
                    host = jax.device_get(outputs)  # graftlint: allow(host-sync-in-hot-path) stats + trace only (small)
        dt = time.perf_counter() - t0
        with self._transfer_lock:
            self.transfer_d2h_seconds += dt
            self.dispatch_count += 1
        with tracer.span("rollout.fold", t=t, lanes=lanes) as folded:
            # Under playout cap randomization the per-move sim count
            # varies; the trace records what actually ran.
            self._total_simulations += (
                int(host["trace"]["sims"].sum()) * self.batch_size
            )
            # The chunk's time follows its full searches: the count
            # goes on the span that folds the fetch that brought it.
            folded["full_moves"] = int(host["trace"]["is_full"].sum())
            self._total_reused_visits += int(host["trace"]["reused"].sum())
            if "expert_tokens" in host["trace"]:
                tokens = host["trace"]["expert_tokens"].sum(axis=0, dtype=np.int64)
                self._expert_tokens = (
                    tokens
                    if self._expert_tokens is None
                    else self._expert_tokens + tokens
                )
                self._routed_assignments += int(
                    host["trace"]["routed"].sum(dtype=np.int64)
                )
            if "linear_tokens" in host["trace"]:
                self._linear_tokens += int(
                    host["trace"]["linear_tokens"].sum(dtype=np.int64)
                )

            self.last_trace = host["trace"]
            if self.device_stats:
                # Search leg folded from the fetched stat-pack; rollout
                # leg is a pure host fold over arrays the fetch ALREADY
                # carried (per-step-of-T terminations, reward extremes).
                self.last_device_stats = {
                    "search": fold_search_stats(host.get("device_stats")),
                    "rollout": rollout_chunk_stats(
                        host["episode"]["ending"], host["trace"]["reward"]
                    ),
                }
            episode = host["episode"]
            self._fold_episode_stats(episode)
            sentinels = int(host["sentinel_live"].sum())
            if sentinels:
                logger.warning(
                    "SelfPlay: %d zero-visit sentinel actions on LIVE games "
                    "(clamped to action 0) — root search produced no visits.",
                    sentinels,
                )
        if not fetch_experiences:
            return payload
        mat, flush = host["mat"], host["flush"]
        mmask = mat["mask"]  # (T, B)
        if mmask.any():
            self._out.append(
                (
                    mat["grid"][mmask],
                    mat["other"][mmask],
                    mat["policy"][mmask],
                    mat["ret"][mmask].astype(np.float32),
                    mat["pw"][mmask].astype(np.float32),
                )
            )
        fmask = flush["mask"]  # (T, B, n)
        if fmask.any():
            self._out.append(
                (
                    flush["grid"][fmask],
                    flush["other"][fmask],
                    flush["policy"][fmask],
                    flush["ret"][fmask].astype(np.float32),
                    flush["pw"][fmask].astype(np.float32),
                )
            )
        return None

    def _fold_episode_stats(self, episode: dict) -> None:
        """Accumulate finished-episode stats from one chunk's outputs."""
        ending = episode["ending"]  # (T, B)
        if ending.any():
            self._episode_scores.extend(
                episode["score"][ending].astype(float).tolist()
            )
            self._episode_lengths.extend(
                episode["length"][ending].astype(int).tolist()
            )
            self._episode_start_versions.extend(
                episode["start_version"][ending].astype(int).tolist()
            )
            self._episodes_played += int(ending.sum())
            self._episodes_truncated += int(episode["truncated"][ending].sum())

    def warm_chunk(self, num_moves: int | None = None) -> bool:
        """AOT-precompile the rollout chunk program WITHOUT running it.

        Lowers with the engine's real (variables, carry, version)
        arguments — so the cache signature matches what `play_chunk`
        will dispatch — and either deserializes a cached executable or
        compiles + serializes one. Lowering never executes or donates;
        the carry is untouched. Returns True when an AOT executable is
        ready (`cli warm`)."""
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        version = self.net.weights_version
        return self._chunk_fn(t).warm(
            self._place_variables(
                self._inference_variables(self.net.variables, version),
                version,
            ),
            self._carry,
            jnp.int32(version),
        )

    def analyze_chunk(self, num_moves: int | None = None) -> "dict | None":
        """Memory record of the rollout chunk program at this engine's
        real dispatch avals (telemetry/memory.py) — AOT analysis only,
        nothing executes and the carry is untouched (`cli fit`). The
        rollout family's `cost_analysis()` record rides the same
        compile (telemetry/roofline.py)."""
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        version = self.net.weights_version
        return self._chunk_fn(t).analyze(
            self._place_variables(
                self._inference_variables(self.net.variables, version),
                version,
            ),
            self._carry,
            jnp.int32(version),
        )

    def play_move(self) -> None:
        """Advance every game by one move (single-move chunk)."""
        self.play_chunk(1)

    def play_moves(self, num_moves: int) -> SelfPlayResult:
        """Advance all games `num_moves` moves and harvest experiences."""
        self.play_chunk(num_moves)
        return self.harvest()

    def play_moves_device(
        self, num_moves: int
    ) -> tuple[SelfPlayResult, dict]:
        """Device-replay variant of `play_moves`: experiences never
        leave the device. Returns (stats-only harvest, device payload
        for `DeviceReplayBuffer.ingest_payload`)."""
        payload = self.play_chunk(num_moves, fetch_experiences=False)
        assert payload is not None
        return self.harvest(), payload

    def harvest(self) -> SelfPlayResult:
        """Collect emitted experiences + episode stats since last call."""
        if self._out:
            grids = np.concatenate([o[0] for o in self._out])
            others = np.concatenate([o[1] for o in self._out])
            policies = np.concatenate([o[2] for o in self._out])
            values = np.concatenate([o[3] for o in self._out])
            pweights = np.concatenate([o[4] for o in self._out])
        else:
            c, h, w = self._grid_shape
            grids = np.zeros((0, c, h, w), np.float32)
            others = np.zeros((0, self._other_dim), np.float32)
            policies = np.zeros((0, self._action_dim), np.float32)
            values = np.zeros((0,), np.float32)
            pweights = np.zeros((0,), np.float32)
        result = SelfPlayResult(
            grid=grids,
            other_features=others,
            policy_target=policies,
            value_target=values,
            policy_weight=pweights,
            episode_scores=self._episode_scores,
            episode_lengths=self._episode_lengths,
            episode_start_versions=self._episode_start_versions,
            num_episodes=self._episodes_played,
            num_truncated=self._episodes_truncated,
            total_simulations=self._total_simulations,
            total_reused_visits=self._total_reused_visits,
            expert_tokens=self._expert_tokens,
            routed_assignments=self._routed_assignments,
            linear_tokens=self._linear_tokens,
            trainer_step_at_episode_start=(
                self._min_weights_version
                if self._min_weights_version is not None
                else self.net.weights_version
            ),
        )
        self._out = []
        self._episode_scores = []
        self._episode_lengths = []
        self._episode_start_versions = []
        self._episodes_played = 0
        self._episodes_truncated = 0
        self._total_simulations = 0
        self._total_reused_visits = 0
        self._expert_tokens = None
        self._routed_assignments = 0
        self._linear_tokens = 0
        self._min_weights_version = None
        return result
