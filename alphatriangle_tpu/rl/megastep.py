"""Anakin-style fused megastep: rollout chunk + ring ingest + K learner
steps as ONE device program (Podracer, arXiv:2104.06272 §2 "Anakin").

A pre-chip CPU measurement showed the cost of host-orchestrated phases: the
overlapped loop ran at 0.774x of serialized self-play and fused learner
steps gained nothing (0.44 -> 0.45 steps/s), because every iteration
pays per-phase host round trips — dispatch chunk, fetch, fold, sample,
dispatch learner — and the phases contend in the device FIFO instead of
composing. Anakin's answer is to keep acting, replay and learning
inside one XLA program so the only host work per iteration is fetching
metrics. This module composes the three seams the codebase already has
into that program:

- `SelfPlayEngine._chunk` (rl/self_play.py): `ROLLOUT_CHUNK_MOVES`
  lockstep moves of all B games, driven by the learner's *current
  on-device params* (`TrainState.params`), so weight sync is free and
  ZERO-staleness — there is no `sync_to_network` copy on the hot path,
  and every move of every megastep searches with the newest weights.
- `ring_scatter` (rl/device_buffer.py): the chunk's masked experience
  outputs go straight into the device-resident replay ring (the rows
  that pass validation, written as windows of consecutive slots) —
  nothing is fetched, nothing is re-uploaded.
- `Trainer._train_steps_from_impl` (rl/trainer.py): K training batches
  are sampled ON DEVICE from the ring (stratified proportional PER over
  a device-resident priority array, or uniform), gathered, and run as K
  fused SGD steps.

Only stats/metrics/TD summaries return to the host: ONE dispatch and
ONE `device_get` per iteration, counter-asserted in the tests.

PER semantics (host mirror reconciliation):

The priority array lives on device and is the sampling truth inside the
program: freshly ingested rows get max-priority init before sampling,
and the group's TD errors update priorities in step order after the
fused steps ((|δ|+ε)^α — the same formula as the host SumTree). The
host SumTree stays alive as a *mirror*, reconciled at megastep
boundaries from the returned (slots, TD errors): it serves beta
annealing, readiness gating, the max-priority watermark, metrics and —
critically — buffer persistence, so checkpoints and resume are
interchangeable with the other loop modes. `sync_priorities_from_host`
(re)seeds the device array from the mirror after restores/warmup.

dp-sharded megastep (multi-device meshes):

On a single-process dp-only mesh the SAME fused program spans every
device (program family `megastep/dp<D>_t<T>_k<K>`), composing the three
sharded seams the codebase already has:

- the rollout chunk runs lane-sharded under GSPMD (each device plays
  its B/dp games — lanes are independent, so no collectives appear);
- ONE `jax.shard_map` region does
  the per-shard replay work with no collectives except a weight-norm
  `pmax`: every shard ring-scatters ITS lanes' rows into ITS ring shard
  (`ShardedDeviceReplayBuffer.scatter_local`, cap_local slots + a trash
  row), max-priority-inits them in its slice of the dp-sharded priority
  array, samples its B/dp stratum of each of the K batches from that
  device-local slice (`sample_local`, per-shard rng via
  `fold_in(key, axis_index)`), IS-normalizes against the global batch
  max (`pmax` over dp), and gathers its sampled rows locally — indices
  come back globally encoded as `shard * stride + slot`;
- the K learner steps run on the dp-sharded stacked batch under GSPMD
  with replicated params: the gradient `psum` over dp is inserted by
  XLA from the shardings (the repo-wide idiom — rl/trainer.py spells no
  collective by hand), so params stay bit-identical on every shard;
- a second small `shard_map` writes the K steps' TD-error priorities
  back into each shard's priority slice, in step order.

Host reconciliation generalizes per shard: the program returns (dp,)
per-shard counts + globally-encoded (K, B) sampled indices + TD errors,
and the host replays them into the per-shard SumTree mirrors
(`ShardedDeviceReplayBuffer.reconcile_ingest` at the SAME pre-dispatch
max-priority watermark the device sampled against, then
`update_priorities` routed by the global index encoding). Checkpoints
keep flowing through the buffer's snapshot contract, so resume is
interchangeable with sync/overlapped/single-device-megastep runs.

Scope: single-process; single-device mesh, or a dp-only mesh whose
capacity/batch/lanes divide dp (the `ShardedDeviceReplayBuffer` gate in
training/setup.py). Sharded sampling draws per-shard strata with
per-shard keys, so sampled BATCHES differ from a single-device run at
the same seed — the pinned invariants are params bit-identical across
shards and device/host priority agreement per shard
(tests/test_megastep_sharded.py).

CPU note: the program contains learner steps, so it rides
`cpu_aot=False` like the rest of the learner family (an XLA:CPU
deserialized executable of a donating learner program returns the train
state UNCHANGED — see rl/trainer.py). The donation/reload regression
guard (params actually update across megasteps) is pinned in
tests/test_megastep.py.
"""

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import config_digest, get_compile_cache
from ..config.train_config import TrainConfig
from ..nn.precision import cast_params_for_inference
from ..ops import per_sample
from ..telemetry.device_stats import (
    beacon_signature,
    beacons_armed,
    device_stats_signature,
    emit_beacon,
    fold_search_stats,
    note_dispatch,
    rollout_chunk_stats,
)
from ..telemetry.flight import flight_span
from ..telemetry.tracer import default_tracer
from .device_buffer import DeviceReplayBuffer, read_rows, ring_read, ring_scatter

logger = logging.getLogger(__name__)


class MegastepRunner:
    """Owns the fused megastep program binding one (engine, trainer,
    device ring) triple; the training loop's third mode
    (`TrainConfig.FUSED_MEGASTEP`) drives it one call per iteration."""

    def __init__(
        self,
        engine,
        trainer,
        buffer: DeviceReplayBuffer,
        train_config: TrainConfig,
    ):
        if not getattr(buffer, "is_device", False):
            raise ValueError(
                "MegastepRunner needs a device-resident replay ring "
                "(rl/device_buffer.DeviceReplayBuffer, or the dp-sharded "
                "rl/sharded_device_buffer.ShardedDeviceReplayBuffer)."
            )
        if jax.process_count() > 1:
            raise ValueError("MegastepRunner is single-process only.")
        self.sharded = bool(getattr(buffer, "is_sharded", False))
        if self.sharded:
            # The fused program's shard_map region pairs each device's
            # rollout lanes with its own ring shard: the engine must
            # shard its lanes over exactly the ring's mesh + dp axis.
            if engine.mesh is None or engine.mesh != buffer.mesh:
                raise ValueError(
                    "Sharded megastep: the self-play engine must shard "
                    "its lanes over the replay ring's mesh (got engine "
                    f"mesh {engine.mesh}, ring mesh {buffer.mesh})."
                )
            if tuple(engine.data_axes) != (buffer.dp_axis,):
                raise ValueError(
                    "Sharded megastep: engine lanes must ride exactly "
                    f"the ring's dp axis ({buffer.dp_axis!r}); got "
                    f"{tuple(engine.data_axes)}."
                )
            if trainer.mesh != buffer.mesh:
                raise ValueError(
                    "Sharded megastep: trainer and replay ring must "
                    "share one mesh."
                )
            if train_config.BATCH_SIZE % buffer.dp != 0:
                raise ValueError(
                    f"BATCH_SIZE={train_config.BATCH_SIZE} must divide "
                    f"over dp={buffer.dp} (each shard samples its B/dp "
                    "stratum in-program)."
                )
        elif engine.mesh is not None:
            raise ValueError(
                "MegastepRunner with the single-device ring needs a "
                "single-device engine; mesh-sharded lanes pair with the "
                "dp-sharded ring (ShardedDeviceReplayBuffer)."
            )
        self.engine = engine
        self.trainer = trainer
        self.buffer = buffer
        self.config = train_config
        self.batch_size = train_config.BATCH_SIZE
        self.cap = buffer.capacity
        self.dp = buffer.dp if self.sharded else 1
        self.use_per = train_config.USE_PER
        self.per_alpha = float(train_config.PER_ALPHA)
        self.per_epsilon = float(train_config.PER_EPSILON)
        self.beta_initial = float(train_config.PER_BETA_INITIAL)
        self.beta_final = float(train_config.PER_BETA_FINAL)
        self.beta_anneal = float(train_config.PER_BETA_ANNEAL_STEPS or 1)
        # Device-resident priority array — the sampling truth inside
        # the program. Single-device: (cap + 1,) float32, the +1 the
        # trash slot pinned at priority 0 so it is never sampled.
        # Sharded: (dp * stride,) float32 sharded over dp, one trash
        # slot per shard at local index cap_local. None until
        # `sync_priorities_from_host` seeds it (lazily on the first
        # megastep, or explicitly after a checkpoint restore).
        self._priorities: jax.Array | None = None
        # One compiled program per distinct (chunk moves, K) pair, AOT
        # cached. cpu_aot=False: the program donates + updates the train
        # state, the exact family whose XLA:CPU deserialization silently
        # returns donated state unchanged (rl/trainer.py).
        # Device telemetry plane (telemetry/device_stats.py): the
        # stat-pack flag rides the engine's searches (snapshotted at
        # engine construction) and adds output leaves; beacons embed
        # host callbacks. Both shape the program, so both join the
        # cache extra, and beacon-armed executables skip serialization.
        self.device_stats = bool(getattr(engine, "device_stats", False))
        self.last_device_stats: "dict | None" = None
        extra = (
            config_digest(
                engine.mcts_config,
                train_config,
                trainer.nn.model_config,
                engine.env.cfg,
            )
            + (
                f"|att{int(getattr(trainer.nn.model, 'attention_fn', None) is not None)}"
            )
            + device_stats_signature()
            + beacon_signature()
        )
        impl = self._sharded_impl if self.sharded else self._impl
        name = (
            (lambda t, k: f"megastep/dp{self.dp}_t{t}_k{k}")
            if self.sharded
            else (lambda t, k: f"megastep/t{t}_k{k}")
        )
        self._name_fn = name
        self._megastep_fn = functools.lru_cache(maxsize=None)(
            lambda t, k: get_compile_cache().wrap(
                name(t, k),
                jax.jit(
                    functools.partial(impl, t, k),
                    donate_argnums=(0, 1, 2, 3),
                ),
                extra=extra,
                cpu_aot=False,
                serialize=not beacons_armed(),
            )
        )
        # Observability: program dispatches (the loop's one-dispatch-
        # per-iteration assertion reads this) and blocking fetch time
        # (telemetry/perf.py transfer accounting).
        self.dispatch_count = 0
        self.transfer_d2h_seconds = 0.0
        # Flight recorder (telemetry/flight.py); training/setup.py and
        # the loop's lazy construction path attach the run's recorder.
        self.flight = None

    # --- device program ---------------------------------------------------

    def _sample_indices(self, priorities, size, state, k: int):
        """On-device (K, B) slot sampling + IS weights.

        PER: stratified proportional sampling over the priority array
        (ops/per_sample.py, a searchsorted over the cumsum) — the
        vectorized equivalent of the host SumTree's stratified descent
        (utils/sumtree.py). Zero-priority (empty/trash) slots are never
        selected: their cumsum segments are empty. Uniform:
        floor(u * size).
        """
        b = self.batch_size
        rng, k_sample = jax.random.split(state.rng)
        state = state.replace(rng=rng)
        if self.use_per:
            idx, probs = per_sample(priorities, self.cap, k, b, k_sample)
            # Beta annealed on the learner-step clock, exactly as the
            # host mirror's `ExperienceBuffer.beta` computes it.
            frac = jnp.clip(
                state.step.astype(jnp.float32) / self.beta_anneal, 0.0, 1.0
            )
            beta = self.beta_initial + frac * (
                self.beta_final - self.beta_initial
            )
            w = (size.astype(jnp.float32) * probs) ** (-beta)
            weights = (
                w / jnp.max(w, axis=1, keepdims=True)
            ).astype(jnp.float32)
        else:
            u = jax.random.uniform(k_sample, (k, b))
            idx = jnp.clip(
                jnp.floor(u * size.astype(jnp.float32)).astype(jnp.int32),
                0,
                jnp.maximum(size - 1, 0),
            )
            weights = jnp.ones((k, b), jnp.float32)
        return state, idx, weights

    def _per_stat_pack(self, priorities, weights) -> dict:
        """Ingest/PER stat leg of the device stat-pack: priority-mass
        skew (max over mean of the live slots — empty and trash slots
        sit at exactly 0 so the mask is free) and the IS-weight
        extremes of the K sampled batches. Pure reductions over arrays
        already in the program; rides the one fetch."""
        live = priorities
        count = jnp.maximum((live > 0).sum(), 1).astype(jnp.float32)
        mean_live = jnp.maximum(live.sum() / count, 1e-9)
        return {
            "priority_skew": live.max() / mean_live,
            "is_weight_min": weights.min(),
            "is_weight_max": weights.max(),
        }

    def _impl(
        self,
        num_moves: int,
        k: int,
        state,
        carry,
        storage,
        priorities,
        cursor,
        size,
        max_priority,
    ):
        """The fused megastep (pure; donated: state, carry, storage,
        priorities). Returns (state', carry', storage', priorities',
        host outputs) — the host outputs are the ONLY fetch."""
        # 1. Rollout chunk with the learner's live params: weight sync
        # is the absence of a copy. The version tag for staleness
        # accounting is the learner step itself (zero staleness by
        # construction: every episode starts under the current step).
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        # Inference precision policy (nn/precision.py): the rollout
        # phase reads a cast copy; the learner steps below keep
        # consuming the f32 originals in `state`.
        variables = cast_params_for_inference(
            variables, self.trainer.nn.model_config
        )
        new_carry, outs = self.engine._chunk(
            num_moves, variables, carry, state.step.astype(jnp.int32)
        )
        emit_beacon("rollout_chunk", state.step)
        mat, flush = outs.pop("mat"), outs.pop("flush")
        ds_search = outs.pop("device_stats", None)

        # 2. Scatter the harvest into the device ring (same math as
        # DeviceReplayBuffer._ingest_impl, positions kept for PER).
        new_storage, new_cursor, count, pos, keep = ring_scatter(
            storage, cursor, (mat, flush), self.cap, with_positions=True
        )
        new_size = jnp.minimum(size + count, self.cap)
        emit_beacon("ring_scatter", state.step)

        # 3. Max-priority init for the fresh rows (host-ring parity),
        # trash slot pinned to 0 so sampling can never return it.
        if self.use_per:
            priorities = priorities.at[pos].set(
                jnp.where(keep, max_priority, 0.0)
            )
            priorities = priorities.at[self.cap].set(0.0)

        # 4. Sample K batches on device (post-ingest: fresh rows are
        # immediately eligible, as in the sync loop's fold-then-sample).
        state, idx, weights = self._sample_indices(
            priorities, new_size, state, k
        )
        ds_per = (
            self._per_stat_pack(priorities, weights)
            if self.device_stats
            else None
        )

        # 5. K fused learner steps gathered from the ring (the exact
        # program body Trainer.train_steps_from dispatches).
        new_state, metrics_k, td_k = self.trainer._train_steps_from_impl(
            state, new_storage, idx, weights
        )

        # 6. Priority updates from the group's TD errors, in step order
        # (deterministic last-write-wins for rows sampled by several
        # steps — the same net effect as the host path's sequential
        # per-step update_batch calls).
        if self.use_per:
            for j in range(k):
                prio_j = (
                    jnp.abs(td_k[j]) + self.per_epsilon
                ) ** self.per_alpha
                priorities = priorities.at[idx[j]].set(
                    prio_j.astype(jnp.float32)
                )

        out = {
            "rows_added": count,
            "episode": outs["episode"],
            "trace": outs["trace"],
            "sentinel_live": outs["sentinel_live"],
            "metrics": metrics_k,
            "td": td_k,
            "idx": idx,
            # Stat-pack legs (None = empty pytree nodes when off):
            # search leg from the chunk's scanned waves, PER leg from
            # the sampling phase. They ride this one fetch.
            "device_stats": {"search": ds_search, "per": ds_per},
        }
        return new_state, new_carry, new_storage, priorities, out

    def _sharded_impl(
        self,
        num_moves: int,
        k: int,
        state,
        carry,
        storage,
        priorities,
        cursors,
        sizes,
        max_priority,
    ):
        """The dp-sharded fused megastep (pure; donated: state, carry,
        storage, priorities). Same five phases as `_impl`, with the
        replay phases per shard under ONE shard_map region and the
        rollout/learner phases under GSPMD — the learner's gradient
        psum over dp comes from the shardings (replicated params,
        dp-sharded batch), not from hand-written collectives, so params
        stay bit-identical on every shard.

        `cursors`/`sizes` are (dp,) int32 per-shard ring state (from
        the host mirror, like `_impl`'s scalar cursor/size); indices
        return globally encoded (`shard * stride + slot`)."""
        from jax.sharding import PartitionSpec as P

        buf = self.buffer
        dp_axis = buf.dp_axis
        b_local = self.batch_size // buf.dp

        # 1. Rollout chunk with the learner's live params, lane-sharded
        # over dp under GSPMD (the engine's own mesh-mode program body).
        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        # Inference precision policy (nn/precision.py): the rollout
        # phase reads a cast copy; the learner steps below keep
        # consuming the f32 originals in `state`.
        variables = cast_params_for_inference(
            variables, self.trainer.nn.model_config
        )
        new_carry, outs = self.engine._chunk(
            num_moves, variables, carry, state.step.astype(jnp.int32)
        )
        emit_beacon("rollout_chunk", state.step)
        mat, flush = outs.pop("mat"), outs.pop("flush")
        ds_search = outs.pop("device_stats", None)

        # Per-call scalars for the shard_map region, computed OUTSIDE
        # it: one sampling key split off the train state (each shard
        # folds in its axis index for an independent stratum draw) and
        # beta on the learner-step clock, exactly as `_sample_indices`.
        rng, k_sample = jax.random.split(state.rng)
        state = state.replace(rng=rng)
        if self.use_per:
            frac = jnp.clip(
                state.step.astype(jnp.float32) / self.beta_anneal, 0.0, 1.0
            )
            beta = self.beta_initial + frac * (
                self.beta_final - self.beta_initial
            )
        else:
            beta = jnp.float32(0.0)

        def shard_body(
            storage_local,
            priorities_local,
            cursor_local,
            size_local,
            mat_local,
            flush_local,
            max_p,
            key,
            beta_,
        ):
            # 2+3. Scatter this shard's lanes into this shard's ring
            # slice, fresh rows max-priority-inited, trash row pinned.
            new_storage, new_prios, count = buf.scatter_local(
                storage_local,
                priorities_local if self.use_per else None,
                cursor_local[0],
                (mat_local, flush_local),
                max_p,
            )
            if new_prios is None:
                new_prios = priorities_local
            new_size = jnp.minimum(size_local[0] + count, buf.cap_local)
            # 4. Sample this shard's B/dp stratum of each of the K
            # batches from the device-local priority slice.
            shard = jax.lax.axis_index(dp_axis)
            idx_local, w = buf.sample_local(
                new_prios,
                new_size,
                k,
                b_local,
                jax.random.fold_in(key, shard),
                beta_,
            )
            if self.use_per:
                # One max-normalization across the GLOBAL batch per
                # step row (the host path's single batch-wide
                # normalization) — the region's only collective.
                wmax = jax.lax.pmax(
                    jnp.max(w, axis=1, keepdims=True), dp_axis
                )
                w = w / wmax
            w = w.astype(jnp.float32)
            # Local row gather: each device reads only its own shard.
            rows = read_rows(new_storage, idx_local)
            idx_global = (shard * buf.stride + idx_local).astype(jnp.int32)
            return (
                new_storage,
                new_prios,
                count.reshape(1),
                idx_global,
                w,
                rows,
            )

        shd, stk, rep = P(dp_axis), P(None, dp_axis), P()
        (
            new_storage,
            priorities,
            counts,
            idx,
            weights,
            rows,
        ) = jax.shard_map(
            shard_body,
            mesh=buf.mesh,
            in_specs=(shd, shd, shd, shd, stk, stk, rep, rep, rep),
            out_specs=(shd, shd, shd, stk, stk, stk),
            check_vma=False,
        )(storage, priorities, cursors, sizes, mat, flush,
          max_priority, k_sample, beta)
        emit_beacon("ring_scatter", state.step)
        # PER stat leg over the dp-sharded priority array + stacked
        # weights: plain jnp reductions — GSPMD inserts the cross-shard
        # collectives from the shardings, same idiom as the learner's
        # gradient psum below.
        ds_per = (
            self._per_stat_pack(priorities, weights)
            if self.device_stats
            else None
        )

        # 5. K fused learner steps on the (K, B) stacked batch, dp-
        # sharded on axis 1 (the shard_map's out_specs): GSPMD inserts
        # the gradient all-reduce over dp, params remain replicated.
        stacked = {
            "grid": rows["grid"].astype(jnp.float32),
            "other_features": rows["other_features"],
            "policy_target": rows["policy_target"],
            "value_target": rows["value_target"],
            "policy_weight": rows["policy_weight"],
            "weights": weights,
        }
        new_state, metrics_k, td_k = self.trainer._train_steps_impl(
            state, stacked
        )

        # 6. TD-error priority write-back, per shard in step order
        # (each shard owns exactly the indices it sampled — the global
        # encoding routes by arithmetic, no cross-shard traffic).
        if self.use_per:
            stride = buf.stride

            def write_prios(priorities_local, idx_local, td_local):
                base = jax.lax.axis_index(dp_axis) * stride
                p = priorities_local
                for j in range(k):
                    prio_j = (
                        jnp.abs(td_local[j]) + self.per_epsilon
                    ) ** self.per_alpha
                    p = p.at[idx_local[j] - base].set(
                        prio_j.astype(jnp.float32)
                    )
                return p

            priorities = jax.shard_map(
                write_prios,
                mesh=buf.mesh,
                in_specs=(shd, stk, stk),
                out_specs=shd,
                check_vma=False,
            )(priorities, idx, td_k)

        out = {
            "counts": counts,  # (dp,) per-shard rows written
            "episode": outs["episode"],
            "trace": outs["trace"],
            "sentinel_live": outs["sentinel_live"],
            "metrics": metrics_k,
            "td": td_k,
            "idx": idx,
            "device_stats": {"search": ds_search, "per": ds_per},
        }
        return new_state, new_carry, new_storage, priorities, out

    # --- host API ---------------------------------------------------------

    def _max_priority_watermark(self) -> float:
        """The pre-dispatch max-priority watermark fresh rows enter at
        — the host mirror reconciliation reuses the SAME value."""
        if self.sharded:
            return self.buffer.max_priority
        tree = self.buffer.tree
        return float(tree.max_priority) if tree is not None else 1.0

    def sync_priorities_from_host(self) -> None:
        """(Re)seed the device priority array from the host SumTree
        mirror(s) — after warmup ingests, a checkpoint restore, or any
        other host-side write. Device becomes the sampling truth from
        the next megastep on."""
        buf = self.buffer
        if self.sharded:
            # (dp * stride,) laid out shard-major, matching the global
            # encoding; per-shard trash rows stay 0.
            p = np.zeros(buf.dp * buf.stride, np.float32)
            if buf.trees is not None:
                for s, tree in enumerate(buf.trees):
                    leaves = np.arange(buf.cap_local) + tree._cap2
                    lo = s * buf.stride
                    p[lo : lo + buf.cap_local] = tree.tree[leaves]
            self._priorities = jnp.asarray(p)
            return
        p = np.zeros(self.cap + 1, np.float32)
        tree = buf.tree
        if tree is not None:
            leaves = np.arange(self.cap) + tree._cap2
            p[: self.cap] = tree.tree[leaves]
        self._priorities = jnp.asarray(p)

    def _dispatch_args(self, t: int, k: int) -> tuple:
        if self._priorities is None:
            self.sync_priorities_from_host()
        buf = self.buffer
        max_p = self._max_priority_watermark()
        if self.sharded:
            from jax.sharding import NamedSharding, PartitionSpec as P

            args = (
                self.trainer.state,
                self.engine._carry,
                buf.storage,
                self._priorities,
                jnp.asarray(buf._cursors, jnp.int32),
                jnp.asarray(buf._sizes, jnp.int32),
                jnp.float32(max_p),
            )
            shard = NamedSharding(buf.mesh, P(buf.dp_axis))
            rep = NamedSharding(buf.mesh, P())
            # Commit every argument AT ITS PROGRAM SHARDING before
            # dispatch — the same recompile trap as the single-device
            # path below, with shardings instead of a device: the first
            # call's host-built arrays (seeded priorities, cursors, the
            # scalars) would otherwise key a second compile once the
            # previous megastep's committed outputs flow back in.
            return jax.device_put(
                args,
                (
                    self.trainer._state_shard,
                    self.engine._carry_shardings(),
                    shard,
                    shard,
                    shard,
                    shard,
                    rep,
                ),
            )
        args = (
            self.trainer.state,
            self.engine._carry,
            buf.storage,
            self._priorities,
            jnp.int32(buf._pos),
            jnp.int32(buf._size),
            jnp.float32(max_p),
        )
        # Commit EVERY argument to the device before dispatch. The first
        # call's arguments are a mix of uncommitted host-built arrays
        # (initial carry window zeros, the seeded priority array, the
        # per-call scalars) and committed jit outputs, while every later
        # call sees all-committed outputs of the previous megastep — and
        # jit keys compiled executables on that placement mapping, so
        # without this the SECOND megastep silently recompiles the whole
        # program (measured: a 48s duplicate compile at a tiny CPU
        # scale). device_put is a no-op for anything already resident.
        return jax.device_put(args, jax.devices()[0])

    def run_megastep(
        self, num_moves: int | None = None, k: int | None = None
    ) -> tuple[list, int]:
        """One fused megastep: ONE device dispatch, ONE blocking fetch.

        Returns (per-step (metrics, TD errors) list — the
        `train_steps_finish` contract — and the number of experience
        rows ingested). Side effects: engine carry + episode stats,
        buffer storage/counters + reconciled host PER mirror, trainer
        state + host step mirror all advance.
        """
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        k = int(k or max(1, self.config.FUSED_LEARNER_STEPS))
        buf, engine, trainer = self.buffer, self.engine, self.trainer
        max_p = self._max_priority_watermark()
        args = self._dispatch_args(t, k)
        start_step = trainer._host_step
        with flight_span(
            self.flight,
            "megastep",
            self._name_fn(t, k),
            avals=f"B{self.batch_size}xT{t}xK{k}",
        ):
            note_dispatch(self._name_fn(t, k))
            with default_tracer().span(
                "megastep.dispatch",
                t=t,
                k=k,
                ring_read=ring_read(buf.storage),
            ):
                (
                    trainer.state,
                    engine._carry,
                    buf.storage,
                    self._priorities,
                    out,
                ) = self._megastep_fn(t, k)(*args)
            self.dispatch_count += 1
            t0 = time.perf_counter()
            host = jax.device_get(out)  # graftlint: allow(host-sync-in-hot-path) the one transfer per megastep
            self.transfer_d2h_seconds += time.perf_counter() - t0

        # --- host mirror reconciliation (megastep boundary) ----------
        if self.sharded:
            counts = np.asarray(host["counts"]).reshape(-1)
            count = int(counts.sum())
            # Per-shard SumTree mirrors, cursors and sizes replay the
            # device's scatter at the SAME pre-dispatch watermark it
            # sampled against; then the TD updates route by the global
            # index encoding, in the device's step order.
            buf.reconcile_ingest(
                counts,
                max_priority=max_p if buf.trees is not None else None,
            )
            if buf.trees is not None:
                for j in range(k):
                    buf.update_priorities(host["idx"][j], host["td"][j])
        else:
            tree = buf.tree
            count = int(host["rows_added"])
            # One chunk's rows (B * (T + n) worst case) must fit the
            # ring for the mirror's slot arithmetic to stay 1:1 with
            # surviving rows — same assumption as the sharded ring's
            # ingest assert.
            assert count <= self.cap, (
                f"megastep ingested {count} rows into a {self.cap}-slot "
                "ring in one scatter (shrink ROLLOUT_CHUNK_MOVES or grow "
                "BUFFER_CAPACITY)"
            )
            slots = (buf._pos + np.arange(count)) % self.cap
            if tree is not None and count:
                # Fresh rows at the same pre-group watermark the device
                # used.
                tree.update_batch(slots, np.full(count, max_p))
                tree.data_pointer = int((buf._pos + count) % self.cap)
                tree.n_entries = min(buf._size + count, self.cap)
            buf._pos = int((buf._pos + count) % self.cap)
            buf._size = min(buf._size + count, self.cap)
            # TD-error priority updates, in the same step order the
            # device applied them.
            if tree is not None:
                for j in range(k):
                    buf.update_priorities(host["idx"][j], host["td"][j])

        # --- engine-side stats (play_chunk's host tail) --------------
        engine.last_trace = host["trace"]
        if self.device_stats:
            ds = host.get("device_stats") or {}
            metrics = host["metrics"]
            learner = {}
            for src, dst in (
                ("grad_norm", "grad_norm_max"),
                ("update_norm", "update_norm_max"),
            ):
                if src in metrics:
                    learner[dst] = round(float(np.max(metrics[src])), 6)
            per = {
                key: round(float(val), 6)
                for key, val in (ds.get("per") or {}).items()
            }
            self.last_device_stats = {
                "search": fold_search_stats(ds.get("search")),
                "rollout": rollout_chunk_stats(
                    host["episode"]["ending"], host["trace"]["reward"]
                ),
                "per": per or None,
                "learner": learner or None,
            }
            engine.last_device_stats = self.last_device_stats
        engine._fold_episode_stats(host["episode"])
        engine._total_simulations += (
            int(host["trace"]["sims"].sum()) * engine.batch_size
        )
        engine._total_reused_visits += int(host["trace"]["reused"].sum())
        # The megastep's version clock is the learner step (zero
        # staleness); seed the harvest window tag with the group start.
        engine._min_weights_version = (
            start_step
            if engine._min_weights_version is None
            else min(engine._min_weights_version, start_step)
        )
        sentinels = int(host["sentinel_live"].sum())
        if sentinels:
            logger.warning(
                "Megastep: %d zero-visit sentinel actions on LIVE games "
                "(clamped to action 0).",
                sentinels,
            )

        # --- trainer-side results (train_steps_finish contract) ------
        trainer._host_step += k
        results = trainer.group_results(
            start_step, host["metrics"], np.asarray(host["td"])
        )
        return results, count

    # --- AOT warming / memory analysis (cli warm / cli fit) ---------------

    def warm_megastep(
        self, num_moves: int | None = None, k: int | None = None
    ) -> bool:
        """AOT-precompile the megastep program WITHOUT executing it (no
        donation happens at lowering). True when an AOT executable is
        ready; always False on CPU (cpu_aot bypass, reported as
        skipped-cpu by `cli warm`)."""
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        k = int(k or max(1, self.config.FUSED_LEARNER_STEPS))
        return self._megastep_fn(t, k).warm(*self._dispatch_args(t, k))

    def analyze_megastep(
        self, num_moves: int | None = None, k: int | None = None
    ) -> "dict | None":
        """Memory record of the megastep program at real dispatch avals
        (telemetry/memory.py; `cli fit`) — AOT analysis only, nothing
        executes. The record persists as a `.mem.json` sidecar in the
        compile cache even on CPU, where the executable itself is
        never serialized (cpu_aot bypass); the megastep family's
        `cost_analysis()` record + `.cost.json` sidecar ride the same
        compile (telemetry/roofline.py)."""
        t = int(num_moves or self.config.ROLLOUT_CHUNK_MOVES)
        k = int(k or max(1, self.config.FUSED_LEARNER_STEPS))
        return self._megastep_fn(t, k).analyze(
            *self._dispatch_args(t, k), persist=True
        )
