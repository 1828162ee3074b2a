"""dp-sharded device-resident replay: the multi-chip zero-copy data path.

`DeviceReplayBuffer` (rl/device_buffer.py) keeps the replay ring in one
chip's HBM so the steady-state learner uploads indices, not batches.
This module extends the idea to a data-parallel mesh: the ring shards
over the dp axis, and the whole experience path becomes device-local —

- **rollouts** shard their lockstep lanes over dp (rl/self_play.py), so
  each device produces experience rows for exactly the games it played;
- **ingest** is a `shard_map` scatter: every device ring-writes ITS OWN
  lanes' rows into ITS OWN ring shard (per-shard cursors), so no
  experience bytes cross devices or the host link — the counts (dp
  int32s) are the only fetch;
- **sampling** stays host-side but stratifies per shard: B/dp rows from
  each shard's own SumTree, because the learner batch is dp-sharded and
  each device can only gather its local rows without collectives. (The
  reference's PER is a single global tree; equal-rows-per-shard
  proportional sampling is the standard distributed-PER relaxation —
  shard contents are i.i.d. games, so per-shard totals concentrate.)
- **gather** is a `shard_map` on the learner side: each device gathers
  its B/dp batch rows from its local shard (`Trainer`'s sharded `from`
  path), feeding the dp-sharded fused train step directly.

Indices are globally encoded as `shard * (cap_local + 1) + slot` — the
actual row index in the sharded storage array — so priority updates
route by arithmetic; the row past each shard's ring (local index
`cap_local`) is the slot `ring_scatter` reports for a row it does not
write, exactly like the single-device ring (priority arrays pin it to
0; the rings' own arrays are not written there).

Scope (gated in training/setup.py): single-process, dp-only meshes
(mdl == sp == 1) — with a wider sp the sp-replicas of the learner batch
would need identical rows, which per-device ingest cannot provide
without the collectives this design exists to avoid. The reference has
no counterpart: its buffer is one host object fed by actor RPC
(`alphatriangle/rl/core/buffer.py:25-195`).
"""

import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config.train_config import TrainConfig
from ..ops import per_sample
from ..utils.sumtree import SumTree
from .buffer import ExperienceBuffer
from .device_buffer import ring_scatter

logger = logging.getLogger(__name__)


class ShardedDeviceReplayBuffer(ExperienceBuffer):
    """PER/uniform replay whose ring shards over the mesh's dp axis."""

    is_device = True
    is_sharded = True

    def __init__(
        self,
        config: TrainConfig,
        grid_shape: tuple[int, int, int],
        other_dim: int,
        action_dim: int,
        mesh: Mesh,
        dp_axis: str = "dp",
        seed: int | None = None,
    ):
        super().__init__(config, seed=seed, action_dim=action_dim)
        dp = int(mesh.shape.get(dp_axis, 1))
        if mesh.devices.size != dp:
            raise ValueError(
                "ShardedDeviceReplayBuffer needs a dp-only mesh "
                f"(got {dict(mesh.shape)}): wider mdl/sp axes would "
                "need cross-device row movement at ingest or gather."
            )
        if self.capacity % dp != 0:
            raise ValueError(
                f"BUFFER_CAPACITY={self.capacity} must divide over "
                f"dp={dp} ring shards."
            )
        self.mesh = mesh
        self.dp_axis = dp_axis
        self.dp = dp
        self.cap_local = self.capacity // dp
        self.stride = self.cap_local + 1  # + per-shard trash row
        self.per_sample_backend = config.PER_SAMPLE_BACKEND
        self._grid_shape = grid_shape
        self._other_dim = other_dim

        shard = NamedSharding(mesh, P(dp_axis))
        n = dp * self.stride
        self.storage: dict[str, jax.Array] = {
            "grid": jnp.zeros((n, *grid_shape), jnp.int8),
            "other_features": jnp.zeros((n, other_dim), jnp.float32),
            "policy_target": jnp.zeros((n, action_dim), jnp.float32),
            "value_target": jnp.zeros(n, jnp.float32),
            "policy_weight": jnp.ones(n, jnp.float32),
        }
        self.storage = jax.device_put(self.storage, shard)

        # Per-shard host bookkeeping. The parent's single global tree
        # is unused — sampling is stratified per shard.
        self.tree = None
        self.trees: "list[SumTree] | None" = (
            [SumTree(self.cap_local) for _ in range(dp)]
            if self.use_per
            else None
        )
        self._cursors = np.zeros(dp, dtype=np.int64)
        self._sizes = np.zeros(dp, dtype=np.int64)
        # Device program dispatches this ring made (telemetry gauge).
        self.dispatch_count = 0

        self._ingest_jit = jax.jit(
            jax.shard_map(
                self._ingest_local,
                mesh=mesh,
                in_specs=(P(dp_axis), P(dp_axis), P(None, dp_axis)),
                out_specs=(P(dp_axis), P(dp_axis)),
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

    # --- device ingest ----------------------------------------------------

    def _ingest_local(
        self,
        storage_local: dict[str, jax.Array],
        cursor_local: jax.Array,
        blocks_local: tuple[dict[str, jax.Array], ...],
    ):
        """One shard's ring write: the SAME `ring_scatter` math as the
        single-device ring (validation, the gather of the rows that
        pass, the window write), over the LOCAL lanes and the LOCAL
        ring shard (cap = cap_local). Runs under shard_map with no
        collectives — the partitioning IS the distribution."""
        new_storage, _, count = ring_scatter(
            storage_local, cursor_local[0], blocks_local, self.cap_local
        )
        return new_storage, count.reshape(1)

    def _ingest_blocks(
        self, blocks: "tuple[dict[str, Any], ...]"
    ) -> tuple[int, np.ndarray]:
        """Run the sharded ingest. Returns (total rows written, their
        globally-encoded slots in per-shard write order)."""
        self.storage, counts_dev = self._ingest_jit(
            self.storage, jnp.asarray(self._cursors, jnp.int32), blocks
        )
        self.dispatch_count += 1
        counts = np.asarray(counts_dev)  # (dp,) — the one fetch
        return self.reconcile_ingest(counts)

    def reconcile_ingest(
        self,
        counts: np.ndarray,
        max_priority: "float | None" = None,
    ) -> tuple[int, np.ndarray]:
        """Host bookkeeping for rows a device program ALREADY scattered
        into the shards (per-shard write order: cursor, cursor+1, ...):
        SumTree max-priority init, per-shard cursors/sizes, the global
        size. Callers are the dispatching ingest above and the sharded
        megastep (rl/megastep.py), which scatters INSIDE its fused
        program and reconciles from the returned per-shard counts.

        `max_priority` pins the watermark fresh rows enter at — the
        sharded megastep passes the single pre-dispatch watermark its
        device program sampled against, so mirror and device priorities
        stay row-for-row equal; None uses each tree's own current
        watermark (the plain ingest path's per-shard semantics).

        Returns (total rows written, their globally-encoded slots in
        per-shard write order)."""
        counts = np.asarray(counts).reshape(-1)
        # Host-side slot reconstruction below assumes each shard wrote
        # at most cap_local rows this ingest (slot uniqueness): a count
        # above cap_local would mean the ring lapped itself WITHIN one
        # scatter, making `cursor + arange(c) % cap_local` repeat slots
        # — later writes would silently win and the SumTree priorities
        # would attach to overwritten rows. The engine cannot produce
        # it (a chunk's lanes-per-shard x (T + n) rows are sized well
        # under capacity), so a trip here means a config/payload bug.
        assert int(counts.max(initial=0)) <= self.cap_local, (
            f"sharded ingest wrote {counts.max()} rows into a "
            f"{self.cap_local}-slot shard in one scatter; per-shard "
            "slot uniqueness is violated (shrink the chunk or grow "
            "BUFFER_CAPACITY)"
        )
        all_slots = []
        for k in range(self.dp):
            c = int(counts[k])
            if c == 0:
                continue
            local = (self._cursors[k] + np.arange(c)) % self.cap_local
            all_slots.append(k * self.stride + local)
            if self.trees is not None:
                tree = self.trees[k]
                watermark = (
                    tree.max_priority
                    if max_priority is None
                    else max_priority
                )
                tree.update_batch(
                    local,
                    np.full(c, watermark, dtype=np.float64),
                )
                tree.data_pointer = int(
                    (self._cursors[k] + c) % self.cap_local
                )
                tree.n_entries = int(
                    min(self._sizes[k] + c, self.cap_local)
                )
            self._cursors[k] = (self._cursors[k] + c) % self.cap_local
            self._sizes[k] = min(self._sizes[k] + c, self.cap_local)
        self._size = int(self._sizes.sum())
        slots = (
            np.concatenate(all_slots)
            if all_slots
            else np.zeros(0, dtype=np.int64)
        )
        return int(counts.sum()), slots

    def ingest_payload(self, payload: dict[str, Any]) -> int:
        """Fold one dp-sharded rollout chunk's device-resident outputs
        into the sharded ring. Each device's lanes scatter into its own
        shard; only the per-shard counts come back."""
        return self._ingest_blocks((payload["mat"], payload["flush"]))[0]

    # --- in-program entry points (the sharded megastep's shard_map) -------

    @property
    def max_priority(self) -> float:
        """Global max-priority watermark across the shard trees. The
        sharded megastep passes ONE watermark into its device program
        (fresh rows on every shard enter at it before sampling) and
        `reconcile_ingest` re-applies the same one to the mirror."""
        if self.trees is None:
            return 1.0
        return float(max(t.max_priority for t in self.trees))

    def scatter_local(
        self,
        storage_local: dict[str, jax.Array],
        priorities_local: "jax.Array | None",
        cursor: jax.Array,
        blocks_local: tuple,
        max_priority: jax.Array,
    ):
        """One shard's ring scatter + PER priority init, for use INSIDE
        an enclosing `shard_map` body (the sharded megastep's fused
        program). Same `ring_scatter` math as `_ingest_local`, plus the
        priority bookkeeping the fused program needs before it samples:
        fresh rows enter at the caller's max-priority watermark and the
        trash row (local index cap_local) pins to 0 so sampling can
        never return it. `priorities_local` is the shard's (stride,)
        slice, or None for uniform replay.

        Returns (new_storage, new_priorities, rows written)."""
        new_storage, _, count, pos, keep = ring_scatter(
            storage_local,
            cursor,
            blocks_local,
            self.cap_local,
            with_positions=True,
        )
        if priorities_local is not None:
            priorities_local = priorities_local.at[pos].set(
                jnp.where(keep, max_priority, 0.0)
            )
            priorities_local = priorities_local.at[self.cap_local].set(0.0)
        return new_storage, priorities_local, count

    def sample_local(
        self,
        priorities_local: jax.Array,
        size: jax.Array,
        k: int,
        b_local: int,
        key: jax.Array,
        beta: jax.Array,
    ):
        """One shard's stratified (K, b_local) slot sampling inside an
        enclosing `shard_map` body. PER: the shared stratified draw over
        the shard's own priority slice (ops/per_sample.py;
        `TrainConfig.PER_SAMPLE_BACKEND` picks the searchsorted or
        Pallas compare-count lowering) — the vectorized equivalent of
        this shard's SumTree descent (utils/sumtree.py); zero-priority
        (empty/trash) slots have empty cumsum segments and are never
        selected. IS weights come back UNNORMALIZED — the caller
        max-normalizes across the GLOBAL batch (a pmax over dp),
        matching `sample`'s single batch-wide normalization. Uniform:
        floor(u * size), unit weights.

        Returns (local slot indices (K, b_local) int32, weights)."""
        size_f = size.astype(jnp.float32)
        if self.use_per:
            idx, probs = per_sample(
                priorities_local,
                self.cap_local,
                k,
                b_local,
                key,
                mode=self.per_sample_backend,
            )
            weights = (size_f * probs) ** (-beta)
        else:
            u = jax.random.uniform(key, (k, b_local))
            idx = jnp.clip(
                jnp.floor(u * size_f).astype(jnp.int32),
                0,
                jnp.maximum(size - 1, 0),
            )
            weights = jnp.ones((k, b_local), jnp.float32)
        return idx, weights

    # --- memory attribution (telemetry/memory.py) -------------------------

    def storage_nbytes(self) -> int:
        """Exact bytes of the sharded ring storage across all dp shards
        (dtype/shape math; `storage_nbytes() // dp` is the per-device
        HBM the ring occupies)."""
        from ..telemetry.memory import tree_bytes

        return tree_bytes(self.storage)

    def memory_record(self) -> dict:
        """This ring's `kind: "memory"` ledger record (dp-sharded)."""
        from ..telemetry.memory import replay_ring_record

        return replay_ring_record(
            self.storage_nbytes(),
            self.capacity,
            shards=self.dp,
            location="device",
        )

    def add_dense(
        self,
        grid: np.ndarray,
        other_features: np.ndarray,
        policy_target: np.ndarray,
        value_target: np.ndarray,
        policy_weight: np.ndarray | None = None,
    ) -> np.ndarray:
        """Host-array insert (restore path, tests). Rows stripe across
        the dp shards (contiguous N/dp runs per shard — slot layout
        differs from the host ring, which replay semantics permit);
        ragged counts are padded with masked rows."""
        grid = np.asarray(grid, dtype=np.float32)
        k = grid.shape[0]
        if k == 0:
            return np.zeros(0, dtype=np.int64)
        pad = (-k) % self.dp
        n = k + pad

        def padded(a: np.ndarray, dtype) -> jnp.ndarray:
            a = np.asarray(a, dtype=dtype)
            if pad:
                a = np.concatenate(
                    [a, np.zeros((pad, *a.shape[1:]), dtype=dtype)]
                )
            return jnp.asarray(a[None])  # (1, N, ...) lane dim on axis 1

        mask = np.ones(n, bool)
        mask[k:] = False
        block = {
            "grid": padded(grid, np.float32),
            "other": padded(other_features, np.float32),
            "policy": padded(policy_target, np.float32),
            "ret": padded(
                np.asarray(value_target, np.float32).reshape(-1), np.float32
            ),
            "pw": padded(
                np.ones(k, np.float32)
                if policy_weight is None
                else np.asarray(policy_weight, np.float32).reshape(-1),
                np.float32,
            ),
            "mask": jnp.asarray(mask[None]),
        }
        count, slots = self._ingest_blocks((block,))
        if count < k:
            logger.warning(
                "ShardedDeviceReplayBuffer: dropped %d invalid rows "
                "of %d on add.",
                k - count,
                k,
            )
        return slots.astype(np.int64)

    # --- sampling ---------------------------------------------------------

    def sample(
        self, batch_size: int, current_train_step: int | None = None
    ) -> "dict[str, np.ndarray] | None":
        """Stratified per-shard sampling: B/dp rows from each shard's
        own tree, returned shard-major so the (K, B) index upload's
        axis-1 sharding lands each shard's slice on its device.
        Returns {"indices" (globally encoded), "weights"} or None."""
        if batch_size % self.dp != 0:
            raise ValueError(
                f"BATCH_SIZE={batch_size} must divide over dp={self.dp} "
                "for the sharded ring (each device gathers B/dp rows)."
            )
        b_local = batch_size // self.dp
        if not self.is_ready() or any(
            self._sizes[k] < b_local for k in range(self.dp)
        ):
            return None
        indices = np.empty(batch_size, dtype=np.int64)
        weights = np.empty(batch_size, dtype=np.float32)
        for k in range(self.dp):
            lo, hi = k * b_local, (k + 1) * b_local
            if self.use_per:
                if current_train_step is None:
                    raise ValueError(
                        "current_train_step is required for PER sampling."
                    )
                assert self.trees is not None
                tree = self.trees[k]
                slots, priorities = tree.sample_batch(b_local, self._rng)
                probs = np.maximum(priorities, 1e-12) / max(
                    tree.total_priority, 1e-12
                )
                beta = self.beta(current_train_step)
                weights[lo:hi] = (self._sizes[k] * probs) ** (-beta)
            else:
                slots = self._rng.integers(
                    0, self._sizes[k], size=b_local
                )
                weights[lo:hi] = 1.0
            indices[lo:hi] = k * self.stride + slots
        # Max-normalize across the WHOLE batch (matches the host path's
        # single normalization; per-shard maxima would skew shards).
        weights = (weights / weights.max()).astype(np.float32)
        return {"indices": indices, "weights": weights}

    def update_priorities(
        self, indices: np.ndarray, td_errors: np.ndarray
    ) -> None:
        """Route the parent's `p = (|δ| + ε)^α` update to each shard's
        tree via the global index encoding."""
        if not self.use_per or self.trees is None:
            return
        indices = np.asarray(indices, dtype=np.int64).reshape(-1)
        td = np.asarray(td_errors, dtype=np.float64).reshape(-1)
        if indices.shape != td.shape:
            raise ValueError(
                f"indices {indices.shape} and td_errors {td.shape} "
                "must match."
            )
        if len(indices) == 0:
            return
        td = np.where(np.isfinite(td), td, 0.0)
        priorities = (np.abs(td) + self.per_epsilon) ** self.alpha
        shard = indices // self.stride
        slot = indices % self.stride
        for k in range(self.dp):
            m = shard == k
            if m.any():
                self.trees[k].update_batch(slot[m], priorities[m])

    # --- persistence ------------------------------------------------------

    def get_state(self) -> dict[str, Any]:
        """Snapshot interchangeable with the host/device buffers: valid
        rows concatenated shard by shard (each shard's rows in
        chronological order; cross-shard interleaving is not recorded —
        replay sampling is order-free, so only row+priority content
        matters)."""
        state: dict[str, Any] = {
            "pos": 0,
            "size": self._size,
            "storage": None,
            "priorities": None,
        }
        if self._size == 0:
            return state
        host = jax.device_get(self.storage)
        parts: dict[str, list] = {k: [] for k in host}
        pri_parts: list[np.ndarray] = []
        for k in range(self.dp):
            sz = int(self._sizes[k])
            if sz == 0:
                continue
            # Chronological within the shard: oldest at the cursor when
            # the shard ring has wrapped.
            order = np.arange(sz)
            if sz == self.cap_local:
                order = np.roll(order, -int(self._cursors[k]))
            rows = k * self.stride + order
            for name, arr in host.items():
                parts[name].append(np.asarray(arr[rows]).copy())
            if self.trees is not None:
                leaves = order + self.trees[k]._cap2
                pri_parts.append(self.trees[k].tree[leaves].copy())
        state["storage"] = {
            name: np.concatenate(chunks) for name, chunks in parts.items()
        }
        if pri_parts:
            state["priorities"] = np.concatenate(pri_parts)
        # Rows are already chronological per shard; mark unwrapped so a
        # restorer's slot->chronology roll is a no-op.
        state["pos"] = min(self._size, self.capacity)
        return state

    def set_state(self, state: dict[str, Any]) -> None:
        """Restore a snapshot from ANY buffer kind by re-ingesting its
        rows (striped across shards) and re-attaching priorities."""
        storage = state.get("storage")
        if storage is None:
            return
        old_size = int(state["size"])
        old_pos = int(state["pos"])
        order = np.roll(
            np.arange(old_size), -(old_pos % max(old_size, 1))
        )
        n = min(old_size, self.capacity)
        order = order[-n:]  # keep newest on shrink

        # Reset shards.
        self._cursors[:] = 0
        self._sizes[:] = 0
        self._size = 0
        if self.use_per:
            self.trees = [SumTree(self.cap_local) for _ in range(self.dp)]

        slots = self.add_dense(
            np.asarray(storage["grid"])[order].astype(np.float32),
            np.asarray(storage["other_features"])[order],
            np.asarray(storage["policy_target"])[order],
            np.asarray(storage["value_target"])[order],
            policy_weight=np.asarray(
                storage.get(
                    "policy_weight", np.ones(old_size, np.float32)
                )
            )[order],
        )
        pri = state.get("priorities")
        if pri is not None and self.trees is not None:
            pri = np.asarray(pri, dtype=np.float64)[order]
            if len(pri) == len(slots):
                # update_priorities would re-apply the (|δ|+ε)^α
                # transform; these are already priorities.
                shard = slots // self.stride
                slot = slots % self.stride
                for k in range(self.dp):
                    m = shard == k
                    if m.any():
                        self.trees[k].update_batch(slot[m], pri[m])
            else:
                logger.warning(
                    "Priority snapshot length %d != restored rows %d; "
                    "keeping max-priority init.",
                    len(pri),
                    len(slots),
                )
