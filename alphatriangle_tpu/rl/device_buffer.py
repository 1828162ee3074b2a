"""Device-resident experience replay: the zero-copy training data path.

The host replay buffer (`rl/buffer.py`) mirrors the reference's
topology (`alphatriangle/rl/core/buffer.py:25-195`): experiences are
fetched from the rollout device program to host memory, stored in a
NumPy ring, and every sampled batch is re-uploaded for training. On a
chip whose host link is slow relative to compute that round trip IS
the learner bottleneck: at flagship scale one fused 16-step group
stages ~8.5 MB of batches.

`DeviceReplayBuffer` keeps the ring in device HBM instead:

- **Ingest** is one jitted program: the rollout chunk's dense masked
  experience outputs (still device arrays — `SelfPlayEngine.
  play_moves_device` never fetches them) are flattened and validated
  (finiteness + policy-distribution checks, absorbing the role of
  `SelfPlayResult`'s validator); the rows that pass are brought
  together, in candidate order, by a gather whose source indices come
  from a prefix sum over the validity mask, and written over the
  consecutive slots from the running cursor on as windows of the ring:
  read `W` rows, lay the new rows over them, write them back in place
  (`write_windows`; `W` is the first block's rows, at least a tile of
  the chip's lanes: `ingest_window_rows`). The device's work
  follows the rows that exist, not the candidates, and no whole-ring
  array is re-laid-out for it: the chip keeps the ring's rows along
  its lanes, where a window is contiguous and a row scatter is not.
  Invalid rows go nowhere (the row at index `capacity` is kept for the
  priority arrays that pin it to 0, and nothing writes it). Only the
  *count* of rows written returns to the host (one scalar), which is
  all the host-side PER SumTree needs: rows occupy slots
  `[cursor, cursor+count) % capacity` in order, and new rows get
  max-priority init exactly like the host buffer.
- **Sampling** stays host-side (the SumTree is cheap and sequential —
  SURVEY.md §7 "PER on host vs device") but returns only slot
  *indices* and IS weights; the trainer gathers the actual rows on
  device (`Trainer.train_steps_from`), so a fused K-step group uploads
  K*B int32 indices (~16 KB) instead of K batches (~8.5 MB). Every
  program that trains from a device ring gathers through `read_rows`
  (the fused group, the dp-sharded ring's local gather, the megastep):
  `ring[idx]` bit for bit, read from the ring as it lies. A 2-D array a
  whole number of the chip's 8 sublanes wide (`policy_target` at 360
  actions) is gathered through its view `(rows, width // 8, 8)`, the
  same bytes in the chip's tiles of 8 features x 128 rows, because a
  gather of whole rows that wide makes XLA copy the whole array to
  row-major first (4.3 GB and 13.6 ms a group at 3,000,000 rows); every
  other array is read by `ring[idx]`, which lowers in place for the
  narrow ones (`grid`, `other_features`, the 1-D arrays) and still
  copies a wide one of another width (756). `ring_read` says which
  arrays went which way, on the dispatch spans.
- **Priorities** update from the TD errors the trainer already fetches
  (K*B float32 — small), identical to the host path.
- **Persistence** round-trips through the same snapshot dict as the
  host buffer (one bulk fetch per buffer spill — checkpoints are rare)
  so `.npz` spills are interchangeable between the two buffer kinds
  and a run can resume from either.

Storage dtypes match the host ring: grid int8 (cells are exactly
{-1,0,1}), everything else float32. The gather casts grid back to
float32, so a batch sampled from the device ring is bit-identical to
the same rows sampled from the host ring.

Single-device, single-process only (gated in `training/setup.py`):
this ring lives on one chip. The multi-chip variant — ring sharded
over the dp axis, each device ingesting its own lanes' rollouts via
`shard_map` and gathering its own batch rows — is
`rl/sharded_device_buffer.py`.

CPU-backend caveat (DEVICE_REPLAY="on" there is a test/dev mode):
XLA:CPU's *async dispatch* deadlocks when one thread blocks on an
in-flight program while another thread enqueues programs sharing its
buffers — reproduced with a producer rollout chunk + consumer ingest
of its payload from two threads, flagship-size programs only (both
fetches hang forever; tiny programs slip through). The fix is
`jax.config.update("jax_cpu_enable_async_dispatch", False)` BEFORE the
CPU client is created (the flag is latched at client construction —
setting it here in the constructor is provably too late). The runner
(`training/runner.py`) applies it at entry when DEVICE_REPLAY="on";
tests apply it in conftest. The TPU backend's device-FIFO dispatch
model is unaffected.
"""

import logging
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..config.train_config import TrainConfig
from ..telemetry.tracer import default_tracer
from .buffer import ExperienceBuffer

logger = logging.getLogger(__name__)

# Each ring array and the field of an experience row block that fills
# it (the key names the rollout program emits for its `mat`/`flush`
# outputs), in canonical order.
_RING_FIELDS = {
    "grid": "grid",
    "other_features": "other",
    "policy_target": "policy",
    "value_target": "ret",
    "policy_weight": "pw",
}


# The chip lays a ring array's rows along its lanes, in tiles of up to
# 1,024 rows. For a window narrower than a lane tile XLA re-lays-out
# the whole ring around the write, as it did for the scatter (8 ms an
# ingest at a 250,000-row ring: PERF.md section 5, PR 35).
_WINDOW_ROWS_AT_LEAST = 1024


def ingest_window_rows(blocks: tuple[dict[str, Any], ...], cap: int) -> int:
    """Rows of one window of the ingest's ring write: the first block's
    candidates (a chunk's `mat` block: the most rows that mature in its
    T moves), at least a tile of lanes and at most the ring. A function
    of shapes alone."""
    return min(max(blocks[0]["mask"].size, _WINDOW_ROWS_AT_LEAST), cap)


def ingest_windows(count: int, cursor: int, cap: int, width: int) -> int:
    """How many windows `write_windows` writes for an ingest of `count`
    valid rows at `cursor`, on the host: its loop on plain integers.
    One where the rows fit a window; more where they outnumber it or
    wrap the ring's end."""
    kept = min(count, cap)
    first = (cursor + count - kept) % cap
    done = windows = 0
    while done < kept:
        done += min(width, cap - (first + done) % cap, kept - done)
        windows += 1
    return windows


def write_windows(
    storage: dict[str, jax.Array],
    rows: dict[str, jax.Array],
    keep: jax.Array,
    first: jax.Array,
    cap: int,
    width: int,
):
    """Write the kept candidate rows, in candidate order, over the
    ring's consecutive slots from `first` on (mod `cap`), a window of
    `width` rows at a time. Returns (new_storage, windows written).

    A window holds rows `[done, done + m)` of the kept ones at slots
    `[s, s + m)`: it never wraps (`m <= cap - s`) and starts at `s`, or
    earlier where `s` lies within `width` of the ring's end, so that it
    lies inside `[0, cap)`. The kept row for each slot of the window
    comes by a gather (its candidate index from the prefix sum of
    `keep`); slots outside `[s, s + m)` keep what they held."""
    n = keep.shape[0]
    rank = jnp.cumsum(keep.astype(jnp.int32))  # kept rows up to and with i
    kept = rank[-1]
    slot = jnp.arange(width, dtype=jnp.int32)

    def write(carry):
        storage, done, windows = carry
        s = (first + done) % cap
        m = jnp.minimum(jnp.minimum(width, cap - s), kept - done)
        start = jnp.minimum(s, cap - width)
        j = slot - (s - start)  # the window's j-th new row lands here
        fresh = (j >= 0) & (j < m)
        source = jnp.minimum(
            jnp.searchsorted(rank, done + j + 1, side="left"), n - 1
        )
        new_storage = {}
        for name, ring in storage.items():
            held = jax.lax.dynamic_slice_in_dim(ring, start, width)
            new = rows[_RING_FIELDS[name]][source].astype(ring.dtype)
            window = jnp.where(
                fresh.reshape(-1, *[1] * (ring.ndim - 1)), new, held
            )
            new_storage[name] = jax.lax.dynamic_update_slice_in_dim(
                ring, window, start, 0
            )
        return new_storage, done + m, windows + 1

    storage, _, windows = jax.lax.while_loop(
        lambda carry: carry[1] < kept,
        write,
        (storage, jnp.int32(0), jnp.int32(0)),
    )
    return storage, windows


# The chip keeps a 2-D ring array as tiles of 8 features (the sublanes)
# x 128 rows (the lanes): one a whole number of these wide is read
# through the view `(rows, width // 8, 8)`, the same bytes (the module's
# docstring, "Sampling"; PERF.md section 6, PR 37).
_SUBLANES = 8


def _viewed(ring) -> bool:
    """Whether `read_rows` reads this ring array through its view of
    sublane tiles. A function of its shape alone."""
    return ring.ndim == 2 and ring.shape[1] % _SUBLANES == 0


def ring_read(storage: dict[str, Any]) -> dict[str, list[str]]:
    """How `read_rows` reads each ring array: `in_place` through the
    view of sublane tiles, `as_is` by `ring[idx]`. What the dispatch
    spans report (`learner.dispatch`, `megastep.dispatch`), by the rule
    the program is built on."""
    how: dict[str, list[str]] = {"in_place": [], "as_is": []}
    for name, ring in storage.items():
        how["in_place" if _viewed(ring) else "as_is"].append(name)
    return how


def read_rows(
    storage: dict[str, jax.Array], idx: jax.Array
) -> dict[str, jax.Array]:
    """The rows `idx` (any shape) of every ring array, `ring[idx]` bit
    for bit, read from the ring as the chip keeps it: the write's twin
    (`write_windows`). The one gather of batch rows for every program
    that trains from a device ring."""
    rows = {}
    for name, ring in storage.items():
        if _viewed(ring):
            tiles = ring.reshape(ring.shape[0], -1, _SUBLANES)[idx]
            rows[name] = tiles.reshape(*idx.shape, ring.shape[1])
        else:
            rows[name] = ring[idx]
    return rows


@jax.named_scope("replay/ingest_scatter")
def ring_scatter(
    storage: dict[str, jax.Array],
    cursor: jax.Array,
    blocks: tuple[dict[str, jax.Array], ...],
    cap: int,
    with_positions: bool = False,
):
    """Flatten + validate + ring-write experience blocks (pure).

    The single source of the ingest math for BOTH device rings AND the
    fused megastep program (rl/megastep.py): the single-device buffer
    calls it whole-ring, the dp-sharded buffer calls it per shard
    inside `shard_map` — the validation predicate and the keep and
    slot rules must never diverge between them.

    Each block holds arrays with arbitrary leading dims (the chunk
    program's (T,B) matured and (T,B,n) flushed outputs) plus a boolean
    `mask` over those leading dims. Rows are written in block order,
    leading-dims-major — the same order the host path produces via
    boolean indexing, so the paths fill identical slots with identical
    rows. Returns (new_storage, new_cursor, rows_written); with
    `with_positions` it additionally returns each candidate row's slot
    (`cap` for a row that is not written) and keep mask, which the
    megastep needs to max-priority-init the fresh rows in its
    device-resident PER array."""

    def flat(block: dict[str, jax.Array], f: str) -> jax.Array:
        lead = block["mask"].shape
        v = block[f]
        return v.reshape(-1, *v.shape[len(lead):])

    rows = {
        f: jnp.concatenate([flat(b, f) for b in blocks])
        for f in _RING_FIELDS.values()
    }
    mask = jnp.concatenate([b["mask"].reshape(-1) for b in blocks])
    # Validation absorbed from SelfPlayResult's validator + the host
    # buffer's finite filter (rl/types.py:78-85, buffer.py:120-128).
    valid = (
        mask
        & jnp.isfinite(rows["grid"]).all(axis=(1, 2, 3))
        & jnp.isfinite(rows["other"]).all(axis=1)
        & jnp.isfinite(rows["policy"]).all(axis=1)
        & jnp.isfinite(rows["ret"])
        & (jnp.abs(rows["policy"].sum(axis=1) - 1.0) < 1e-3)
    )
    offsets = jnp.cumsum(valid.astype(jnp.int32)) - 1
    count = valid.sum(dtype=jnp.int32)
    # A single ingest larger than the ring keeps only the newest `cap`
    # rows — the older ones would be overwritten by the wrap anyway,
    # and dropping them keeps the written slots distinct. The cursor
    # still advances by the full count, matching the host ring.
    keep = valid & (offsets >= count - cap)
    new_storage, _ = write_windows(
        storage,
        rows,
        keep,
        (cursor + jnp.maximum(count - cap, 0)) % cap,
        cap,
        ingest_window_rows(blocks, cap),
    )
    new_cursor = (cursor + count) % cap
    if with_positions:
        pos = jnp.where(keep, (cursor + offsets) % cap, cap)
        return new_storage, new_cursor, count, pos, keep
    return new_storage, new_cursor, count


class DeviceReplayBuffer(ExperienceBuffer):
    """Uniform/PER replay whose ring storage lives in device HBM.

    Subclasses the host buffer for everything link-independent
    (readiness, beta annealing, priority updates, SumTree sampling
    math); replaces storage reads/writes with jitted device ops.
    """

    is_device = True

    def __init__(
        self,
        config: TrainConfig,
        grid_shape: tuple[int, int, int],
        other_dim: int,
        action_dim: int,
        seed: int | None = None,
    ):
        super().__init__(config, seed=seed, action_dim=action_dim)
        cap = self.capacity
        # One row past the ring at index `cap`: the slot `ring_scatter`
        # reports for a row it does not write (nothing writes it).
        self.storage: dict[str, jax.Array] = {
            "grid": jnp.zeros((cap + 1, *grid_shape), jnp.int8),
            "other_features": jnp.zeros((cap + 1, other_dim), jnp.float32),
            "policy_target": jnp.zeros((cap + 1, action_dim), jnp.float32),
            "value_target": jnp.zeros(cap + 1, jnp.float32),
            "policy_weight": jnp.ones(cap + 1, jnp.float32),
        }
        self._grid_shape = grid_shape
        self._other_dim = other_dim
        self._ingest_jit = jax.jit(self._ingest_impl, donate_argnums=(0,))
        # Device program dispatches this ring made (telemetry: the
        # loop's dispatches-per-iteration gauge sums these counters).
        self.dispatch_count = 0

    # --- device ingest ----------------------------------------------------

    def _ingest_impl(
        self,
        storage: dict[str, jax.Array],
        cursor: jax.Array,
        blocks: tuple[dict[str, jax.Array], ...],
    ):
        """Flatten + validate + ring-write experience blocks.

        The math lives in the module-level `ring_scatter` (shared with
        the dp-sharded ring's per-shard ingest).
        """
        return ring_scatter(storage, cursor, blocks, self.capacity)

    def _ingest_blocks(
        self, blocks: "tuple[dict[str, Any], ...]"
    ) -> tuple[int, np.ndarray]:
        """Run the jitted ingest; returns (rows written, their slots)."""
        tracer = default_tracer()
        with tracer.span("replay.ingest_dispatch"):
            self.storage, _, count_dev = self._ingest_jit(
                self.storage, jnp.int32(self._pos), blocks
            )
            self.dispatch_count += 1
        with tracer.span("replay.ingest_wait") as args:
            count = int(count_dev)  # the one blocking scalar fetch
            args["rows"] = count
            args["windows"] = ingest_windows(
                count,
                self._pos,
                self.capacity,
                ingest_window_rows(blocks, self.capacity),
            )
        with tracer.span("replay.tree_update", rows=count):
            slots = (self._pos + np.arange(count)) % self.capacity
            if self.tree is not None and count:
                self.tree.update_batch(
                    slots,
                    np.full(count, self.tree.max_priority, dtype=np.float64),
                )
                self.tree.data_pointer = int(
                    (self._pos + count) % self.capacity
                )
                self.tree.n_entries = min(self._size + count, self.capacity)
            self._pos = int((self._pos + count) % self.capacity)
            self._size = min(self._size + count, self.capacity)
        return count, slots

    def ingest_payload(self, payload: dict[str, Any]) -> int:
        """Fold one rollout chunk's device-resident experience outputs
        (`SelfPlayEngine.play_moves_device`) into the ring. Returns the
        number of rows written — the only thing fetched."""
        return self._ingest_blocks((payload["mat"], payload["flush"]))[0]

    def add_dense(
        self,
        grid: np.ndarray,
        other_features: np.ndarray,
        policy_target: np.ndarray,
        value_target: np.ndarray,
        policy_weight: np.ndarray | None = None,
    ) -> np.ndarray:
        """Host-array insert (restore path, tests, host-side generators).

        Same contract as the host buffer's `add_dense`, via one upload
        + the shared ingest program. Note the device path additionally
        enforces the policy-distribution check (the validator layer the
        device path absorbs), which the host buffer leaves to
        `SelfPlayResult`.
        """
        grid = np.asarray(grid, dtype=np.float32)
        k = grid.shape[0]
        if k == 0:
            return np.zeros(0, dtype=np.int64)
        block = {
            "grid": jnp.asarray(grid),
            "other": jnp.asarray(other_features, dtype=jnp.float32),
            "policy": jnp.asarray(policy_target, dtype=jnp.float32),
            "ret": jnp.asarray(
                np.asarray(value_target, dtype=np.float32).reshape(-1)
            ),
            "pw": jnp.asarray(
                np.ones(k, np.float32)
                if policy_weight is None
                else np.asarray(policy_weight, dtype=np.float32).reshape(-1)
            ),
            "mask": jnp.ones(k, bool),
        }
        count, slots = self._ingest_blocks((block,))
        if count < k:
            logger.warning(
                "DeviceReplayBuffer: dropped %d invalid rows of %d on add.",
                k - count,
                k,
            )
        return slots.astype(np.int64)

    # --- memory attribution (telemetry/memory.py) -------------------------

    def storage_nbytes(self) -> int:
        """Exact bytes of the device-resident ring storage (dtype/shape
        math over the allocated arrays; equals
        `telemetry.memory.replay_ring_bytes` for this geometry)."""
        from ..telemetry.memory import tree_bytes

        return tree_bytes(self.storage)

    def memory_record(self) -> dict:
        """This ring's `kind: "memory"` ledger record (HBM-resident)."""
        from ..telemetry.memory import replay_ring_record

        return replay_ring_record(
            self.storage_nbytes(), self.capacity, shards=1, location="device"
        )

    # --- sampling ---------------------------------------------------------

    def sample(
        self, batch_size: int, current_train_step: int | None = None
    ) -> "dict[str, np.ndarray] | None":
        """Sample slot indices + IS weights (no data movement).

        Returns {"indices", "weights"} — the trainer gathers the rows
        on device (`Trainer.train_steps_from`). The sampling math is
        the parent's `_sample_indices` (shared, not duplicated).
        """
        with default_tracer().span("replay.sample"):
            sampled = self._sample_indices(batch_size, current_train_step)
            if sampled is None:
                return None
            slots, weights = sampled
            return {"indices": slots.astype(np.int64), "weights": weights}

    def update_priorities(
        self, indices: np.ndarray, td_errors: np.ndarray
    ) -> None:
        """The parent's SumTree write-back, under its span."""
        with default_tracer().span("replay.priorities"):
            super().update_priorities(indices, td_errors)

    # --- persistence ------------------------------------------------------

    def get_state(self) -> dict[str, Any]:
        """Same snapshot dict as the host buffer (one bulk fetch)."""
        state: dict[str, Any] = {
            "pos": self._pos,
            "size": self._size,
            "storage": None,
            "priorities": None,
        }
        if self._size > 0:
            host = jax.device_get(self.storage)
            state["storage"] = {
                k: np.asarray(v[: self._size]).copy() for k, v in host.items()
            }
        if self.tree is not None and self._size > 0:
            leaves = np.arange(self._size) + self.tree._cap2
            state["priorities"] = self.tree.tree[leaves].copy()
        return state

    def set_state(self, state: dict[str, Any]) -> None:
        """Restore a snapshot (host- or device-buffer produced): let the
        parent rebuild its host ring + SumTree, then upload the ring."""
        super().set_state(state)
        if self._storage is None:
            return
        host = {
            k: np.zeros(
                (self.capacity + 1, *v.shape[1:]), dtype=self.storage[k].dtype
            )
            for k, v in self._storage.items()
        }
        for k, v in self._storage.items():
            host[k][: self.capacity] = v
        self.storage = jax.device_put(host)
        self._storage = None  # free the host copy; device ring is truth
