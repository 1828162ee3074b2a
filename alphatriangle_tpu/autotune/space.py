"""Search space for the fit-driven autotuner (docs/AUTOTUNE.md).

A candidate is one point in the
`(SELF_PLAY_BATCH_SIZE, BUFFER_CAPACITY, rollout chunk T, fused K, dp,
geometry preset)` space the ROADMAP names. Everything here is pure
config math — no JAX import, so candidate enumeration and gate
pruning run instantly and are unit-testable without a backend.

Two prune families run before any expensive feasibility work:

- **Divisibility gates** mirror `sharded_megastep_dp`
  (telemetry/memory.py) and the training-time buffer gate
  (training/setup.py): a dp-sharded candidate whose capacity / learner
  batch / lane count does not divide dp would silently fall back to
  the single-device program at run time, so the search refuses to
  score it as a dp candidate at all.
- **Monotone-in-B dominance**: with every other axis fixed, both the
  composed memory budget and the predicted throughput are monotone
  non-decreasing in the lane count B (throughput model:
  autotune/model.py; memory: more lanes = strictly more rollout
  residency and transient). So within a group only the LARGEST
  feasible B can win — the search walks B descending and marks the
  rest dominated without ever consulting the feasibility oracle.
"""

from dataclasses import dataclass, field

# Row statuses the search assigns to candidates (stdout table + JSON).
STATUS_FIT = "fit"  # oracle-confirmed feasible
STATUS_OVER = "over"  # oracle says over the byte limit
STATUS_GATE = "gate"  # failed a divisibility/geometry gate
STATUS_DOMINATED = "dominated"  # smaller B than a feasible sibling
STATUS_RING = "ring-over"  # ring math alone exceeds the limit
STATUS_SKIPPED = "skipped"  # search ended before evaluation


@dataclass(frozen=True)
class Candidate:
    """One point in the autotuner's search space.

    Beside the shape axes it carries the wave backup's lowering, the
    rollout inference precision, the serve ladder and subtree reuse
    (`kernels()`, the artifact's provenance block). The backup's two
    lowerings are parity-pinned rewrites of the same math and move the
    same buffers; `inference_precision="bfloat16"` adds a cast
    parameter copy, which is why it appears in `oracle_key()`."""

    geometry: str  # named board geometry (config/presets.py)
    sp_batch: int  # SELF_PLAY_BATCH_SIZE (lockstep lanes)
    capacity: int  # BUFFER_CAPACITY (replay ring rows)
    chunk: int  # ROLLOUT_CHUNK_MOVES (T)
    fused_k: int  # FUSED_LEARNER_STEPS (K)
    dp: int  # data-parallel mesh width tuned for
    backup_update: str = "xla"  # MCTSConfig.backup_update
    inference_precision: str = "float32"  # ModelConfig.INFERENCE_PRECISION
    # Serve-shape ladder spec (serving/buckets.py): CSV rung list, ""
    # meaning a single fixed rung at the lane count. A serve-
    # side axis — it never changes training residency, so it is absent
    # from oracle_key() (free axis: ladders share feasibility answers).
    serve_buckets: str = ""
    # MCTSConfig.tree_reuse: NOT memory-free — reuse widens every tree
    # plane from max_simulations+1 to ~2x that many node slots, so it
    # appears in oracle_key() alongside the other residency-changing
    # axes. It also changes per-move search behavior (carried visits).
    tree_reuse: bool = False

    def group_key(self) -> tuple:
        """Axes held fixed under monotone-in-B dominance."""
        return (
            self.geometry,
            self.capacity,
            self.chunk,
            self.fused_k,
            self.dp,
            self.backup_update,
            self.inference_precision,
            self.serve_buckets,
            self.tree_reuse,
        )

    def oracle_key(self) -> tuple:
        """Axes the feasibility oracle's answer can depend on. The serve
        ladder and the backup's lowering are deliberately absent:
        candidates differing only there share one oracle result (free
        axes for the search)."""
        return (
            self.geometry,
            self.sp_batch,
            self.capacity,
            self.chunk,
            self.fused_k,
            self.dp,
            self.inference_precision,
            self.tree_reuse,
        )

    def kernels(self) -> dict:
        """The kernel-axis block (tuned_preset.json provenance)."""
        return {
            "backup_update": self.backup_update,
            "inference_precision": self.inference_precision,
            "serve_buckets": self.serve_buckets,
            "tree_reuse": self.tree_reuse,
        }

    def label(self) -> str:
        base = (
            f"{self.geometry}/B{self.sp_batch}/cap{self.capacity}"
            f"/t{self.chunk}/k{self.fused_k}/dp{self.dp}"
        )
        tags = [
            tag
            for tag, default in (
                (f"b-{self.backup_update}", "b-xla"),
                (f"p-{self.inference_precision}", "p-float32"),
                (f"sb-{self.serve_buckets}", "sb-"),
                (f"r-{'on' if self.tree_reuse else 'off'}", "r-off"),
            )
            if tag != default
        ]
        return base + (f"/{'+'.join(tags)}" if tags else "")


@dataclass
class SearchSpace:
    """Axis values the tuner enumerates (geometry names must exist in
    `config.presets.GEOMETRY_PRESETS` or equal the sentinel "plan",
    meaning the base configuration's own board)."""

    geometries: list = field(default_factory=lambda: ["plan"])
    batches: list = field(default_factory=lambda: [256, 512, 1024])
    capacities: list = field(default_factory=lambda: [50_000, 100_000])
    chunks: list = field(default_factory=lambda: [8, 16])
    fused_ks: list = field(default_factory=lambda: [8, 16])
    dps: list = field(default_factory=lambda: [1])
    # Backup, precision, ladder and reuse axes. Single-valued by
    # default, so the lattice only grows when a caller opts into the
    # comparison; axes sharing an oracle_key reuse the same answer.
    backup_updates: list = field(default_factory=lambda: ["xla"])
    precisions: list = field(default_factory=lambda: ["float32"])
    # Serve-shape ladders ("" = fixed single rung; "64,256,1024" =
    # the micro-batcher's rung set). Free axis for the oracle.
    serve_bucket_ladders: list = field(default_factory=lambda: [""])
    tree_reuses: list = field(default_factory=lambda: [False])

    def candidates(self) -> list:
        """Every lattice point, B descending within each group so the
        dominance walk can early-exit on the first feasible lane count."""
        kernel_points = [
            (bu, pr, sb, tr)
            for bu in self.backup_updates
            for pr in self.precisions
            for sb in self.serve_bucket_ladders
            for tr in self.tree_reuses
        ]
        out = []
        for geometry in self.geometries:
            for capacity in sorted({int(c) for c in self.capacities}):
                for chunk in sorted({int(t) for t in self.chunks}):
                    for k in sorted({int(k) for k in self.fused_ks}):
                        for dp in sorted({int(d) for d in self.dps}):
                            for backup, prec, buckets, reuse in kernel_points:
                                for b in sorted(
                                    {int(b) for b in self.batches},
                                    reverse=True,
                                ):
                                    out.append(
                                        Candidate(
                                            geometry=geometry,
                                            sp_batch=b,
                                            capacity=capacity,
                                            chunk=chunk,
                                            fused_k=k,
                                            dp=dp,
                                            backup_update=backup,
                                            inference_precision=prec,
                                            serve_buckets=buckets,
                                            tree_reuse=reuse,
                                        )
                                    )
        return out

    def size(self) -> int:
        return (
            len(self.geometries)
            * len({int(b) for b in self.batches})
            * len({int(c) for c in self.capacities})
            * len({int(t) for t in self.chunks})
            * len({int(k) for k in self.fused_ks})
            * len({int(d) for d in self.dps})
            * len(self.backup_updates)
            * len(self.precisions)
            * len(self.serve_bucket_ladders)
            * len(self.tree_reuses)
        )


def divisibility_gate(
    candidate: Candidate, lbatch: int, min_buffer: int
) -> "str | None":
    """Reason string when a candidate fails a hard config gate, else
    None. Mirrors `sharded_megastep_dp` (telemetry/memory.py) plus the
    TrainConfig validators, so gated candidates are exactly the ones a
    run would reject or silently de-shard."""
    c = candidate
    if c.sp_batch < 1 or c.capacity < 1 or c.chunk < 1 or c.fused_k < 1:
        return "non-positive axis"
    if lbatch > c.capacity:
        return f"BATCH_SIZE {lbatch} > BUFFER_CAPACITY {c.capacity}"
    if min_buffer > c.capacity:
        return (
            f"MIN_BUFFER_SIZE_TO_TRAIN {min_buffer} > "
            f"BUFFER_CAPACITY {c.capacity}"
        )
    if c.dp > 1:
        # The sharded-megastep gate: every sharded dimension must
        # divide dp or the run falls back to the single-device family.
        for name, value in (
            ("BUFFER_CAPACITY", c.capacity),
            ("BATCH_SIZE", lbatch),
            ("SELF_PLAY_BATCH_SIZE", c.sp_batch),
        ):
            if value % c.dp != 0:
                return f"{name} {value} % dp {c.dp} != 0"
    return None


def prune_dominated(candidates: list, feasible: set) -> dict:
    """{candidate: status} marking every candidate whose group already
    holds a feasible sibling with a larger-or-equal B as dominated.

    `feasible` is the set of candidates the oracle confirmed. Used by
    the search to label rows; the search itself never oracle-checks a
    candidate once a bigger sibling fit (monotone-in-B dominance)."""
    best_b: dict = {}
    for c in feasible:
        key = c.group_key()
        if key not in best_b or c.sp_batch > best_b[key]:
            best_b[key] = c.sp_batch
    out = {}
    for c in candidates:
        top = best_b.get(c.group_key())
        if top is not None and c.sp_batch < top:
            out[c] = STATUS_DOMINATED
    return out
