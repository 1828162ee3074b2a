"""Training-loop configuration.

Capability parity with the reference `TrainConfig`
(`alphatriangle/config/train_config.py:18-103`): loop length, batching,
n-step returns, optimizer/scheduler, loss weights, checkpoint cadence,
PER knobs, profiling. TPU-specific additions replace the reference's
per-worker-CPU knobs with on-device self-play sizing: the number of
games stepped in parallel on the accelerator and the rollout chunk
length per dispatch.
"""

import time
from typing import Literal

from pydantic import BaseModel, Field, field_validator, model_validator


class TrainConfig(BaseModel):
    """Training hyperparameters (pydantic)."""

    RUN_NAME: str = Field(
        default_factory=lambda: f"train_{time.strftime('%Y%m%d_%H%M%S')}"
    )
    LOAD_CHECKPOINT_PATH: str | None = Field(default=None)
    LOAD_BUFFER_PATH: str | None = Field(default=None)
    AUTO_RESUME_LATEST: bool = Field(default=True)
    RANDOM_SEED: int = Field(default=42)

    # --- Loop ---
    MAX_TRAINING_STEPS: int | None = Field(default=100_000, ge=1)

    # --- Self-play (TPU-native: games batched on device, not Ray actors) ---
    # Number of games stepped in lockstep per device dispatch. This is
    # the MCTS leaf-eval batch seen by the MXU (replaces the reference's
    # NUM_SELF_PLAY_WORKERS x mcts_batch_size CPU batching).
    SELF_PLAY_BATCH_SIZE: int = Field(default=512, ge=1)
    # Moves played per jitted rollout dispatch before results return to host.
    ROLLOUT_CHUNK_MOVES: int = Field(default=16, ge=1)
    # The reference's worker-count knob, re-expressed: in overlapped
    # mode (ASYNC_ROLLOUTS) this many independent rollout streams run,
    # each a producer thread driving its own SELF_PLAY_BATCH_SIZE-lane
    # engine (own PRNG stream + game carry, shared weights), all
    # feeding one harvest queue. Streams pipeline host-side harvest
    # compaction against device compute. Ignored by the synchronous
    # loop (one stream).
    NUM_SELF_PLAY_WORKERS: int = Field(default=1, ge=1)
    WORKER_UPDATE_FREQ_STEPS: int = Field(default=10, ge=1)
    # Hard cap on moves per episode (safety net for jitted rollouts).
    MAX_EPISODE_MOVES: int = Field(default=1000, ge=1)
    # Learner steps per rollout chunk. None = auto: match the production
    # rate (experiences harvested / BATCH_SIZE), the synchronous
    # equivalent of the reference's free-running async learner.
    LEARNER_STEPS_PER_ROLLOUT: int | None = Field(default=None, ge=1)

    # --- Overlapped (async) orchestration ---
    # Run self-play in a producer thread feeding a bounded queue while
    # the learner consumes at REPLAY_RATIO; host work (harvest
    # compaction, PER sampling, priority updates) then overlaps with
    # device compute instead of serializing with it (the reference's
    # async producer/consumer topology, `training/loop.py:298-416`,
    # re-expressed for one process).
    ASYNC_ROLLOUTS: bool = Field(default=False)
    # Target learner consumption rate: samples consumed per experience
    # produced (steps * BATCH_SIZE / experiences). The synchronous
    # loop's implicit `added/BATCH_SIZE` matching corresponds to 1.0;
    # here it is an explicit, measured knob.
    REPLAY_RATIO: float = Field(default=1.0, gt=0)
    # Bounded harvest queue between producer and learner (backpressure:
    # the producer blocks when the learner falls this many chunks behind).
    ROLLOUT_QUEUE_MAX: int = Field(default=4, ge=1)
    # Pipelined learner (overlapped mode only): dispatch fused group
    # N+1 to the device BEFORE fetching group N's results, so the
    # learner always has a program queued behind the producers' rollout
    # chunks and never blocks a full host round trip per group. Costs
    # one extra group of PER-priority staleness (bounded by
    # FUSED_LEARNER_STEPS); False restores strictly serial fetches.
    PIPELINE_LEARNER: bool = Field(default=True)
    # Target wall-clock seconds per producer rollout dispatch in
    # overlapped mode. A flagship chunk of ROLLOUT_CHUNK_MOVES moves is
    # a single multi-second device program the learner's dispatches
    # must queue behind (measured 0.02 learner steps/s at 16-move
    # ~10 s chunks); producers auto-shrink their per-dispatch move
    # count until a chunk fits this budget, bounding the learner's
    # queue wait. None disables auto-tuning (dispatch
    # ROLLOUT_CHUNK_MOVES every time).
    ASYNC_CHUNK_SECONDS: float | None = Field(default=2.0, gt=0)
    # Producer stream supervision: a crashed rollout stream is
    # respawned with a fresh engine (carry + PRNG; compiled programs
    # shared, so no recompile) after an exponential backoff, up to
    # this many times per stream; exhausted, the run aborts with the
    # original error. The reference detects dead actors and merely
    # removes them (`worker_manager.py:153-159`) — SURVEY §7.9 asked
    # for restart. 0 = abort on first crash.
    PRODUCER_MAX_RESTARTS: int = Field(default=3, ge=0)
    PRODUCER_RESTART_BACKOFF_S: float = Field(default=1.0, gt=0)

    # --- Fused megastep (Anakin) orchestration ---
    # Third loop mode (rl/megastep.py, docs/PARALLELISM.md "Megastep"):
    # rollout chunk + device-ring ingest + on-device PER sampling + K
    # fused learner steps run as ONE jitted device program, so the only
    # per-iteration host work is fetching stats/metrics (one dispatch,
    # one fetch). Weight sync is free and zero-staleness — the rollout
    # reads the learner's live on-device params; there is no
    # sync_to_network copy on the hot path. Requires the device-resident
    # replay ring on a single-device, single-process mesh
    # (DEVICE_REPLAY must not be "off"; megastep forces the ring on
    # otherwise-ineligible backends the way DEVICE_REPLAY="on" does).
    # Learner steps per megastep = LEARNER_STEPS_PER_ROLLOUT when set,
    # else FUSED_LEARNER_STEPS. Mutually exclusive with ASYNC_ROLLOUTS.
    FUSED_MEGASTEP: bool = Field(default=False)

    # --- Batching / buffer ---
    BATCH_SIZE: int = Field(default=256, ge=1)
    # Learner steps fused into ONE device dispatch (a lax.scan over
    # pre-sampled batches). 1 = exact reference semantics (PER
    # priorities update between consecutive steps). >1 trades bounded
    # priority staleness (< FUSED_LEARNER_STEPS steps) for one host
    # round trip per group instead of per step, which is what lets the
    # learner keep pace with multi-second self-play chunks on a single
    # shared chip.
    FUSED_LEARNER_STEPS: int = Field(default=1, ge=1)
    BUFFER_CAPACITY: int = Field(default=250_000, ge=1)
    MIN_BUFFER_SIZE_TO_TRAIN: int = Field(default=25_000, ge=1)
    # Device-resident replay ring: experiences stream from the rollout
    # program into an on-device ring buffer and training batches are
    # gathered on device from host-chosen indices, so the steady-state
    # training loop moves only scalars, indices and metrics between
    # host and device. "auto" enables it on single-process accelerator
    # meshes (where re-uploading every sampled batch over the
    # host<->device link bounds the learner): one chip gets the
    # single ring (rl/device_buffer.py); a dp-only multi-device mesh
    # gets the dp-SHARDED ring (rl/sharded_device_buffer.py) — each
    # device ingests its own rollout lanes and gathers its own batch
    # shard, so no experience bytes cross devices either. "off" keeps
    # the host SoA ring; "on" forces the device ring (CPU backend
    # included — used by tests).
    DEVICE_REPLAY: Literal["auto", "on", "off"] = Field(default="auto")

    # --- N-step returns ---
    N_STEP_RETURNS: int = Field(default=5, ge=1)
    GAMMA: float = Field(default=0.99, gt=0, le=1.0)

    # --- Optimizer ---
    OPTIMIZER_TYPE: Literal["Adam", "AdamW", "SGD"] = Field(default="AdamW")
    LEARNING_RATE: float = Field(default=2e-4, gt=0)
    WEIGHT_DECAY: float = Field(default=1e-4, ge=0)
    GRADIENT_CLIP_VALUE: float | None = Field(default=1.0)

    # --- LR schedule ---
    LR_SCHEDULER_TYPE: Literal["StepLR", "CosineAnnealingLR"] | None = Field(
        default="CosineAnnealingLR"
    )
    LR_SCHEDULER_T_MAX: int | None = Field(default=None)
    LR_SCHEDULER_ETA_MIN: float = Field(default=1e-6, ge=0)
    LR_SCHEDULER_STEP_SIZE: int = Field(default=10_000, ge=1)
    LR_SCHEDULER_GAMMA: float = Field(default=0.5, gt=0, le=1.0)

    # --- Loss weights ---
    POLICY_LOSS_WEIGHT: float = Field(default=1.0, ge=0)
    VALUE_LOSS_WEIGHT: float = Field(default=1.0, ge=0)
    ENTROPY_BONUS_WEIGHT: float = Field(default=0.001, ge=0)

    # --- Checkpointing ---
    CHECKPOINT_SAVE_FREQ_STEPS: int = Field(default=2500, ge=1)

    # --- PER ---
    USE_PER: bool = Field(default=True)
    PER_ALPHA: float = Field(default=0.6, ge=0)
    PER_BETA_INITIAL: float = Field(default=0.4, ge=0, le=1.0)
    PER_BETA_FINAL: float = Field(default=1.0, ge=0, le=1.0)
    PER_BETA_ANNEAL_STEPS: int | None = Field(default=None)
    PER_EPSILON: float = Field(default=1e-5, gt=0)
    # How the on-device stratified PER draw locates its cumsum indices:
    # "xla" (searchsorted) or "pallas" (tiled compare-count kernel,
    # ops/per_sample.py). Bit-identical selections (exact float
    # compares over a shared prefix-sum); a pure performance knob to
    # be settled by on-hardware benchmarks.
    PER_SAMPLE_BACKEND: str = Field(default="xla", pattern="^(xla|pallas)$")

    # --- Temperature schedule for action selection (move-indexed) ---
    TEMPERATURE_INITIAL: float = Field(default=1.0, ge=0)
    TEMPERATURE_FINAL: float = Field(default=0.1, ge=0)
    TEMPERATURE_ANNEAL_MOVES: int = Field(default=30, ge=1)

    # --- Device / compile ---
    # DEVICE is enforced at startup (utils.helpers.enforce_platform).
    # WORKER_DEVICE and COMPILE_MODEL are config-surface parity stubs:
    # self-play shares the learner's device by design (there are no
    # separate worker processes), and JAX jits everything regardless.
    DEVICE: Literal["auto", "tpu", "cpu"] = Field(default="auto")
    WORKER_DEVICE: Literal["auto", "tpu", "cpu"] = Field(default="auto")
    COMPILE_MODEL: bool = Field(default=True)

    # --- Profiling ---
    PROFILE_WORKERS: bool = Field(default=False)

    @model_validator(mode="after")
    def _check_buffer_sizes(self) -> "TrainConfig":
        if self.MIN_BUFFER_SIZE_TO_TRAIN > self.BUFFER_CAPACITY:
            raise ValueError(
                "MIN_BUFFER_SIZE_TO_TRAIN cannot be greater than BUFFER_CAPACITY."
            )
        if self.BATCH_SIZE > self.BUFFER_CAPACITY:
            raise ValueError("BATCH_SIZE cannot be greater than BUFFER_CAPACITY.")
        return self

    @model_validator(mode="after")
    def _derive_schedule_lengths(self) -> "TrainConfig":
        # Auto-derive cosine horizon and PER beta anneal from the run
        # length, as the reference does (`train_config.py:131-209`).
        horizon = self.MAX_TRAINING_STEPS or 100_000
        if self.LR_SCHEDULER_TYPE == "CosineAnnealingLR" and self.LR_SCHEDULER_T_MAX is None:
            self.LR_SCHEDULER_T_MAX = horizon
        if self.USE_PER and self.PER_BETA_ANNEAL_STEPS is None:
            self.PER_BETA_ANNEAL_STEPS = horizon
        if self.LR_SCHEDULER_T_MAX is not None and self.LR_SCHEDULER_T_MAX <= 0:
            raise ValueError("LR_SCHEDULER_T_MAX must be positive if set.")
        if self.PER_BETA_ANNEAL_STEPS is not None and self.PER_BETA_ANNEAL_STEPS <= 0:
            raise ValueError("PER_BETA_ANNEAL_STEPS must be positive if set.")
        return self

    @field_validator("GRADIENT_CLIP_VALUE")
    @classmethod
    def _check_grad_clip(cls, v: float | None) -> float | None:
        if v is not None and v <= 0:
            raise ValueError("GRADIENT_CLIP_VALUE must be positive if set.")
        return v

    @model_validator(mode="after")
    def _check_megastep(self) -> "TrainConfig":
        if self.FUSED_MEGASTEP and self.ASYNC_ROLLOUTS:
            raise ValueError(
                "FUSED_MEGASTEP and ASYNC_ROLLOUTS are mutually "
                "exclusive loop modes (the megastep already overlaps "
                "acting and learning inside one device program)."
            )
        if self.FUSED_MEGASTEP and self.DEVICE_REPLAY == "off":
            raise ValueError(
                "FUSED_MEGASTEP needs the device-resident replay ring "
                "(its sampling and ingest run on device); set "
                "DEVICE_REPLAY to 'auto' or 'on'."
            )
        return self

    @model_validator(mode="after")
    def _check_beta(self) -> "TrainConfig":
        if self.PER_BETA_FINAL < self.PER_BETA_INITIAL:
            raise ValueError("PER_BETA_FINAL cannot be less than PER_BETA_INITIAL.")
        return self


TrainConfig.model_rebuild(force=True)
