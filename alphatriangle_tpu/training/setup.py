"""Component construction (reference `training/setup.py:29-239`).

The reference's setup is dominated by Ray: `ray.init` fallbacks, CPU
detection and worker clamping, detached-actor discovery. None of that
exists here — setup is pure object construction plus config validation,
mesh building, and checkpoint-manager creation. Errors propagate; there
are no actors to tear down on failure.
"""

import logging
import os
from typing import NamedTuple

from ..config.env_config import EnvConfig
from ..config.mcts_config import MCTSConfig
from ..config.mesh_config import (
    MeshConfig,
    lane_shard_count,
    rollout_lane_axes,
)
from ..config.model_config import ModelConfig
from ..config.persistence_config import PersistenceConfig
from ..config.telemetry_config import TelemetryConfig
from ..config.train_config import TrainConfig
from ..config.validation import print_config_info_and_validate
from ..env.engine import TriangleEnv
from ..features.core import get_feature_extractor
from ..nn.network import NeuralNetwork
from ..parallel.distributed import is_primary
from ..rl.buffer import ExperienceBuffer
from ..rl.self_play import SelfPlayEngine
from ..rl.trainer import Trainer
from ..stats.collector import StatsCollector
from ..stats.persistence import CheckpointManager
from ..telemetry import RunTelemetry
from .components import TrainingComponents

logger = logging.getLogger(__name__)

# Each rollout stream keeps roughly one multi-second chunk program in
# the device FIFO at all times; past a few streams per chip the learner
# and the streams only inflate each other's queue waits.
MAX_STREAMS_PER_DEVICE = 4


def clamp_self_play_workers(requested: int) -> int:
    """Clamp rollout-stream count to the host + device budget.

    The reference clamps its Ray self-play actors to cores-2
    (`alphatriangle/training/setup.py:106-151`) because its actors ARE
    CPU-bound searchers. Streams here are producer threads driving
    device-batched engines: on an accelerator host they spend their
    lives blocked on device transfers (harvest compaction is light),
    so the binding budget is device dispatch depth
    (MAX_STREAMS_PER_DEVICE per local chip), not host cores — a 1-core
    TPU VM frontend legitimately runs several streams. Only when the
    "device" IS the host CPU does the reference's cores-2 rule apply
    unchanged. Returns the effective count, warning when it clamps.
    """
    import jax

    cores = os.cpu_count() or 1
    device_cap = MAX_STREAMS_PER_DEVICE * jax.local_device_count()
    if jax.default_backend() == "cpu":
        # The "device" IS the host: reference rule, cores-2.
        cap = max(1, min(cores - 2 if cores > 2 else 1, device_cap))
    else:
        # Accelerator host: threads are dispatch-bound, cores don't
        # bind — the per-chip dispatch budget is the whole cap.
        cap = max(1, device_cap)
    if requested > cap:
        logger.warning(
            "NUM_SELF_PLAY_WORKERS=%d exceeds this host's budget "
            "(%d cores, %d local device(s)); clamping to %d streams.",
            requested,
            cores,
            jax.local_device_count(),
            cap,
        )
        return cap
    return requested


def wants_device_ring(train_config: TrainConfig) -> bool:
    """Whether a run of `train_config` on this backend asks for the
    device-resident replay ring (`_make_buffer` grants it when the mesh
    allows). "auto" requires an accelerator backend: on the CPU backend
    host NumPy and "device" memory are the same RAM, so the scatter
    program would add overhead for nothing. The megastep requires the
    ring wherever it runs (the CPU backend included), exactly like an
    explicit "on"."""
    import jax

    mode = train_config.DEVICE_REPLAY
    return (
        mode == "on"
        or (mode == "auto" and jax.default_backend() != "cpu")
        or train_config.FUSED_MEGASTEP
    )


def _make_buffer(
    train_config: TrainConfig,
    env_config: EnvConfig,
    model_config: ModelConfig,
    extractor,
    mesh,
) -> ExperienceBuffer:
    """Pick the replay-ring home per `TrainConfig.DEVICE_REPLAY`.

    Three tiers:
    - single-device, single-process mesh -> `DeviceReplayBuffer`
      (rl/device_buffer.py): the ring lives on the one chip;
    - dp-ONLY multi-device mesh (mdl == sp == 1, single process, with
      capacity and batch dividing dp) -> `ShardedDeviceReplayBuffer`
      (rl/sharded_device_buffer.py): the ring shards over dp and
      composes with dp-sharded rollouts into a fully device-local
      experience path;
    - anything else -> host buffer.

    Whether the run asks for a device ring at all: `wants_device_ring`
    ("on" forces it on the CPU backend too — tests do).
    """
    import jax

    grid_shape = (
        model_config.GRID_INPUT_CHANNELS,
        env_config.ROWS,
        env_config.COLS,
    )
    mode = train_config.DEVICE_REPLAY
    single = jax.process_count() == 1 and mesh.devices.size == 1
    # First axis is data-parallel by convention (MeshConfig.build_mesh).
    dp = mesh.shape[mesh.axis_names[0]]
    sharded_ok = (
        jax.process_count() == 1
        and mesh.devices.size > 1
        and mesh.devices.size == dp  # dp-only: no mdl/sp replication
        and train_config.BUFFER_CAPACITY % dp == 0
        and train_config.BATCH_SIZE % dp == 0
        # The ingest shard_map splits payload lanes dp-ways, so the
        # rollout engine must actually be lane-sharded the same way —
        # a single-device engine's payload would crash the scatter.
        and train_config.SELF_PLAY_BATCH_SIZE % dp == 0
    )
    if train_config.FUSED_MEGASTEP and not (single or sharded_ok):
        # The megastep program samples and trains against a device-
        # resident ring: either the single-device ring or the dp-
        # sharded one (per-device ring shards + in-program shard_map
        # sampling, rl/megastep.py) — anything else has no ring for
        # the fused program to live in.
        raise ValueError(
            "FUSED_MEGASTEP needs a single-process mesh that is "
            "single-device, or dp-only with BUFFER_CAPACITY, "
            "BATCH_SIZE and SELF_PLAY_BATCH_SIZE divisible by dp "
            f"(got {dict(mesh.shape)}, {jax.process_count()} "
            "processes)."
        )
    want = wants_device_ring(train_config)
    if mode == "on" and not (single or sharded_ok):
        # An explicit force that can't be honored must not silently
        # substitute the other code path.
        raise ValueError(
            "DEVICE_REPLAY='on' needs a single-device mesh or a "
            "single-process dp-only mesh with BUFFER_CAPACITY, "
            "BATCH_SIZE and SELF_PLAY_BATCH_SIZE divisible by dp "
            f"(got {dict(mesh.shape)}, {jax.process_count()} "
            "processes); use DEVICE_REPLAY='auto' to fall back to "
            "the host buffer."
        )
    if want and single:
        from ..rl.device_buffer import DeviceReplayBuffer

        logger.info(
            "Device-resident replay ring: capacity %d on %s.",
            train_config.BUFFER_CAPACITY,
            jax.devices()[0],
        )
        return DeviceReplayBuffer(
            train_config,
            grid_shape=grid_shape,
            other_dim=extractor.other_dim,
            action_dim=env_config.action_dim,
        )
    if want and sharded_ok:
        from ..rl.sharded_device_buffer import ShardedDeviceReplayBuffer

        logger.info(
            "dp-sharded device replay ring: capacity %d over %d shards.",
            train_config.BUFFER_CAPACITY,
            dp,
        )
        return ShardedDeviceReplayBuffer(
            train_config,
            grid_shape=grid_shape,
            other_dim=extractor.other_dim,
            action_dim=env_config.action_dim,
            mesh=mesh,
            dp_axis=mesh.axis_names[0],
        )
    if want:
        logger.info(
            "DEVICE_REPLAY=%s: mesh %s not eligible for a device ring "
            "-> host buffer.",
            mode,
            dict(mesh.shape),
        )
    return ExperienceBuffer(train_config, action_dim=env_config.action_dim)


class RunPrograms(NamedTuple):
    """The objects that own a run's compiled programs."""

    mesh: object
    env: TriangleEnv
    extractor: object
    net: NeuralNetwork
    trainer: Trainer
    buffer: ExperienceBuffer
    self_play: SelfPlayEngine
    megastep: object


def build_run_programs(
    env_config: EnvConfig,
    model_config: ModelConfig,
    mcts_config: MCTSConfig,
    train_config: TrainConfig,
    mesh_config: MeshConfig,
    telemetry_config: TelemetryConfig,
) -> RunPrograms:
    """Mesh, net, trainer, replay ring, rollout engine and (under
    FUSED_MEGASTEP) the megastep runner for one set of configs.

    `setup_training_components` and `warm.warm_programs` both build
    through here: a program's AOT cache key covers its configs, the
    device-stats flag, the mesh and the ring it takes as an argument, so
    `cli warm` pays off only if it constructs exactly what the run
    constructs."""
    # Resolve the telemetry config FIRST and publish the device-stats
    # flag process-wide: engines snapshot it at CONSTRUCTION (it shapes
    # their compiled programs and joins the AOT cache digests), so the
    # flag must be settled before SelfPlayEngine/Trainer exist. Set on
    # every process unconditionally — a primary-only gate would compile
    # DIFFERENT programs per process and deadlock a multi-host mesh.
    from ..telemetry.device_stats import set_device_stats

    set_device_stats(
        telemetry_config.ENABLED and telemetry_config.DEVICE_STATS
    )

    try:
        mesh = mesh_config.build_mesh()
    except ValueError as exc:
        logger.warning("Mesh build failed (%s); single-device fallback.", exc)
        mesh = MeshConfig.single_device_mesh()

    env = TriangleEnv(env_config)
    extractor = get_feature_extractor(env, model_config)
    # Sequence-parallel attention when the mesh has a real sp axis
    # (otherwise a configured SP_SIZE would silently shard nothing and
    # halve effective throughput with replicated work).
    attention_fn = None
    if mesh.shape.get(mesh_config.SP_AXIS, 1) > 1:
        from ..parallel import make_sp_attention

        attention_fn = make_sp_attention(
            mesh,
            kind=mesh_config.SP_ATTENTION,
            sp_axis=mesh_config.SP_AXIS,
            dp_axis=mesh_config.DP_AXIS,
        )
        logger.info(
            "Sequence-parallel attention: %s over sp=%d",
            mesh_config.SP_ATTENTION,
            mesh.shape[mesh_config.SP_AXIS],
        )
    net = NeuralNetwork(
        model_config,
        env_config,
        seed=train_config.RANDOM_SEED,
        attention_fn=attention_fn,
    )
    trainer = Trainer(
        net, train_config, mesh=mesh, mdl_axis=mesh_config.MDL_AXIS
    )
    buffer = _make_buffer(train_config, env_config, model_config, extractor, mesh)
    # Multi-device mesh: shard the lockstep lanes so rollouts occupy
    # every chip, not just one of the learner's (the reference fans
    # self-play actors across hardware, `worker_manager.py:39-75`).
    # Lanes ride the dp axis plus sp when present — sequence
    # parallelism never applies to the board-sized rollout net, so a
    # real sp axis would otherwise sit idle (or worse, duplicate
    # rollout work) during self-play.
    sp_mesh = None
    sp_axes: tuple = ()
    if mesh.devices.size > 1:
        sp_axes = rollout_lane_axes(
            mesh, mesh_config.DP_AXIS, mesh_config.SP_AXIS
        )
        lane_shards = lane_shard_count(mesh, sp_axes)
        if train_config.SELF_PLAY_BATCH_SIZE % lane_shards == 0:
            sp_mesh = mesh
            logger.info(
                "Self-play lanes sharded over mesh axes %s (%d-way).",
                sp_axes,
                lane_shards,
            )
        else:
            logger.warning(
                "SELF_PLAY_BATCH_SIZE=%d does not divide the mesh's "
                "%d lane shards %s; self-play stays on one device "
                "(pick a divisible batch to fan rollouts across the "
                "mesh).",
                train_config.SELF_PLAY_BATCH_SIZE,
                lane_shards,
                sp_axes,
            )
    self_play = SelfPlayEngine(
        env,
        extractor,
        net,
        mcts_config,
        train_config,
        seed=train_config.RANDOM_SEED + 1,
        mesh=sp_mesh,
        data_axes=sp_axes or ("dp",),
    )
    # Fused megastep (rl/megastep.py): one device program per iteration
    # runs rollout + ingest + on-device PER sampling + K learner steps;
    # the runner binds the engine/trainer/ring triple built above.
    megastep_runner = None
    if train_config.FUSED_MEGASTEP:
        from ..rl.megastep import MegastepRunner

        megastep_runner = MegastepRunner(
            self_play, trainer, buffer, train_config
        )
        logger.info(
            "Fused megastep mode: %d moves + %d learner steps per "
            "mesh dispatch (%d-way dp-sharded).",
            train_config.ROLLOUT_CHUNK_MOVES,
            train_config.LEARNER_STEPS_PER_ROLLOUT
            or max(1, train_config.FUSED_LEARNER_STEPS),
            megastep_runner.dp,
        )
    return RunPrograms(
        mesh, env, extractor, net, trainer, buffer, self_play, megastep_runner
    )


def setup_training_components(
    train_config: TrainConfig | None = None,
    env_config: EnvConfig | None = None,
    model_config: ModelConfig | None = None,
    mcts_config: MCTSConfig | None = None,
    mesh_config: MeshConfig | None = None,
    persistence_config: PersistenceConfig | None = None,
    telemetry_config: TelemetryConfig | None = None,
    use_tensorboard: bool = True,
) -> TrainingComponents:
    """Validate configs and build every training component."""
    configs = print_config_info_and_validate(
        env=env_config,
        model=model_config,
        train=train_config,
        mcts=mcts_config,
        mesh=mesh_config,
        persistence=persistence_config,
    )
    env_config = configs["env"]
    model_config = configs["model"]
    train_config = configs["train"]
    mcts_config = configs["mcts"]
    mesh_config = configs["mesh"]
    persistence_config = configs["persistence"]
    # The run's artifacts live under its RUN_NAME.
    if persistence_config.RUN_NAME != train_config.RUN_NAME:
        persistence_config = persistence_config.model_copy(
            update={"RUN_NAME": train_config.RUN_NAME}
        )

    telemetry_config = telemetry_config or TelemetryConfig()
    mesh, env, extractor, net, trainer, buffer, self_play, megastep_runner = (
        build_run_programs(
            env_config,
            model_config,
            mcts_config,
            train_config,
            mesh_config,
            telemetry_config,
        )
    )
    # TensorBoard and the live-console JSONL are singleton host-side
    # work: process 0 only (N processes appending one shared file would
    # interleave diverging step/episode lines and corrupt `cli watch`'s
    # windowed rates).
    stats = StatsCollector(
        persistence_config,
        use_tensorboard=use_tensorboard and is_primary(),
        use_live_file=is_primary(),
    )
    checkpoints = CheckpointManager(persistence_config)
    # Telemetry (spans + heartbeat + watchdog + anomaly screening) is a
    # primary-process concern like the live file: N hosts rewriting one
    # shared health.json would interleave diverging heartbeats.
    if not is_primary():
        telemetry_config = telemetry_config.model_copy(
            update={"ENABLED": False}
        )
    # Live MFU/throughput accounting (telemetry/perf.py): analytic
    # FLOPs from the run's own model/env configs, peak from the device
    # kind table or the ALPHATRIANGLE_PEAK_TFLOPS override. Feeds the
    # metrics ledger, health.json and `cli watch`.
    import jax

    from ..telemetry.perf import UtilizationMeter
    from ..utils.flops import forward_flops, model_step_flops

    device = jax.devices()[0]
    perf_meter = UtilizationMeter(
        forward_flops=forward_flops(
            model_config, env_config, env_config.action_dim
        ),
        # An MFU credits no recomputed forward (REMAT).
        train_step_flops=model_step_flops(
            model_config,
            env_config,
            env_config.action_dim,
            train_config.BATCH_SIZE,
        ),
        device_kind=str(getattr(device, "device_kind", device.platform)),
        buffer_capacity=train_config.BUFFER_CAPACITY,
        # Gauge denominator contract: dispatch counters tally mesh-level
        # program launches (one per host dispatch, however many devices
        # the mesh spans), so the meter records the mesh width beside
        # them instead of scaling them by it.
        mesh_devices=mesh.devices.size,
    )
    telemetry = RunTelemetry(
        telemetry_config,
        run_dir=persistence_config.get_run_base_dir(),
        stats=stats,
        run_name=persistence_config.RUN_NAME,
        perf=perf_meter,
    )
    # Every processed metric batch is appended to the durable ledger —
    # including the loop's final force flush and the collector's own
    # close-time flush (docs/OBSERVABILITY.md "Ledger").
    stats.set_tick_sink(telemetry.record_metrics)
    # Compile costs become `compile/<program>` spans in trace.json: the
    # AOT executable cache (compile_cache.py) reports every hit
    # (deserialize), miss (fresh compile) and serialize through the
    # run's tracer, so cold-vs-warm start cost is visible next to the
    # rollout/learner spans it delays.
    from ..compile_cache import get_compile_cache

    get_compile_cache().set_tracer(telemetry.tracer)
    # Dispatch flight recorder (telemetry/flight.py): every hot-family
    # device dispatch writes an intent record before launch and a seal
    # after the fetch, so a SIGKILLed window still names the program it
    # died inside (`cli doctor`).
    self_play.flight = telemetry.flight
    trainer.flight = telemetry.flight
    if megastep_runner is not None:
        megastep_runner.flight = telemetry.flight
    # Static memory attribution -> metrics ledger (telemetry/memory.py):
    # train-state bytes from tree-size accounting, replay-ring bytes
    # from the buffers' own dtype/shape math. Program records join
    # lazily as each program compiles (compile_cache memory capture);
    # `cli mem <run>` renders the combined table from artifacts alone.
    try:
        from ..telemetry.memory import replay_ring_record, replay_ring_bytes, train_state_record

        telemetry.record_memory(train_state_record(trainer.state))
        if hasattr(buffer, "memory_record"):
            telemetry.record_memory(buffer.memory_record())
        else:
            telemetry.record_memory(
                replay_ring_record(
                    replay_ring_bytes(
                        train_config.BUFFER_CAPACITY,
                        (
                            model_config.GRID_INPUT_CHANNELS,
                            env_config.ROWS,
                            env_config.COLS,
                        ),
                        extractor.other_dim,
                        env_config.action_dim,
                    ),
                    train_config.BUFFER_CAPACITY,
                    location="host",
                )
            )
    except Exception:
        logger.exception("static memory attribution failed (continuing)")
    # Compiler cost ground truth for the learner-side family
    # (telemetry/roofline.py): on CPU those programs bypass the AOT
    # dispatch path (cpu_aot=False), so nothing would ever capture
    # their `cost_analysis()` — analyze once at setup. On an
    # accelerator every dispatched program captures its own cost when
    # it compiles, and the program analyzed here is not always one the
    # run dispatches (a fused-K sync run never calls the per-step
    # program: 90 s of set-up compile on a v5e for nothing). Best-effort
    # like the block above; ALPHATRIANGLE_COST_PRECAPTURE=0 skips it
    # (the test suite — the compile is pure overhead in seconds-long
    # throwaway runs).
    from ..telemetry.roofline import cost_precapture_enabled

    if (
        telemetry.enabled
        and cost_precapture_enabled()
        and jax.default_backend() == "cpu"
    ):
        try:
            if megastep_runner is not None:
                megastep_runner.analyze_megastep()
            else:
                trainer.analyze_step()
        except Exception:
            logger.exception("cost pre-capture failed (continuing)")
    all_configs = {
        "env": env_config,
        "model": model_config,
        "train": train_config,
        "mcts": mcts_config,
        "mesh": mesh_config,
        "persistence": persistence_config,
        "telemetry": telemetry_config,
    }
    checkpoints.save_configs(all_configs)
    # Experiment-param channel (reference `logging_utils.py:13-35`).
    stats.log_params(all_configs)
    logger.info(
        "Components ready: mesh %s, self-play batch %d, run %s",
        dict(mesh.shape),
        self_play.batch_size,
        persistence_config.RUN_NAME,
    )
    return TrainingComponents(
        env=env,
        extractor=extractor,
        net=net,
        buffer=buffer,
        trainer=trainer,
        self_play=self_play,
        stats=stats,
        checkpoints=checkpoints,
        env_config=env_config,
        model_config=model_config,
        train_config=train_config,
        mcts_config=mcts_config,
        mesh_config=mesh_config,
        persistence_config=persistence_config,
        telemetry=telemetry,
        telemetry_config=telemetry_config,
        megastep=megastep_runner,
    )
