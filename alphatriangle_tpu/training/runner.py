"""Top-level `run_training` (reference `training/runner.py:166-307`).

Responsibilities kept at parity: logging setup, auto-resume resolution,
component setup, initial-state load (train state + buffer + counters),
loop run, final save, exit-code mapping. Dropped by design: Ray init/
shutdown, actor kill fallbacks, MLflow bootstrapping (TensorBoard only
in this environment).
"""

import json
import logging
import os
import signal
import threading

from ..config.env_config import EnvConfig
from ..config.mcts_config import MCTSConfig
from ..config.mesh_config import MeshConfig
from ..config.model_config import ModelConfig
from ..config.persistence_config import PersistenceConfig
from ..config.telemetry_config import TelemetryConfig
from ..config.train_config import TrainConfig
from ..logging_config import setup_logging
from ..parallel.distributed import (
    DistributedConfig,
    initialize_distributed,
    is_primary,
)
from ..stats.persistence import CheckpointManager
from ..telemetry.flight import PREEMPT_EXIT_CODE
from ..utils.helpers import (
    enable_persistent_compilation_cache,
    enforce_platform,
)
from .loop import LoopStatus, TrainingLoop
from .setup import setup_training_components

logger = logging.getLogger(__name__)

EXIT_CODES = {
    LoopStatus.COMPLETED: 0,
    LoopStatus.STOPPED: 0,
    LoopStatus.ERROR: 1,
    LoopStatus.PREEMPTED: PREEMPT_EXIT_CODE,
}

#: JSON env var of TrainConfig field overrides injected by
#: `cli supervise` (supervise/supervisor.py OVERRIDES_ENV): the
#: recovery policy's degraded/quarantined knobs reach the child here,
#: regardless of which CLI flags spawned it. `<FIELD>__scale` keys
#: multiply the current value (min 1) instead of replacing it.
SUPERVISE_OVERRIDES_ENV = "ALPHATRIANGLE_SUPERVISE_OVERRIDES"


def _apply_supervise_overrides(train_config: TrainConfig) -> TrainConfig:
    raw = os.environ.get(SUPERVISE_OVERRIDES_ENV)
    if not raw:
        return train_config
    try:
        overrides = json.loads(raw)
    except ValueError:
        logger.warning(
            "Unparseable %s=%r; ignoring.", SUPERVISE_OVERRIDES_ENV, raw
        )
        return train_config
    if not isinstance(overrides, dict) or not overrides:
        return train_config
    # Reserved telemetry directives ride the same override channel but
    # are NOT TrainConfig fields — pop them before construction. The
    # only one today: `TELEMETRY__BEACONS` (the policy sets it on a
    # wedge respawn) arms progress beacons process-wide BEFORE any
    # engine compiles, so the rebuilt programs phase themselves into
    # beacons.jsonl (telemetry/device_stats.py).
    telemetry_keys = {
        k: overrides.pop(k)
        for k in [k for k in overrides if k.startswith("TELEMETRY__")]
    }
    if telemetry_keys.get("TELEMETRY__BEACONS"):
        from ..telemetry.device_stats import arm_beacons

        arm_beacons()
        logger.warning(
            "Supervisor directive TELEMETRY__BEACONS: progress beacons "
            "armed for this respawn."
        )
    if not overrides:
        return train_config
    resolved: dict = {}
    for key, value in overrides.items():
        if key.endswith("__scale"):
            field = key[: -len("__scale")]
            current = getattr(train_config, field)
            resolved[field] = max(1, round(current * float(value)))
        else:
            resolved[key] = value
    logger.warning(
        "Supervisor recovery overrides active: %s", resolved
    )
    # Rebuild through the constructor so pydantic validation runs
    # (mirrors cli.merge_train_overrides) and derived schedule lengths
    # stay untouched — the horizon is not a recovery knob.
    base = train_config.model_dump()
    base.update(resolved)
    return TrainConfig(**base)


def _install_preempt_handler(loop: TrainingLoop):
    """Route SIGTERM into `loop.request_preempt()` (main thread only —
    signal.signal raises elsewhere, and library callers embedding
    run_training in a thread keep their own handling). Returns a
    restore callback. SIGINT keeps its KeyboardInterrupt semantics
    (exit 0, reference behavior); SIGTERM is the preemption contract."""
    if threading.current_thread() is not threading.main_thread():
        return lambda: None

    def _on_sigterm(signum, frame):
        logger.warning(
            "SIGTERM received: preempting (emergency checkpoint, then "
            "exit %d).",
            PREEMPT_EXIT_CODE,
        )
        loop.request_preempt()

    previous = signal.signal(signal.SIGTERM, _on_sigterm)
    return lambda: signal.signal(signal.SIGTERM, previous)


def _resolve_auto_resume(
    train_config: TrainConfig, persistence: PersistenceConfig
) -> tuple[TrainConfig, PersistenceConfig]:
    """Point RUN_NAME at the newest checkpointed run when auto-resume is
    on and that run isn't this one already (reference `README.md:23`,
    `setup.py:174-176`)."""
    if not train_config.AUTO_RESUME_LATEST:
        return train_config, persistence
    latest = CheckpointManager.find_latest_run(persistence)
    if latest is None or latest == train_config.RUN_NAME:
        return train_config, persistence
    logger.info("Auto-resume: continuing latest run '%s'.", latest)
    return (
        train_config.model_copy(update={"RUN_NAME": latest}),
        persistence.model_copy(update={"RUN_NAME": latest}),
    )


def run_training(
    train_config: TrainConfig | None = None,
    env_config: EnvConfig | None = None,
    model_config: ModelConfig | None = None,
    mcts_config: MCTSConfig | None = None,
    mesh_config: MeshConfig | None = None,
    persistence_config: PersistenceConfig | None = None,
    distributed_config: DistributedConfig | None = None,
    telemetry_config: TelemetryConfig | None = None,
    log_level: str = "INFO",
    use_tensorboard: bool = True,
    dry_setup: bool = False,
) -> int:
    """Run a full training session; returns a process exit code.

    `dry_setup` stops after component construction (mesh, network,
    buffer, trainer, telemetry) and returns 0 without training — the
    cheapest end-to-end proof that a config (e.g. a `cli tune` preset)
    is actually runnable on this backend (`cli train --dry-setup`)."""
    setup_logging(log_level)
    train_config = train_config or TrainConfig()
    train_config = _apply_supervise_overrides(train_config)
    # Must precede any backend init: the platform latches there.
    enforce_platform(train_config.DEVICE)
    if train_config.DEVICE_REPLAY == "on" or train_config.FUSED_MEGASTEP:
        # Forced device replay may land on the CPU backend (tests,
        # smokes). XLA:CPU's async dispatch deadlocks under the
        # device-replay thread topology, and the flag is latched at CPU
        # client creation — so it must be set HERE, before any backend
        # touch (see rl/device_buffer.py module docstring). No effect
        # on accelerator backends.
        import jax

        jax.config.update("jax_cpu_enable_async_dispatch", False)
    # Cluster membership must also precede backend init.
    multi_host = initialize_distributed(distributed_config)
    if multi_host and not is_primary():
        # Secondary hosts run compute + collective saves, no dashboards.
        use_tensorboard = False
    persistence_config = persistence_config or PersistenceConfig(
        RUN_NAME=train_config.RUN_NAME
    )
    train_config, persistence_config = _resolve_auto_resume(
        train_config, persistence_config
    )
    # Backend resolves here (setup compiles programs next): an
    # accelerator run caches its compiles, a CPU run must not.
    enable_persistent_compilation_cache()

    try:
        components = setup_training_components(
            train_config=train_config,
            env_config=env_config,
            model_config=model_config,
            mcts_config=mcts_config,
            mesh_config=mesh_config,
            persistence_config=persistence_config,
            telemetry_config=telemetry_config,
            use_tensorboard=use_tensorboard,
        )
    except Exception:
        logger.exception("Component setup failed.")
        return 1

    if dry_setup:
        components.stats.close()
        components.checkpoints.close()
        logger.info(
            "Dry setup OK: components constructed for run '%s' "
            "(no training performed).",
            train_config.RUN_NAME,
        )
        return 0

    loop = TrainingLoop(components)
    try:
        if train_config.LOAD_CHECKPOINT_PATH:
            loaded = components.checkpoints.restore_path(
                train_config.LOAD_CHECKPOINT_PATH, components.trainer.state
            )
        else:
            loaded = components.checkpoints.restore(
                components.trainer.state, buffer=components.buffer
            )
        if train_config.LOAD_BUFFER_PATH:
            components.checkpoints.restore_buffer_path(
                components.buffer, train_config.LOAD_BUFFER_PATH
            )
        if loaded.train_state is not None:
            components.trainer.set_state(loaded.train_state)
            components.trainer.sync_to_network()
            loop.set_initial_state(
                loaded.global_step,
                int(loaded.counters.get("episodes_played", 0)),
                int(loaded.counters.get("total_simulations", 0)),
            )
            loop.weight_updates = int(
                loaded.counters.get("weight_updates", 0)
            )
            logger.info(
                "Resumed at step %d (%d episodes, buffer %s).",
                loaded.global_step,
                loop.episodes_played,
                len(components.buffer),
            )
    except Exception:
        # Training a fresh model into an existing run's directory would
        # pollute its checkpoints; abort instead (the user can disable
        # AUTO_RESUME_LATEST or fix the path).
        logger.exception(
            "State restore failed for run '%s'; aborting rather than "
            "writing a fresh model into its run directory.",
            train_config.RUN_NAME,
        )
        return 1

    restore_handler = _install_preempt_handler(loop)
    try:
        status = loop.run()
    finally:
        restore_handler()
    components.stats.close()
    components.checkpoints.close()
    logger.info("Training finished: %s", status.value)
    return EXIT_CODES[status]
