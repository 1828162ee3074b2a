"""Flywheel loop mode: learner + matchmade league games in one process.

The RLAX topology (arXiv:2512.06392) folded onto one host: the
synchronous training loop keeps its rollout→learn cadence, but a
configured fraction of iterations (`LEAGUE_MIX_RATIO`) plays a round
of matchmade league games through a `PolicyService` instead of a
self-play chunk. Each round:

    broadcast live params ──► live net plays G games (emitter ON)
    matchmaker samples opponent ──► opponent plays G games (emitter OFF)
    win fraction ──► pool Elo update (league.jsonl)
    promotion gate ──► live net checkpoints into the pool on a win streak
    emitter drain ──► staleness guard ──► replay ring (max-priority PER)

Live-game trajectories are harvested by the `TrajectoryEmitter` and
folded through the exact `_fold_result` seam self-play uses, so the
replay ring ingests them with max-priority PER init and the ledger
accounts them; `kind:"league"` records carry the pool/ingest/staleness
story for `cli perf`. The service owns a SEPARATE `NeuralNetwork`
whose weights swap every half-round (`reload_weights`, zero
recompiles) — sharing the learner's net would let an opponent load
corrupt concurrent self-play.
"""

import logging
import time

from ..training.loop import TrainingLoop
from .emitter import TrajectoryEmitter, apply_staleness_guard
from .matchmaker import Matchmaker
from .pool import LEAGUE_FILENAME, LIVE_ID, LeaguePool, pairwise_win_fraction

logger = logging.getLogger(__name__)


def member_variables(checkpoints, template_state, checkpoint_path):
    """Inference variables of a pool member's checkpoint, restored
    WITHOUT touching the trainer (`restore_path` never mutates; the
    elo-ladder's restore→set_state pattern would clobber the learner
    mid-run)."""
    loaded = checkpoints.restore_path(str(checkpoint_path), template_state)
    if loaded.train_state is None:
        raise FileNotFoundError(
            f"league member checkpoint unreadable: {checkpoint_path}"
        )
    variables = {"params": loaded.train_state.params}
    batch_stats = getattr(loaded.train_state, "batch_stats", None)
    if batch_stats is not None:
        variables["batch_stats"] = batch_stats
    return variables


class FlywheelLoop(TrainingLoop):
    """`TrainingLoop` whose sync iterations interleave league rounds.

    Only the synchronous loop composes with a league round (the round
    drives the service between learner steps on one thread);
    `run_flywheel` rejects ASYNC_ROLLOUTS/FUSED_MEGASTEP configs."""

    def __init__(
        self,
        components,
        league_config,
        service,
        emitter: TrajectoryEmitter,
        pool: LeaguePool,
        matchmaker: Matchmaker,
    ):
        super().__init__(components)
        self.league = league_config
        self.service = service
        self.emitter = emitter
        self.pool = pool
        self.matchmaker = matchmaker
        self._mix_acc = 0.0
        self.league_rounds = 0
        self.league_moves_ingested = 0
        self.stale_dropped_total = 0
        # Live-params copy served during league rounds, refreshed from
        # the trainer when RELOAD_EVERY_STEPS learner steps passed.
        self._live_vars = None
        self._live_vars_step: "int | None" = None
        # member_id -> restored variables (bounded; tiny pools hit 100%).
        self._opp_cache: dict = {}

    # --- weights ---------------------------------------------------------

    def _live_variables(self):
        """Deep-copied learner variables (the trainer's are donated by
        its next step; handing them to the serve net live would alias
        freed buffers)."""
        import jax
        import jax.numpy as jnp

        step = self.global_step
        if (
            self._live_vars is None
            or step - self._live_vars_step >= self.league.RELOAD_EVERY_STEPS
        ):
            self._live_vars = jax.tree_util.tree_map(
                jnp.array, self.c.trainer.get_variables()
            )
            self._live_vars_step = step
        return self._live_vars

    def _member_variables(self, member_id: str):
        if member_id not in self._opp_cache:
            if len(self._opp_cache) >= 4:
                self._opp_cache.pop(next(iter(self._opp_cache)))
            self._opp_cache[member_id] = member_variables(
                self.c.checkpoints,
                self.c.trainer.state,
                self.pool.members[member_id]["checkpoint"],
            )
        return self._opp_cache[member_id]

    # --- one league round -------------------------------------------------

    def _league_round(self) -> int:
        """Play one matchmade pairing through the service, fold the
        live side's trajectories into the replay ring. Returns rows
        ingested (the `_fold_result` contract `_run_sync` sizes the
        learner burst with)."""
        from ..arena import play_service

        league = self.league
        svc = self.service
        t0 = time.monotonic()
        seed = (
            self.cfg.RANDOM_SEED + 9001 + 2 * self.league_rounds
        )

        # Live half: fresh params, emitter harvesting.
        svc.reload_weights(self._live_variables())
        svc.emitter = self.emitter
        try:
            live_scores, _, _ = play_service(
                svc, league.GAMES_PER_ROUND, league.MAX_GAME_MOVES, seed
            )
        finally:
            svc.emitter = None

        # Opponent half: a matchmade past checkpoint, no harvesting
        # (its visit policies would train the live net toward an old
        # net's search).
        opponent = self.matchmaker.sample_opponent()
        svc.reload_weights(self._member_variables(opponent))
        opp_scores, _, _ = play_service(
            svc, league.GAMES_PER_ROUND, league.MAX_GAME_MOVES, seed + 1
        )

        win_fraction = pairwise_win_fraction(live_scores, opp_scores)
        self.pool.record_result(LIVE_ID, opponent, win_fraction)
        promoted = self._maybe_promote()

        # Harvest → staleness guard → replay ring.
        harvest = self.emitter.drain()
        harvest, dropped = apply_staleness_guard(
            harvest, svc.weight_reloads, league.STALENESS_WINDOW
        )
        self.stale_dropped_total += dropped
        buffer_before = len(self.c.buffer)
        added = self._fold_result(harvest) if harvest is not None else 0
        self.league_rounds += 1
        self.league_moves_ingested += added
        self.c.stats.log_scalar(
            "Stats/stale_dropped", self.stale_dropped_total, self.global_step
        )
        self._ledger_league(
            opponent=opponent,
            win_fraction=win_fraction,
            promoted=promoted,
            added=added,
            dropped=dropped,
            harvest=harvest,
            buffer_before=buffer_before,
            dt=max(1e-9, time.monotonic() - t0),
        )
        logger.info(
            "League round %d: live %.2f vs %s (elo %.1f vs %.1f), "
            "%d rows ingested%s.",
            self.league_rounds,
            win_fraction,
            opponent,
            self.pool.rating(LIVE_ID),
            self.pool.rating(opponent),
            added,
            f", PROMOTED {promoted}" if promoted else "",
        )
        return added

    def _maybe_promote(self) -> "str | None":
        """Checkpoint + pool-seat the live net when its matchmade
        win-rate clears the gate (cheap pre-check before forcing the
        checkpoint save the pool seat points at)."""
        league = self.league
        rate = self.pool.win_rate(LIVE_ID)
        if (
            self.pool.games.get(LIVE_ID, 0) < league.PROMOTION_MIN_GAMES
            or rate is None
            or rate < league.PROMOTION_WIN_RATE
        ):
            return None
        step = self.global_step
        self._maybe_checkpoint(force=True)
        self.c.checkpoints.wait_until_finished()
        checkpoint = (
            self.c.persistence_config.get_checkpoint_dir().resolve()
            / f"step_{step:08d}"
        )
        return self.pool.maybe_promote(
            str(checkpoint),
            step,
            league.PROMOTION_MIN_GAMES,
            league.PROMOTION_WIN_RATE,
        )

    def _ledger_league(
        self,
        opponent: str,
        win_fraction: float,
        promoted: "str | None",
        added: int,
        dropped: int,
        harvest,
        buffer_before: int,
        dt: float,
    ) -> None:
        """One `kind:"league"` metrics-ledger record per round — the
        pool/ingest/staleness summary `cli perf` folds."""
        ledger = getattr(self.telemetry, "ledger", None)
        if ledger is None:
            return
        clock = self.service.weight_reloads
        versions = (
            harvest.context.get("row_versions", []) if harvest else []
        )
        mean_staleness = (
            round(clock - sum(versions) / len(versions), 3)
            if versions
            else None
        )
        ledger.append(
            {
                "kind": "league",
                "time": time.time(),
                "step": self.global_step,
                "round": self.league_rounds,
                "pool_size": len(self.pool),
                "opponent": opponent,
                "opponent_mix": self.matchmaker.opponent_mix(),
                "win_fraction": round(float(win_fraction), 4),
                "live_elo": round(self.pool.rating(LIVE_ID), 3),
                "promoted": promoted,
                "promotions": self.pool.promotions,
                "moves_ingested": added,
                "ingested_moves_per_sec": round(added / dt, 2),
                "stale_dropped": dropped,
                "stale_dropped_total": self.stale_dropped_total,
                "mean_staleness": mean_staleness,
                "weight_reloads": clock,
                "buffer_size_before": buffer_before,
                "buffer_size_after": len(self.c.buffer),
            }
        )

    # --- the mixed loop ---------------------------------------------------

    def _run_sync(self) -> None:
        cfg = self.cfg
        iteration = 0
        while not self.stop_event.is_set():
            if self._max_steps_reached():
                logger.info(
                    "Reached MAX_TRAINING_STEPS=%d.", cfg.MAX_TRAINING_STEPS
                )
                break
            self.profile.on_iteration(iteration)
            iteration += 1
            # Fractional mix accumulator: RATIO=0.25 plays a league
            # round every 4th iteration, RATIO=1.0 every iteration.
            self._mix_acc += self.league.LEAGUE_MIX_RATIO
            if self._mix_acc >= 1.0 and len(self.pool) > 0:
                self._mix_acc -= 1.0
                with self.profile.phase("league"):
                    added = self._league_round()
            else:
                with self.profile.phase("rollout"):
                    added = self._process_rollout()
            n_steps = cfg.LEARNER_STEPS_PER_ROLLOUT or max(
                1, round(added / cfg.BATCH_SIZE)
            )
            self._run_training_steps(n_steps)
            self._iteration_tail()


def seed_pool_from_run(
    pool: LeaguePool, persistence_config, run_name: str
) -> int:
    """Seed the pool with every checkpoint of an existing run. Member
    ids are namespaced `<run>:step_<n>` so live promotions (which mint
    bare `step_<n>`) never collide with seeds. Returns members added."""
    from ..stats.persistence import CheckpointManager

    src = persistence_config.model_copy(update={"RUN_NAME": run_name})
    mgr = CheckpointManager(src)
    before = len(pool)
    ckpt_dir = src.get_checkpoint_dir().resolve()
    for step in mgr.list_steps():
        pool.add_member(
            f"{run_name}:step_{step:08d}",
            str(ckpt_dir / f"step_{step:08d}"),
            step,
        )
    mgr.close()
    return len(pool) - before


def run_flywheel(
    train_config=None,
    league_config=None,
    env_config=None,
    model_config=None,
    mcts_config=None,
    mesh_config=None,
    persistence_config=None,
    telemetry_config=None,
    pool_from: "str | None" = None,
    log_level: str = "INFO",
    use_tensorboard: bool = True,
) -> int:
    """Run a flywheel session (`cli league`); returns an exit code.

    Mirrors `run_training`'s setup/restore/teardown exactly — a
    flywheel run's checkpoints resume under plain `cli train` — plus:
    the league pool (seeded from `pool_from`'s checkpoints when given),
    a `PolicyService` over its own serve net, and the emitter wiring.
    """
    from ..config.league_config import LeagueConfig
    from ..config.persistence_config import PersistenceConfig
    from ..config.train_config import TrainConfig
    from ..logging_config import setup_logging
    from ..training.runner import EXIT_CODES, _resolve_auto_resume
    from ..training.setup import setup_training_components
    from ..utils.helpers import (
        enable_persistent_compilation_cache,
        enforce_platform,
    )

    setup_logging(log_level)
    train_config = train_config or TrainConfig()
    league_config = league_config or LeagueConfig()
    if train_config.FUSED_MEGASTEP or train_config.ASYNC_ROLLOUTS:
        logger.error(
            "Flywheel mode composes with the synchronous loop only; "
            "disable FUSED_MEGASTEP/ASYNC_ROLLOUTS."
        )
        return 1
    enforce_platform(train_config.DEVICE)
    if train_config.DEVICE_REPLAY == "on" or train_config.FUSED_MEGASTEP:
        # Same latched-flag rule as run_training: forced device replay
        # on the CPU backend needs async dispatch off BEFORE any
        # backend touch (rl/device_buffer.py module docstring).
        import jax

        jax.config.update("jax_cpu_enable_async_dispatch", False)
    persistence_config = persistence_config or PersistenceConfig(
        RUN_NAME=train_config.RUN_NAME
    )
    train_config, persistence_config = _resolve_auto_resume(
        train_config, persistence_config
    )
    import jax

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
    c = components

    # League pool: crash-safe league.jsonl beside the run's metrics
    # ledger; replay restores ratings across restarts.
    run_dir = c.persistence_config.get_run_base_dir()
    pool = LeaguePool(
        run_dir / LEAGUE_FILENAME, elo_k=league_config.ELO_K
    )
    if pool_from:
        added = seed_pool_from_run(pool, c.persistence_config, pool_from)
        logger.info(
            "League pool: seeded %d member(s) from run '%s' (%d total).",
            added,
            pool_from,
            len(pool),
        )
    if len(pool) == 0:
        logger.error(
            "League pool is empty: pass --pool-from a run with "
            "checkpoints (matchmaking needs at least one opponent)."
        )
        c.stats.close()
        c.checkpoints.close()
        return 1
    matchmaker = Matchmaker(
        pool,
        temperature=league_config.MATCH_TEMPERATURE,
        exploration_floor=league_config.EXPLORATION_FLOOR,
        seed=train_config.RANDOM_SEED,
    )

    # The league service: its OWN net (weights swap every half-round;
    # sharing c.net would corrupt concurrent self-play), the learner's
    # env/extractor/search config, telemetry=None (the training loop
    # owns the util-tick clock) but the run's flight recorder so league
    # dispatches seal `serve/b<B>` records for cli doctor/watch.
    from ..mcts import BatchedMCTS
    from ..nn.network import NeuralNetwork
    from ..serving import PolicyService

    serve_net = NeuralNetwork(
        c.model_config, c.env_config, seed=train_config.RANDOM_SEED + 7
    )
    serve_mcts = BatchedMCTS(
        c.env, c.extractor, serve_net.model, c.mcts_config, serve_net.support
    )
    service = PolicyService(
        c.env,
        c.extractor,
        serve_net,
        serve_mcts,
        slots=league_config.LEAGUE_SLOTS,
        telemetry=None,
        rng_seed=train_config.RANDOM_SEED + 11,
    )
    service.flight = getattr(c.telemetry, "flight", None)
    emitter = TrajectoryEmitter(
        c.env, c.extractor, use_gumbel=False, gamma=train_config.GAMMA
    )

    loop = FlywheelLoop(
        components, league_config, service, emitter, pool, matchmaker
    )
    try:
        if train_config.LOAD_CHECKPOINT_PATH:
            loaded = c.checkpoints.restore_path(
                train_config.LOAD_CHECKPOINT_PATH, c.trainer.state
            )
        else:
            loaded = c.checkpoints.restore(c.trainer.state, buffer=c.buffer)
        if loaded.train_state is not None:
            c.trainer.set_state(loaded.train_state)
            c.trainer.sync_to_network()
            loop.set_initial_state(
                loaded.global_step,
                int(loaded.counters.get("episodes_played", 0)),
                int(loaded.counters.get("total_simulations", 0)),
            )
            loop.weight_updates = int(
                loaded.counters.get("weight_updates", 0)
            )
            logger.info(
                "Flywheel resumed at step %d (pool %d, live elo %.1f).",
                loaded.global_step,
                len(pool),
                pool.rating(LIVE_ID),
            )
    except Exception:
        logger.exception(
            "State restore failed for run '%s'; aborting rather than "
            "writing a fresh model into its run directory.",
            train_config.RUN_NAME,
        )
        return 1

    status = loop.run()
    c.stats.close()
    c.checkpoints.close()
    logger.info(
        "Flywheel finished: %s (%d league rounds, %d moves ingested, "
        "%d promotion(s), pool %d).",
        status.value,
        loop.league_rounds,
        loop.league_moves_ingested,
        pool.promotions,
        len(pool),
    )
    return EXIT_CODES[status]
