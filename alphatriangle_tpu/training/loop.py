"""The training loop (reference `training/loop.py:23-416`).

Three orchestration modes over device-batched self-play:

- **Synchronous** (default): each iteration plays a rollout chunk
  (`ROLLOUT_CHUNK_MOVES` moves of all `SELF_PLAY_BATCH_SIZE` games),
  folds the harvest into the replay buffer, then runs learner steps —
  auto-matched to the production rate unless
  `LEARNER_STEPS_PER_ROLLOUT` pins it.
- **Overlapped** (`ASYNC_ROLLOUTS=True`): a producer thread plays
  chunks into a bounded queue while the main thread folds harvests and
  runs learner steps gated by an explicit `REPLAY_RATIO` — the
  reference's async producer/consumer topology
  (`training/loop.py:298-416`, `worker_manager.py:106-167`)
  re-expressed for one process; queue depth and achieved replay ratio
  are exported as gauges.
- **Fused megastep** (`FUSED_MEGASTEP=True`, rl/megastep.py): rollout
  chunk + device-ring ingest + on-device PER sampling + K fused
  learner steps as ONE device program per iteration (Anakin,
  arXiv:2104.06272) — one dispatch, one stats fetch, zero-staleness
  weights. The dispatches-per-iteration gauge (telemetry/perf.py)
  makes the difference visible across all three modes.

Cadences are parity knobs:
weight sync every `WORKER_UPDATE_FREQ_STEPS` learner steps
(`loop.py:271-287`), checkpoint every `CHECKPOINT_SAVE_FREQ_STEPS`
(`loop.py:333-339`), buffer spill every `BUFFER_SAVE_FREQ_STEPS`
(`loop.py:341-349`), metric tick per iteration (`loop.py:390-391`).
"""

import logging
import os
import queue
import threading
import time
from collections import deque
from enum import Enum

import jax
import numpy as np

from ..profiling import ProfileSession
from ..stats.events import RawMetricEvent
from ..telemetry import RunTelemetry
from ..utils.helpers import format_eta
from .components import TrainingComponents

logger = logging.getLogger(__name__)


class LoopStatus(str, Enum):
    COMPLETED = "completed"
    STOPPED = "stopped"
    ERROR = "error"
    # SIGTERM absorbed: emergency checkpoint + buffer spill + telemetry
    # flush all ran; the runner exits PREEMPT_EXIT_CODE (114) so a
    # supervisor distinguishes a survivable preemption from a crash.
    PREEMPTED = "preempted"


class TrainingLoop:
    """Drives produce -> buffer -> train -> sync -> persist."""

    def __init__(self, components: TrainingComponents):
        self.c = components
        self.cfg = components.train_config
        self.stop_event = threading.Event()
        self._preempt_requested = False
        # Device-resident replay (rl/device_buffer.py): rollout payloads
        # stay on device and training batches are gathered there; the
        # loop moves only indices, counts and metrics over the link.
        self._device_replay = bool(
            getattr(components.buffer, "is_device", False)
        )

        self.global_step = 0
        self.episodes_played = 0
        self.total_simulations = 0
        # Root visits inherited through MCTS subtree reuse (0 unless
        # MCTSConfig.tree_reuse): feeds the leaf-evals/s gauge.
        self.total_reused_visits = 0
        self.weight_updates = 0
        self.experiences_added = 0  # this run (resume-independent)
        self._steps_this_run = 0
        self._producer_error: BaseException | None = None
        # Producer supervision (overlapped mode): crashed streams
        # report here and the consumer respawns them with backoff —
        # bounded retries, then the run aborts with the original error
        # (the reference only removes dead actors and degrades,
        # `worker_manager.py:153-159`; SURVEY §7.9 asked for restart).
        self._producer_failures: "queue.Queue" = queue.Queue()
        self._streams: dict[int, dict] = {}
        self.producer_restarts = 0
        # Pipelined learner (overlapped mode): fused groups dispatched
        # but not yet fetched, oldest first. Each entry is
        # (trainer handle, samples list).
        self._inflight: deque = deque()
        # Fused-megastep bookkeeping: the runner (setup-built or lazily
        # created), steady-state iteration count (one device dispatch
        # each — the counter the megastep tests assert on), and the
        # loop-wide iteration counter feeding the dispatches-per-
        # iteration gauge in every mode.
        self._megastep_runner = components.megastep
        self.megastep_iterations = 0
        self.iterations = 0
        # Async chunk auto-tune: producers publish one shared tuned
        # move count (first accurate measurement wins).
        self._tune_lock = threading.Lock()
        self._tuned_chunk_moves: int | None = None
        self._last_saved_step: int | None = None
        self._last_buffer_saved_step: int | None = None
        self._cadence_anchor = 0  # resume step; cadence baseline
        self._last_progress_time = time.monotonic()
        self._last_progress_step = 0
        # Telemetry (span tracer + heartbeat + watchdog + anomaly
        # screening) always runs unless configured off; manually
        # assembled components get a default instance.
        self.telemetry = components.telemetry or RunTelemetry(
            components.telemetry_config,
            run_dir=components.persistence_config.get_run_base_dir(),
            stats=components.stats,
            run_name=components.persistence_config.RUN_NAME,
        )
        components.telemetry = self.telemetry
        # Manually assembled components (tests, the benchmark's drivers) skip
        # training/setup.py's flight attach; wire the recorder here so
        # every construction path records dispatches.
        for c in (components.self_play, components.trainer):
            if c is not None and getattr(c, "flight", None) is None:
                c.flight = self.telemetry.flight
        # Per-phase timers always run (ns-level overhead); the device
        # trace + metric export + json dump activate under --profile
        # (reference `worker.py:99-104`, TrainConfig.PROFILE_WORKERS).
        # The attached tracer records each phase occurrence as a span.
        self.profile = ProfileSession(
            enabled=self.cfg.PROFILE_WORKERS,
            profile_dir=components.persistence_config.get_profile_dir(),
            tracer=self.telemetry.tracer,
        )
        if self.cfg.FUSED_LEARNER_STEPS > self.cfg.WORKER_UPDATE_FREQ_STEPS:
            logger.warning(
                "FUSED_LEARNER_STEPS=%d > WORKER_UPDATE_FREQ_STEPS=%d: "
                "weights can only sync at group boundaries, so the "
                "effective sync cadence is the group size.",
                self.cfg.FUSED_LEARNER_STEPS,
                self.cfg.WORKER_UPDATE_FREQ_STEPS,
            )

    # --- preemption -------------------------------------------------------

    def request_preempt(self) -> None:
        """Ask the loop to stop for a preemption (SIGTERM): every mode
        checks `stop_event` per beat, so the loop falls through to the
        `run()` finally — emergency checkpoint, buffer spill, ledger/
        flight flush — then reports PREEMPTED instead of COMPLETED.
        Signal-handler safe (a bool + Event.set, no locks)."""
        self._preempt_requested = True
        self.stop_event.set()

    def _write_preempt_report(self) -> None:
        """Atomic preempt_report.json: the evidence `cli doctor` and
        the supervisor classify a 114 exit on. Written AFTER the
        emergency checkpoint so `checkpointed_step` is the step a
        restart actually resumes from."""
        from ..telemetry.flight import (
            PREEMPT_EXIT_CODE,
            PREEMPT_REPORT_FILENAME,
            write_preempt_report,
        )

        run_dir = self.c.persistence_config.get_run_base_dir()
        write_preempt_report(
            run_dir / PREEMPT_REPORT_FILENAME,
            {
                "kind": "preempt",
                "time": time.time(),
                "pid": os.getpid(),
                "step": self.global_step,
                "checkpointed_step": self._last_saved_step,
                "exit_code": PREEMPT_EXIT_CODE,
            },
        )

    # --- resume -----------------------------------------------------------

    def set_initial_state(
        self, global_step: int, episodes_played: int, total_simulations: int
    ) -> None:
        """Install resumed counters (reference `loop.py:72-86`)."""
        self.global_step = global_step
        self.episodes_played = episodes_played
        self.total_simulations = total_simulations
        self._last_progress_step = global_step
        self._cadence_anchor = global_step

    # --- iteration pieces -------------------------------------------------

    def _play_rollout(self, engine, moves: int) -> tuple:
        """One rollout chunk on `engine`: (stats result, device payload
        or None) — the device-replay branch expressed once."""
        if self._device_replay:
            return engine.play_moves_device(moves)
        return engine.play_moves(moves), None

    def _process_rollout(self) -> int:
        """One rollout chunk -> buffer. Returns experiences added."""
        result, payload = self._play_rollout(
            self.c.self_play, self.cfg.ROLLOUT_CHUNK_MOVES
        )
        return self._fold_result(result, payload=payload)

    def _fold_result(self, result, trace=None, payload=None, added=None) -> int:
        """Fold one self-play harvest into the buffer + metrics.

        `trace` is the producing engine's per-chunk diagnostics; when
        None (sync mode, single producer) the primary engine's
        `last_trace` is read directly. `payload` is the device-resident
        experience block in device-replay mode (scattered into the
        on-device ring; `result` then carries stats only). `added`
        short-circuits the buffer write entirely — megastep mode, where
        the rows were already scattered in-program and only the count
        came back.
        """
        c = self.c
        if added is not None:
            pass  # rows landed in the device ring inside the megastep
        elif payload is not None:
            added = c.buffer.ingest_payload(payload)
        else:
            c.buffer.add_dense(
                result.grid,
                result.other_features,
                result.policy_target,
                result.value_target,
                policy_weight=result.policy_weight,
            )
            added = result.num_experiences
        self.episodes_played += result.num_episodes
        self.total_simulations += result.total_simulations
        self.total_reused_visits += result.total_reused_visits
        step = self.global_step
        events = [
            RawMetricEvent(
                name="Buffer/Size", value=len(c.buffer), global_step=step
            ),
            RawMetricEvent(
                name="SelfPlay/Experiences_Per_Chunk",
                value=added,
                global_step=step,
            ),
        ]
        if result.num_episodes:
            events += [
                RawMetricEvent(
                    name="SelfPlay/Episode_Score",
                    value=float(np.mean(result.episode_scores)),
                    global_step=step,
                ),
                RawMetricEvent(
                    name="SelfPlay/Episode_Length",
                    value=float(np.mean(result.episode_lengths)),
                    global_step=step,
                ),
                RawMetricEvent(
                    name="Progress/Episodes_Played",
                    value=self.episodes_played,
                    global_step=step,
                ),
                RawMetricEvent(
                    name="SelfPlay/Truncated_Fraction",
                    value=result.num_truncated / result.num_episodes,
                    global_step=step,
                ),
                RawMetricEvent(
                    name="SelfPlay/Staleness_Steps",
                    value=(
                        self._version_clock()
                        - float(np.mean(result.episode_start_versions))
                        if result.episode_start_versions
                        else self._version_clock()
                        - result.trainer_step_at_episode_start
                    ),
                    global_step=step,
                ),
            ]
        if trace is None:
            trace = getattr(c.self_play, "last_trace", None)
        if trace is not None and "wasted_slots" in trace:
            # Per-move diagnostics, chunk-aggregated (the reference's
            # per-move mcts_step/step_reward events, `worker.py:141-164`,
            # at per-chunk granularity). Wasted slots per
            # docs/MCTS_DESIGN.md §c.
            events += [
                RawMetricEvent(
                    name="SelfPlay/Wasted_Slot_Fraction",
                    # Normalize per move by the sims that actually ran
                    # (varies per move under playout cap randomization).
                    value=float(
                        np.mean(
                            trace["wasted_slots"]
                            / np.maximum(
                                np.asarray(trace["sims"])[:, None], 1
                            )
                        )
                    ),
                    global_step=step,
                ),
                RawMetricEvent(
                    name="SelfPlay/Step_Reward",
                    value=float(np.mean(trace["reward"])),
                    global_step=step,
                ),
                RawMetricEvent(
                    name="SelfPlay/Root_Value",
                    value=float(np.mean(trace["root_value"])),
                    global_step=step,
                ),
            ]
            if c.self_play.mcts_fast is not None:
                # Playout-cap randomization: achieved full-search rate
                # this chunk (target = MCTSConfig.full_search_prob).
                # Gated on PCR being enabled — without it the fraction
                # is a constant 1.0 and only pollutes dashboards.
                events.append(
                    RawMetricEvent(
                        name="SelfPlay/Full_Search_Fraction",
                        value=float(np.mean(trace["is_full"])),
                        global_step=step,
                    )
                )
        c.stats.log_batch_events(events)
        self.experiences_added += added
        self.telemetry.on_rollout(added, result.num_episodes)
        return added

    def _version_clock(self) -> int:
        """The weights-version clock staleness is measured against:
        the eval wrapper's sync version normally, the learner step in
        megastep mode (episodes there are tagged with the live step —
        zero-staleness by construction, and `net.weights_version` only
        advances at the unrelated sync cadence)."""
        if self.cfg.FUSED_MEGASTEP:
            return self.c.trainer.global_step
        return self.c.net.weights_version

    def _record_step(self, metrics: dict, td_errors, indices, step: int) -> None:
        """Per-learner-step bookkeeping: priorities, counters, events.

        `step` is the learner step this result belongs to — within a
        fused group the trainer's counter is already at the group end,
        so events must carry their own per-step x-value. `indices` is
        None in megastep mode: the runner already reconciled the host
        PER mirror from the device program's sampled slots.
        """
        c = self.c
        if indices is not None:
            c.buffer.update_priorities(indices, td_errors)
        self.global_step = step
        self._steps_this_run += 1
        events = [
            RawMetricEvent(
                name=f"Loss/{key}", value=val, global_step=step
            )
            for key, val in metrics.items()
            if key.endswith("loss")
        ]
        events += [
            RawMetricEvent(
                name="LearningRate",
                value=metrics["learning_rate"],
                global_step=step,
            ),
            RawMetricEvent(
                name="Loss/Entropy", value=metrics["entropy"], global_step=step
            ),
            RawMetricEvent(
                name="Loss/Grad_Norm",
                value=metrics["grad_norm"],
                global_step=step,
            ),
        ]
        if self.cfg.USE_PER:
            events.append(
                RawMetricEvent(
                    name="PER/Beta",
                    value=c.buffer.beta(step),
                    global_step=step,
                )
            )
        c.stats.log_batch_events(events)
        # Liveness beat + streaming anomaly screen (loss spikes,
        # grad-norm explosions, non-finite values, entropy collapse)
        # over this step's metrics, under their stats-pipeline names.
        self.telemetry.on_learner_step(
            step,
            {
                **{
                    f"Loss/{key}": val
                    for key, val in metrics.items()
                    if key.endswith("loss")
                },
                "Loss/Grad_Norm": metrics["grad_norm"],
                "Loss/Entropy": metrics["entropy"],
            },
        )
        if os.environ.get("ALPHATRIANGLE_FAULTS"):
            # Chaos-harness hook (supervise/faults.py): step-indexed
            # faults (sigterm/sigkill/crash at step N) fire here, after
            # the step's bookkeeping is complete.
            from ..supervise.faults import fault_point

            fault_point("step", step)

    def _maybe_sync_weights(self, prev_step: int) -> None:
        """Push learner params when (prev_step, global_step] crossed a
        WORKER_UPDATE_FREQ_STEPS multiple (reference `loop.py:271-287`).

        One sync per call regardless of how many multiples the group
        crossed — only the group-end params exist to install, so with
        FUSED_LEARNER_STEPS > WORKER_UPDATE_FREQ_STEPS the effective
        sync cadence is the group size (warned at loop start)."""
        freq = self.cfg.WORKER_UPDATE_FREQ_STEPS
        if self._crossed(self.global_step, freq, prev_step):
            with self.profile.phase("weight_sync"):
                self.c.trainer.sync_to_network()
            self.weight_updates += 1
            self.c.stats.log_scalar(
                "Progress/Weight_Updates_Total",
                self.weight_updates,
                self.global_step,
            )

    def _run_training_step(self) -> bool:
        """One sample -> train -> priority-update -> maybe sync cycle.

        Returns False when the buffer could not produce a batch
        (reference `loop.py:213-296`).
        """
        return self._run_training_steps(1) == 1

    def _learner_budget(self, allowed: int) -> int:
        """Steps the learner may still dispatch: the caller's allowance
        capped by MAX_TRAINING_STEPS, counting steps already inflight
        (inflight is empty outside the pipelined pump)."""
        budget = allowed
        if self.cfg.MAX_TRAINING_STEPS is not None:
            budget = min(
                budget,
                self.cfg.MAX_TRAINING_STEPS
                - self.global_step
                - self._inflight_steps(),
            )
        return budget

    def _sample_group(self, group: int) -> list:
        """Sample up to `group` training batches from the buffer.

        BATCH_SIZE is the GLOBAL batch; in a multi-host run each host
        samples its share from its local buffer and shard_batch
        assembles the global array (trainer returns local TD rows).
        The PER-beta clock is the trainer's dispatch-time step (equal
        to `global_step` whenever nothing is inflight).
        """
        local_batch = max(1, self.cfg.BATCH_SIZE // jax.process_count())
        with self.profile.phase("sample"):
            samples = []
            for _ in range(group):
                s = self.c.buffer.sample(
                    local_batch,
                    current_train_step=self.c.trainer.global_step,
                )
                if s is None:
                    break
                samples.append(s)
        return samples

    def _run_training_steps(self, max_steps: int) -> int:
        """Up to `max_steps` learner steps, dispatched in fused groups
        of `FUSED_LEARNER_STEPS`. Returns the number of steps run.

        Within a group, PER priorities update after the group's single
        dispatch (staleness bounded by the group size); sampling,
        checkpoint and weight-sync cadences run at group boundaries.
        """
        c = self.c
        k = max(1, self.cfg.FUSED_LEARNER_STEPS)
        ran = 0
        while ran < max_steps and not self.stop_event.is_set():
            budget = self._learner_budget(max_steps - ran)
            if budget <= 0:
                break
            group = min(k, budget)
            samples = self._sample_group(group)
            if not samples:
                break
            prev_step = self.global_step
            with self.profile.phase("train"):
                if self._device_replay:
                    if len(samples) == k and k > 1:
                        outs = c.trainer.train_steps_from(c.buffer, samples)
                    else:
                        # Tail groups ride K=1 programs one at a time
                        # (a fused program per distinct K would
                        # recompile), matching the host-path guard.
                        outs = []
                        for s in samples:
                            outs.extend(
                                c.trainer.train_steps_from(c.buffer, [s])
                            )
                elif len(samples) == k and k > 1:
                    outs = c.trainer.train_steps(
                        [s["batch"] for s in samples]
                    )
                else:
                    # Tail / short groups run as single steps: the
                    # per-step program is already compiled, while a
                    # fused program per distinct K would recompile.
                    outs = []
                    for s in samples:
                        out = c.trainer.train_step(s["batch"])
                        if out is None:
                            break
                        outs.append(out)
            if not outs:
                break
            for i, (s, (metrics, td_errors)) in enumerate(
                zip(samples, outs)
            ):
                self._record_step(
                    metrics, td_errors, s["indices"], prev_step + i + 1
                )
            ran += len(outs)
            self._maybe_sync_weights(prev_step)
            with self.profile.phase("checkpoint"):
                self._maybe_checkpoint()
            if len(outs) < group:
                break
        return ran

    def _crossed(self, step: int, freq: int, last: int | None) -> bool:
        """Did `step` cross a `freq` multiple since `last`? (Cadence
        check robust to steps advancing by more than 1 per call, as
        fused learner groups do.)"""
        anchor = last if last is not None else self._cadence_anchor
        return step > 0 and step // freq > anchor // freq

    def _ckpt_save_due(self, force: bool = False) -> bool:
        return force or self._crossed(
            self.global_step,
            self.cfg.CHECKPOINT_SAVE_FREQ_STEPS,
            self._last_saved_step,
        )

    def _buffer_save_due(self, force: bool = False) -> bool:
        return self.c.persistence_config.SAVE_BUFFER and (
            force
            or self._crossed(
                self.global_step,
                self.c.persistence_config.BUFFER_SAVE_FREQ_STEPS,
                self._last_buffer_saved_step,
            )
        )

    def _checkpoint_due(self) -> bool:
        """Either save cadence pending? The pipelined pump drains the
        inflight groups before `_maybe_checkpoint` whenever this is
        True; both sides call the same per-cadence predicates, so the
        drain decision and the save decision cannot drift apart."""
        return self._ckpt_save_due() or self._buffer_save_due()

    def _maybe_checkpoint(self, force: bool = False) -> None:
        c = self.c
        step = self.global_step
        due = self._ckpt_save_due(force)
        if due and self._last_saved_step != step:
            self._last_saved_step = step
            c.checkpoints.save(
                step,
                c.trainer.state,
                counters={
                    "episodes_played": self.episodes_played,
                    "total_simulations": self.total_simulations,
                    "weight_updates": self.weight_updates,
                },
            )
        save_buffer = self._buffer_save_due(force)
        # On force, always spill: late harvests may have been folded
        # into the buffer after a cadence save at this same step (the
        # async shutdown path does exactly that).
        if save_buffer and (force or self._last_buffer_saved_step != step):
            self._last_buffer_saved_step = step
            c.checkpoints.save_buffer(step, c.buffer)

    def _log_progress(self) -> None:
        now = time.monotonic()
        elapsed = now - self._last_progress_time
        if elapsed < 10.0:
            return
        steps = self.global_step - self._last_progress_step
        rate = steps / elapsed if elapsed > 0 else 0.0
        max_steps = self.cfg.MAX_TRAINING_STEPS
        eta = (
            format_eta((max_steps - self.global_step) / rate)
            if rate > 0 and max_steps
            else "?"
        )
        logger.info(
            "step %d/%s | %.2f steps/s | buffer %d | episodes %d | ETA %s",
            self.global_step,
            max_steps,
            rate,
            len(self.c.buffer),
            self.episodes_played,
            eta,
        )
        self._last_progress_time = now
        self._last_progress_step = self.global_step

    # --- main loop --------------------------------------------------------

    def _max_steps_reached(self) -> bool:
        max_steps = self.cfg.MAX_TRAINING_STEPS
        return max_steps is not None and self.global_step >= max_steps

    def run(self) -> LoopStatus:
        """Run until MAX_TRAINING_STEPS / stop / error
        (reference `loop.py:298-416`)."""
        status = LoopStatus.COMPLETED
        self.telemetry.start()
        try:
            if self.cfg.FUSED_MEGASTEP:
                self._run_megastep_mode()
            elif self.cfg.ASYNC_ROLLOUTS:
                self._run_async()
            else:
                self._run_sync()
        except KeyboardInterrupt:
            logger.warning("Interrupted; saving final state.")
            status = LoopStatus.STOPPED
        except Exception:
            logger.exception("Training loop error.")
            status = LoopStatus.ERROR
        finally:
            self.stop_event.set()
            try:
                self.profile.close()
                self._maybe_checkpoint(force=True)
                self.c.checkpoints.wait_until_finished()
                self.c.stats.force_process_and_log(self.global_step)
            except Exception:
                logger.exception("Final save failed.")
                status = LoopStatus.ERROR
            if self._preempt_requested:
                if status is not LoopStatus.ERROR:
                    status = LoopStatus.PREEMPTED
                self._write_preempt_report()
                logger.warning(
                    "Preempted at step %d (emergency checkpoint at "
                    "step %s); exiting for restart.",
                    self.global_step,
                    self._last_saved_step,
                )
            # Last: the final heartbeat + span-trace export cover the
            # shutdown work above too.
            try:
                self.telemetry.close(self.global_step)
            except Exception:
                logger.exception("Telemetry shutdown failed.")
        return status

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
            with self.profile.phase("rollout"):
                added = self._process_rollout()
            n_steps = cfg.LEARNER_STEPS_PER_ROLLOUT or max(
                1, round(added / cfg.BATCH_SIZE)
            )
            self._run_training_steps(n_steps)
            self._iteration_tail()

    # --- fused megastep (Anakin) ------------------------------------------

    def _megastep_ready(self, need: int) -> bool:
        """Warmup exit test: the ring can produce a training batch.

        Sharded ring: EVERY shard must additionally cover its B/dp
        stratum — the fused program samples per shard from device-local
        priorities, so one under-filled shard would sample garbage rows
        even when the global fill clears the threshold. (Warmup ingests
        stripe each device's own lanes into its own shard, so shards
        fill together; this is a correctness gate, not a throttle.)
        """
        buf = self.c.buffer
        if len(buf) < need:
            return False
        if getattr(buf, "is_sharded", False):
            b_local = self.cfg.BATCH_SIZE // buf.dp
            return int(buf._sizes.min()) >= b_local
        return True

    def _run_megastep_mode(self) -> None:
        """One device program per iteration: rollout chunk + ring
        ingest + on-device sampling + K learner steps (rl/megastep.py).

        Warm-up is host-orchestrated (rollout + ingest, no training)
        until the ring can produce a batch — the megastep program
        always trains, so dispatching it against a not-ready ring would
        sample garbage rows. From then on, ONE dispatch and ONE stats
        fetch per iteration; `megastep_iterations` vs the runner's
        `dispatch_count` is the counter the tests assert equal.
        """
        cfg = self.cfg
        runner = self._megastep_runner
        if runner is None:
            from ..rl.megastep import MegastepRunner

            runner = MegastepRunner(
                self.c.self_play, self.c.trainer, self.c.buffer, cfg
            )
            runner.flight = getattr(self.telemetry, "flight", None)
            self.c.megastep = self._megastep_runner = runner
        need = max(cfg.MIN_BUFFER_SIZE_TO_TRAIN, cfg.BATCH_SIZE)
        iteration = 0
        while not self.stop_event.is_set() and not self._megastep_ready(
            need
        ):
            self.profile.on_iteration(iteration)
            iteration += 1
            with self.profile.phase("rollout"):
                self._process_rollout()
            self._iteration_tail()
        # Device priorities pick up everything the warmup (and any
        # checkpoint restore before it) wrote into the host mirror.
        runner.sync_priorities_from_host()
        k_cfg = cfg.LEARNER_STEPS_PER_ROLLOUT or max(
            1, cfg.FUSED_LEARNER_STEPS
        )
        while not self.stop_event.is_set():
            if self._max_steps_reached():
                logger.info(
                    "Reached MAX_TRAINING_STEPS=%d.", cfg.MAX_TRAINING_STEPS
                )
                break
            # Tail groups shrink K to the remaining budget (a per-(T,K)
            # program compiles once, same contract as the fused paths).
            k = self._learner_budget(k_cfg)
            if k <= 0:
                break
            self.profile.on_iteration(iteration)
            iteration += 1
            prev_step = self.global_step
            with self.profile.phase("megastep"):
                outs, added = runner.run_megastep(
                    cfg.ROLLOUT_CHUNK_MOVES, k
                )
            self.megastep_iterations += 1
            self._fold_result(self.c.self_play.harvest(), added=added)
            for i, (metrics, td_errors) in enumerate(outs):
                self._record_step(
                    metrics, td_errors, None, prev_step + i + 1
                )
            self._maybe_sync_weights(prev_step)
            with self.profile.phase("checkpoint"):
                self._maybe_checkpoint()
            self._iteration_tail()

    # --- overlapped producer/consumer ------------------------------------

    def _producer_chunk_moves(self) -> int:
        """Current per-dispatch move count for producers (tuned or
        configured)."""
        with self._tune_lock:
            if self._tuned_chunk_moves is not None:
                return self._tuned_chunk_moves
        return self.cfg.ROLLOUT_CHUNK_MOVES

    def _maybe_tune_chunk(self, moves: int, dt: float, warmed: bool) -> None:
        """Auto-size async rollout dispatches from one clean measurement.

        A single flagship chunk is a multi-second device program; every
        learner dispatch queues behind it (device programs run FIFO),
        so the chunk length directly sets the learner's worst-case
        queue wait. The first post-compile chunk's wall time gives
        seconds/move; producers then dispatch
        `ASYNC_CHUNK_SECONDS / seconds_per_move` moves at a time. The
        measurement may include learner time slices (conservative:
        over-shrinks, never starves). One shared tuned size — streams
        reuse one compiled program.
        """
        target = self.cfg.ASYNC_CHUNK_SECONDS
        if target is None or not warmed:
            return
        with self._tune_lock:
            if self._tuned_chunk_moves is not None:
                return
            per_move = dt / max(moves, 1)
            tuned = max(
                1,
                min(self.cfg.ROLLOUT_CHUNK_MOVES, round(target / per_move)),
            )
            # Build the tuned size's jit wrapper here, inside the lock,
            # so producer threads don't race the engine's program cache
            # with concurrent first misses.
            if tuned != moves:
                self.c.self_play._chunk_fn(tuned)
                logger.info(
                    "Async chunk auto-tune: %.2fs/%d moves measured "
                    "(%.2fs/move) -> %d moves/dispatch for the %.1fs "
                    "target.",
                    dt,
                    moves,
                    per_move,
                    tuned,
                    target,
                )
            self._tuned_chunk_moves = tuned

    def _producer_loop(self, engine, out: "queue.Queue", stream: int = 0) -> None:
        """Self-play producer: play chunks, enqueue (harvest, trace).

        Runs in a daemon thread (one per rollout stream — the
        reference's NUM_SELF_PLAY_WORKERS actors, `setup.py:106-151`,
        become N independent device-batched streams sharing one queue).
        JAX dispatch is thread-safe; device compute serializes with the
        learner's, but the host-side work on all sides (harvest
        compaction here, PER sampling/priority updates there) overlaps
        with it. Weight syncs are picked up at the next chunk via
        `net.variables` (no broadcast; replaces reference
        `worker_manager.py:169-209`).
        """
        try:
            while not self.stop_event.is_set():
                moves = self._producer_chunk_moves()
                # Timed as "rollout" here — in async mode the producers
                # own the self-play device time; the consumer's queue
                # drain is timed separately as "fold". Chunk sizing is
                # settled before producers start (`_run_async`'s
                # uncontended measurement) — a producer-side sample
                # would include the other streams' queued programs.
                with self.profile.phase("rollout"):
                    result, payload = self._play_rollout(engine, moves)
                item = (result, engine.last_trace, payload)
                # Backpressure wait, timed per stream: persistent high
                # wait here means the consumer (fold + learner) is the
                # bottleneck, not self-play.
                with self.profile.phase(f"enqueue_wait/stream{stream}"):
                    while not self.stop_event.is_set():
                        try:
                            out.put(item, timeout=0.2)
                            break
                        except queue.Full:
                            continue
        except BaseException as exc:
            # Report to the supervisor (consumer thread), which
            # respawns the stream with backoff or — retries exhausted —
            # aborts the run with this error. Shutdown-time noise
            # (threads interrupted mid-dispatch by stop_event) is not
            # a crash.
            if not self.stop_event.is_set():
                self._producer_failures.put((stream, exc))

    # --- producer supervision (overlapped mode) ---------------------------

    def _spawn_producer_thread(
        self, engine, harvests: "queue.Queue", stream: int
    ) -> threading.Thread:
        t = threading.Thread(
            target=self._producer_loop,
            args=(engine, harvests, stream),
            name=f"self-play-producer-{stream}",
            daemon=True,
        )
        t.start()
        return t

    def _fresh_stream_engine(self, stream: int, attempt: int):
        """A replacement engine for a crashed stream: fresh carry and
        PRNG stream (the crashed engine's donated buffers may be
        invalidated mid-dispatch), compiled programs shared with the
        primary — rollout programs depend only on configs, so the
        respawn never recompiles."""
        from ..rl.self_play import SelfPlayEngine

        primary = self.c.self_play
        return SelfPlayEngine(
            primary.env,
            primary.extractor,
            primary.net,
            primary.mcts_config,
            primary.config,
            # The primary may carry an explicit batch-size override
            # (engine batch ≠ config SELF_PLAY_BATCH_SIZE); defaulting
            # here would make share_compiled reject every respawn and
            # burn all PRODUCER_MAX_RESTARTS on a config error.
            batch_size=primary.batch_size,
            seed=self.cfg.RANDOM_SEED + 2000 + stream * 100 + attempt,
            share_compiled=primary,
            mesh=primary.mesh,
            data_axes=primary.data_axes,
        )

    def _supervise_producers(self, harvests: "queue.Queue") -> None:
        """Respawn crashed producer streams with exponential backoff;
        abort the run (original exception) once a stream exhausts
        PRODUCER_MAX_RESTARTS."""
        now = time.monotonic()
        while True:
            try:
                stream, exc = self._producer_failures.get_nowait()
            except queue.Empty:
                break
            rec = self._streams[stream]
            if rec["restarts"] >= self.cfg.PRODUCER_MAX_RESTARTS:
                logger.error(
                    "Producer stream %d crashed and exhausted its %d "
                    "restarts; aborting run.",
                    stream,
                    self.cfg.PRODUCER_MAX_RESTARTS,
                )
                self._producer_error = exc
                self.stop_event.set()
                return
            delay = self.cfg.PRODUCER_RESTART_BACKOFF_S * (
                2 ** rec["restarts"]
            )
            rec["restarts"] += 1
            rec["retry_at"] = now + delay
            logger.warning(
                "Producer stream %d crashed (%s: %s); respawning in "
                "%.2fs (restart %d/%d).",
                stream,
                type(exc).__name__,
                exc,
                delay,
                rec["restarts"],
                self.cfg.PRODUCER_MAX_RESTARTS,
            )
        for stream, rec in self._streams.items():
            if rec.get("retry_at") is not None and now >= rec["retry_at"]:
                rec["retry_at"] = None
                rec["engine"] = self._fresh_stream_engine(
                    stream, rec["restarts"]
                )
                rec["thread"] = self._spawn_producer_thread(
                    rec["engine"], harvests, stream
                )
                self.producer_restarts += 1
                self.c.stats.log_scalar(
                    "System/Producer_Restarts",
                    self.producer_restarts,
                    self.global_step,
                )

    def _learner_steps_allowed(self) -> int:
        """Replay-ratio gate: steps the learner may run this instant.

        REPLAY_RATIO = samples consumed per experience produced, i.e.
        allowed steps = produced * ratio / BATCH_SIZE. Counted within
        this run so a resumed `global_step` doesn't starve the gate.
        Dispatched-but-unfetched pipeline groups count as consumed.
        """
        target = (
            self.experiences_added * self.cfg.REPLAY_RATIO / self.cfg.BATCH_SIZE
        )
        return max(
            0, int(target) - self._steps_this_run - self._inflight_steps()
        )

    # --- pipelined learner (overlapped mode) ------------------------------

    def _inflight_steps(self) -> int:
        return sum(handle["k"] for handle, _ in self._inflight)

    def _dispatch_learner_group(self, allowed: int) -> bool:
        """Sample + dispatch ONE fused group without fetching results.

        Returns True when a group went out. The dispatch returns as
        soon as the transfer is enqueued, so the group's device
        execution overlaps the consumer's queue draining and the NEXT
        group's sampling — and, crucially, sits in the device FIFO
        behind at most one producer chunk instead of idling a full
        round trip per group.
        """
        c = self.c
        k = max(1, self.cfg.FUSED_LEARNER_STEPS)
        group = min(k, self._learner_budget(allowed))
        if group <= 0 or self.stop_event.is_set():
            return False
        samples = self._sample_group(group)
        if not samples:
            return False
        with self.profile.phase("dispatch"):
            if self._device_replay:
                if len(samples) == k and k > 1:
                    handle = c.trainer.train_steps_from_begin(
                        c.buffer, samples
                    )
                    groups = [(handle, samples)] if handle is not None else []
                else:
                    groups = []
                    for s in samples:
                        handle = c.trainer.train_steps_from_begin(
                            c.buffer, [s]
                        )
                        if handle is None:
                            break
                        groups.append((handle, [s]))
            elif len(samples) == k and k > 1:
                handle = c.trainer.train_steps_begin(
                    [s["batch"] for s in samples]
                )
                groups = [(handle, samples)] if handle is not None else []
            else:
                # Short groups ride the per-step program one batch per
                # handle: a fused program per distinct group size would
                # recompile (same guard as _run_training_steps).
                groups = []
                for s in samples:
                    handle = c.trainer.train_steps_begin([s["batch"]])
                    if handle is None:
                        break
                    groups.append((handle, [s]))
        if not groups:
            return False
        self._inflight.extend(groups)
        return True

    def _finish_oldest_group(self) -> int:
        """Blocking fetch + bookkeeping for the oldest inflight group.

        Weight sync after a finish installs the trainer's CURRENT state
        — possibly one group fresher than the step label when another
        group is already inflight; fresher-than-labeled is harmless
        (self-play only ever wants the newest weights).
        """
        handle, samples = self._inflight.popleft()
        with self.profile.phase("train"):
            outs = self.c.trainer.train_steps_finish(handle)
        prev_step = self.global_step
        for i, (s, (metrics, td_errors)) in enumerate(zip(samples, outs)):
            self._record_step(
                metrics, td_errors, s["indices"], prev_step + i + 1
            )
        self._maybe_sync_weights(prev_step)
        return len(outs)

    def _drain_learner(self) -> int:
        ran = 0
        while self._inflight:
            ran += self._finish_oldest_group()
        return ran

    def _pump_learner(self, allowed: int) -> int:
        """One pipelined learner beat: dispatch group N+1, then fetch
        group N. Keeps exactly one group executing and one queued in
        steady state; empties naturally when the gate or buffer starves
        the dispatch. Checkpoints drain the pipeline first so the saved
        params and the step label agree exactly.
        """
        dispatched = self._dispatch_learner_group(allowed)
        ran = 0
        while len(self._inflight) >= 2:
            ran += self._finish_oldest_group()
        if self._inflight and not dispatched:
            ran += self._finish_oldest_group()
        if ran and self._checkpoint_due():
            ran += self._drain_learner()
            with self.profile.phase("checkpoint"):
                self._maybe_checkpoint()
        return ran

    def _make_rollout_streams(self) -> list:
        """The primary engine plus NUM_SELF_PLAY_WORKERS-1 extra
        independent streams (own carry + seed, shared net/weights).
        The count is clamped to the host/device budget (reference
        clamps its actors to cores-2, `setup.py:106-151`)."""
        from ..rl.self_play import SelfPlayEngine
        from .setup import clamp_self_play_workers

        primary = self.c.self_play
        streams = [primary]
        for i in range(1, clamp_self_play_workers(self.cfg.NUM_SELF_PLAY_WORKERS)):
            streams.append(
                SelfPlayEngine(
                    primary.env,
                    primary.extractor,
                    primary.net,
                    primary.mcts_config,
                    primary.config,
                    seed=self.cfg.RANDOM_SEED + 1000 + i,
                    share_compiled=primary,
                    mesh=primary.mesh,
                    data_axes=primary.data_axes,
                )
            )
        return streams

    def _run_async(self) -> None:
        cfg = self.cfg
        harvests: "queue.Queue" = queue.Queue(maxsize=cfg.ROLLOUT_QUEUE_MAX)
        # Materialize the shared chunk program's jit wrapper before the
        # producer threads race the lru_cache: concurrent first misses
        # may each build (and compile) their own wrapper.
        self.c.self_play._chunk_fn(cfg.ROLLOUT_CHUNK_MOVES)
        if cfg.ASYNC_CHUNK_SECONDS is not None:
            # Auto-size async dispatches from an UNCONTENDED measurement
            # taken before any producer or learner work exists: with N
            # streams already running, a producer's own chunk wall time
            # includes the other streams' queued programs and would
            # over-shrink the tuned size N-fold. Chunk 1 compiles;
            # chunk 2 times clean seconds/move. Both harvests feed the
            # buffer — nothing is thrown away. The timed window covers
            # the PLAY only (the fold/ingest is deferred past `dt`): the
            # tuned size targets device seconds per move, and folding a
            # chunk is host/ingest work that would inflate it.
            self._process_rollout()
            t0 = time.perf_counter()
            result, payload = self._play_rollout(
                self.c.self_play, cfg.ROLLOUT_CHUNK_MOVES
            )
            dt = time.perf_counter() - t0
            self._fold_result(result, payload=payload)
            self._maybe_tune_chunk(
                cfg.ROLLOUT_CHUNK_MOVES, dt, warmed=True
            )
        self._streams = {
            i: {
                "engine": engine,
                "thread": self._spawn_producer_thread(engine, harvests, i),
                "restarts": 0,
                "retry_at": None,
            }
            for i, engine in enumerate(self._make_rollout_streams())
        }
        iteration = 0
        try:
            while not self.stop_event.is_set():
                if self._max_steps_reached():
                    logger.info(
                        "Reached MAX_TRAINING_STEPS=%d.",
                        cfg.MAX_TRAINING_STEPS,
                    )
                    break
                self.profile.on_iteration(iteration)
                iteration += 1
                self._supervise_producers(harvests)
                # Drain everything available; block briefly only when
                # there is no learner work to do either.
                folded = 0
                with self.profile.phase("fold"):
                    while True:
                        try:
                            self._fold_result(*harvests.get_nowait())
                            folded += 1
                        except queue.Empty:
                            break
                    if (
                        folded == 0
                        and not self.stop_event.is_set()
                        and (
                            self._learner_steps_allowed() == 0
                            or not self.c.buffer.is_ready()
                        )
                    ):
                        try:
                            self._fold_result(*harvests.get(timeout=0.5))
                            folded += 1
                        except queue.Empty:
                            pass
                if self.cfg.PIPELINE_LEARNER:
                    steps_ran = self._pump_learner(
                        self._learner_steps_allowed()
                    )
                else:
                    steps_ran = self._run_training_steps(
                        self._learner_steps_allowed()
                    )
                if folded == 0 and steps_ran == 0:
                    # Gate open but the buffer can't produce a batch yet
                    # (or the trainer rejected one): don't busy-spin.
                    time.sleep(0.05)
                self.c.stats.log_scalar(
                    "System/Rollout_Queue_Depth",
                    harvests.qsize(),
                    self.global_step,
                )
                if self.experiences_added:
                    self.c.stats.log_scalar(
                        "System/Replay_Ratio_Actual",
                        self._steps_this_run
                        * cfg.BATCH_SIZE
                        / self.experiences_added,
                        self.global_step,
                    )
                self._iteration_tail()
        finally:
            self.stop_event.set()
            # Land any dispatched-but-unfetched learner groups so their
            # steps are recorded before the final checkpoint.
            try:
                self._drain_learner()
            except Exception:
                logger.exception("Draining inflight learner groups failed.")
            for rec in self._streams.values():
                rec["thread"].join(timeout=30.0)
                if rec["thread"].is_alive():
                    logger.warning(
                        "%s did not join within 30s.", rec["thread"].name
                    )
            # Fold any harvests still queued so the final checkpoint /
            # buffer spill includes everything that was actually played.
            while True:
                try:
                    self._fold_result(*harvests.get_nowait())
                except queue.Empty:
                    break
            if self._producer_error is not None:
                raise self._producer_error

    def _transfer_seconds(self) -> tuple[float, float]:
        """Cumulative host<->device transfer seconds: (h2d, d2h).

        h2d = the trainer's batch staging uploads; d2h = the trainer's
        result fetches plus every rollout engine's harvest fetches
        (engines are deduped — async streams include the primary)."""
        c = self.c
        h2d = float(getattr(c.trainer, "transfer_h2d_seconds", 0.0))
        d2h = float(getattr(c.trainer, "transfer_d2h_seconds", 0.0))
        engines = {id(c.self_play): c.self_play}
        for rec in self._streams.values():
            engine = rec.get("engine")
            if engine is not None:
                engines[id(engine)] = engine
        d2h += sum(
            float(getattr(e, "transfer_d2h_seconds", 0.0))
            for e in engines.values()
        )
        if self._megastep_runner is not None:
            d2h += float(self._megastep_runner.transfer_d2h_seconds)
        return h2d, d2h

    def _total_dispatches(self) -> int:
        """Cumulative device-program dispatches across every component
        (rollout engines, learner, ring ingest, megastep) — the
        numerator of the dispatches-per-iteration gauge that makes the
        megastep's one-dispatch iteration visible in `cli perf`."""
        c = self.c
        total = int(getattr(c.trainer, "dispatch_count", 0))
        total += int(getattr(c.buffer, "dispatch_count", 0))
        engines = {id(c.self_play): c.self_play}
        for rec in self._streams.values():
            engine = rec.get("engine")
            if engine is not None:
                engines[id(engine)] = engine
        total += sum(
            int(getattr(e, "dispatch_count", 0)) for e in engines.values()
        )
        if self._megastep_runner is not None:
            total += int(self._megastep_runner.dispatch_count)
        return total

    def _drain_device_stats(self) -> "dict | None":
        """The freshest in-program stat-pack fold (device-stats plane,
        telemetry/device_stats.py): the megastep runner's when fused,
        else the self-play engine's. Consumed once — the producer slot
        is cleared so an idle iteration doesn't re-ledger stale stats."""
        sources = []
        if self._megastep_runner is not None:
            sources.append(self._megastep_runner)
        sources.append(self.c.self_play)
        for rec in self._streams.values():
            engine = rec.get("engine")
            if engine is not None and engine is not self.c.self_play:
                sources.append(engine)
        for src in sources:
            ds = getattr(src, "last_device_stats", None)
            if ds:
                src.last_device_stats = None
                return ds
        return None

    def _iteration_tail(self) -> None:
        if self.cfg.PROFILE_WORKERS:
            for name, val in self.profile.timers.metrics().items():
                self.c.stats.log_scalar(name, val, self.global_step)
        # Device-stats record + the gauge mirror for metrics.prom /
        # `cli watch` (None on legacy/off runs — zero new fields then).
        ds = self._drain_device_stats()
        extra = {}
        if ds:
            self.telemetry.record_device_stats(self.global_step, **ds)
            search = ds.get("search") or {}
            if search.get("root_entropy") is not None:
                extra["root_visit_entropy"] = search["root_entropy"]
            if search.get("occupancy") is not None:
                extra["tree_occupancy"] = search["occupancy"]
            from ..telemetry.device_stats import beacons_armed

            extra["beacons_armed"] = int(beacons_armed())
        # Utilization record first (ledger + heartbeat fields), then the
        # heartbeat write (health.json) — before the stats tick so any
        # Anomaly/* or Health/* events logged this iteration flush too.
        h2d, d2h = self._transfer_seconds()
        self.iterations += 1
        # Cumulative sealed-dispatch wall feeds the chip-idle gauge
        # (telemetry/roofline.py); None on legacy/flight-off runs, so
        # those util records carry zero new fields.
        flight = getattr(self.telemetry, "flight", None)
        dispatch_wall = getattr(flight, "sealed_wall_seconds", None)
        self.telemetry.on_util_tick(
            self.global_step,
            episodes=self.episodes_played,
            experiences=self.experiences_added,
            simulations=self.total_simulations,
            reused_visits=self.total_reused_visits,
            buffer_size=len(self.c.buffer),
            transfer_h2d_s=h2d,
            transfer_d2h_s=d2h,
            dispatches=self._total_dispatches(),
            iterations=self.iterations,
            dispatch_wall_s=dispatch_wall,
            extra=extra or None,
        )
        self.telemetry.on_tick(self.global_step, len(self.c.buffer))
        self.c.stats.process_and_log(self.global_step)
        self._log_progress()
