"""Command-line interface (reference `alphatriangle/cli.py:31-326`).

Subcommands mirror the reference's Typer app: `train` (config
overrides -> `run_training`), `tb` (launch TensorBoard on the runs
root), `ml` (MLflow launcher — degrades with a clear message when
MLflow isn't installed, as in this TPU image). The reference's `ray`
command has no equivalent: there is no actor runtime to inspect; the
device story lives in `jax.devices()` (printed by `devices`).

Console script: `alphatriangle-tpu` (pyproject `[project.scripts]`,
reference `pyproject.toml:53-54`).
"""

import argparse
import logging
import subprocess
import sys
from pathlib import Path

logger = logging.getLogger(__name__)


def _add_train_parser(sub: "argparse._SubParsersAction") -> None:
    p = sub.add_parser("train", help="Run a training session.")
    # Reference override surface (`cli.py:40-74`).
    p.add_argument("--run-name", default=None, help="Run directory name.")
    p.add_argument("--seed", type=int, default=None, help="Random seed.")
    p.add_argument(
        "--log-level",
        default="INFO",
        choices=["DEBUG", "INFO", "WARNING", "ERROR"],
    )
    p.add_argument(
        "--profile",
        action="store_true",
        help="Capture a jax.profiler trace + per-phase timers into "
        "runs/<run>/profile_data/.",
    )
    p.add_argument(
        "--preset",
        default=None,
        metavar="N|PATH",
        help="BASELINE benchmark config 1..5 (config/presets.py) OR a "
        "tuned_preset.json path from `cli tune`; explicit flags below "
        "override preset values. Tuned-preset runs ledger a "
        "predicted-vs-observed tune_outcome record on completion.",
    )
    p.add_argument(
        "--dry-setup",
        action="store_true",
        help="Construct every training component (mesh, network, "
        "buffer, loop threads' inputs) from the resolved config, then "
        "exit 0 without training — proves a tuned preset is runnable.",
    )
    # TPU-native sizing knobs.
    p.add_argument("--max-steps", type=int, default=None)
    p.add_argument("--self-play-batch", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--buffer-capacity", type=int, default=None)
    p.add_argument("--min-buffer", type=int, default=None)
    p.add_argument("--rollout-chunk", type=int, default=None)
    p.add_argument(
        "--fused-learner-steps",
        type=int,
        default=None,
        metavar="K",
        help="Learner steps fused into one device dispatch (1 = exact "
        "per-step PER semantics; >1 collapses host round trips).",
    )
    p.add_argument(
        "--async-rollouts",
        action="store_true",
        help="Overlapped mode: self-play producer thread + replay-ratio"
        "-gated learner (see --replay-ratio).",
    )
    p.add_argument(
        "--device-replay",
        choices=["auto", "on", "off"],
        default=None,
        help="Device-resident replay ring (auto = on for single-chip "
        "accelerator runs): rollouts scatter experiences into device "
        "HBM and batches are gathered there from sampled indices.",
    )
    p.add_argument(
        "--fused-megastep",
        action="store_true",
        help="Anakin-style fused megastep: rollout chunk + ring ingest "
        "+ on-device PER sampling + K learner steps as ONE device "
        "program per iteration; dp-shards over a multi-device mesh "
        "when capacity/batch/lanes divide dp (needs the device ring — "
        "rl/megastep.py, docs/PARALLELISM.md).",
    )
    p.add_argument(
        "--workers",
        type=int,
        default=None,
        metavar="N",
        help="Independent rollout streams in overlapped mode (the "
        "reference's self-play worker count).",
    )
    p.add_argument(
        "--replay-ratio",
        type=float,
        default=None,
        help="Async mode: samples consumed per experience produced.",
    )
    p.add_argument(
        "--fast-sims",
        type=int,
        default=None,
        metavar="S",
        help="Enable playout cap randomization: fast searches use S "
        "sims; only full searches train the policy.",
    )
    p.add_argument(
        "--full-search-prob",
        type=float,
        default=None,
        help="Probability a move runs the full search under playout "
        "cap randomization (default 0.25).",
    )
    p.add_argument(
        "--gumbel",
        action="store_true",
        help="Gumbel root search with sequential halving instead of "
        "PUCT+Dirichlet (stronger at small sim budgets).",
    )
    p.add_argument(
        "--checkpoint-freq",
        type=int,
        default=None,
        metavar="STEPS",
        help="Checkpoint every N learner steps (CHECKPOINT_SAVE_FREQ_STEPS).",
    )
    p.add_argument(
        "--keep-checkpoints",
        type=int,
        default=None,
        metavar="K",
        help="Retain the newest K checkpoints (KEEP_LAST_CHECKPOINTS; "
        "default 5). Raise for post-hoc strength curves over a whole "
        "run's checkpoints.",
    )
    p.add_argument("--no-per", action="store_true")
    p.add_argument(
        "--no-auto-resume",
        action="store_true",
        help="Start fresh instead of resuming the latest run.",
    )
    p.add_argument("--load-checkpoint", default=None, metavar="PATH")
    p.add_argument("--load-buffer", default=None, metavar="PATH")
    p.add_argument("--root-dir", default=None, help="Runs root directory.")
    p.add_argument("--no-tensorboard", action="store_true")
    p.add_argument(
        "--device",
        default=None,
        choices=["auto", "tpu", "cpu"],
        help="Compute platform; cpu forces the CPU backend even when an "
        "accelerator plugin is present.",
    )
    p.add_argument(
        "--distributed",
        action="store_true",
        help="Join a jax.distributed cluster (auto-discovery on TPU "
        "pods; use --coordinator/--num-processes/--process-id for "
        "explicit clusters).",
    )
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT")
    p.add_argument("--num-processes", type=int, default=None)
    p.add_argument("--process-id", type=int, default=None)
    p.add_argument(
        "--no-telemetry",
        action="store_true",
        help="Disable the run-telemetry subsystem (span trace, "
        "health.json heartbeat, stall watchdog, anomaly detection; "
        "docs/OBSERVABILITY.md).",
    )
    p.add_argument(
        "--watchdog-deadline",
        type=float,
        default=None,
        metavar="SECONDS",
        help="Stall watchdog deadline: no learner step and no rollout "
        "harvest for this long dumps thread stacks + flags the "
        "heartbeat (default 300).",
    )


def merge_train_overrides(base_config, overrides: dict):
    """Apply CLI overrides on top of a preset TrainConfig.

    Rebuilds through the constructor (NOT model_copy) so pydantic
    validation runs, and drops derived schedule lengths when the
    horizon changes so they re-derive instead of keeping the preset's
    values (TrainConfig._derive_schedule_lengths only fills Nones).
    """
    from .config import TrainConfig

    base = base_config.model_dump()
    if "MAX_TRAINING_STEPS" in overrides:
        base.pop("LR_SCHEDULER_T_MAX", None)
        base.pop("PER_BETA_ANNEAL_STEPS", None)
    base.update(overrides)
    return TrainConfig(**base)


_PRESET_TARGET_HELP = (
    "1..5 = the BASELINE preset `cli train --preset N` runs "
    "(config/presets.py), or a tuned_preset.json path from `cli tune`."
)


def resolve_preset(target: str, run_name: "str | None" = None) -> dict:
    """The config bundle {env, model, mcts, train, mesh, ...} a preset
    names: 1..5 is a BASELINE preset (config/presets.py), a path is a
    `tuned_preset.json` from `cli tune` (the bundle then carries the
    artifact as `tuned`). `train --preset`, `warm`, `fit` and `tune`
    all resolve through here, so the shapes they answer for are the
    ones `cli train --preset <target>` runs."""
    from .config import baseline_preset, load_tuned_preset

    target = str(target)
    try:
        if target.isdigit():
            return baseline_preset(int(target), run_name=run_name)
        if Path(target).is_file():
            return load_tuned_preset(target)
    except ValueError as exc:
        raise SystemExit(f"preset {target}: {exc}") from exc
    raise SystemExit(
        f"Unknown preset {target!r}: expected 1..5 (the BASELINE.md "
        "configurations, config/presets.py) or a tuned_preset.json "
        "path (emitted by `cli tune`)."
    )


def cmd_train(args: argparse.Namespace) -> int:
    from .config import PersistenceConfig, TrainConfig
    from .parallel.distributed import DistributedConfig
    from .training.runner import run_training

    overrides: dict = {}
    if args.run_name is not None:
        overrides["RUN_NAME"] = args.run_name
    if args.seed is not None:
        overrides["RANDOM_SEED"] = args.seed
    if args.max_steps is not None:
        overrides["MAX_TRAINING_STEPS"] = args.max_steps
    if args.self_play_batch is not None:
        overrides["SELF_PLAY_BATCH_SIZE"] = args.self_play_batch
    if args.batch_size is not None:
        overrides["BATCH_SIZE"] = args.batch_size
    if args.buffer_capacity is not None:
        overrides["BUFFER_CAPACITY"] = args.buffer_capacity
    if args.min_buffer is not None:
        overrides["MIN_BUFFER_SIZE_TO_TRAIN"] = args.min_buffer
    if args.rollout_chunk is not None:
        overrides["ROLLOUT_CHUNK_MOVES"] = args.rollout_chunk
    if args.fused_learner_steps is not None:
        overrides["FUSED_LEARNER_STEPS"] = args.fused_learner_steps
    if args.async_rollouts:
        overrides["ASYNC_ROLLOUTS"] = True
    if args.device_replay is not None:
        overrides["DEVICE_REPLAY"] = args.device_replay
    if args.fused_megastep:
        overrides["FUSED_MEGASTEP"] = True
    if args.workers is not None:
        overrides["NUM_SELF_PLAY_WORKERS"] = args.workers
    if args.replay_ratio is not None:
        overrides["REPLAY_RATIO"] = args.replay_ratio
    if args.checkpoint_freq is not None:
        overrides["CHECKPOINT_SAVE_FREQ_STEPS"] = args.checkpoint_freq
    if args.no_per:
        overrides["USE_PER"] = False
    if args.no_auto_resume:
        overrides["AUTO_RESUME_LATEST"] = False
    if args.load_checkpoint is not None:
        overrides["LOAD_CHECKPOINT_PATH"] = args.load_checkpoint
    if args.load_buffer is not None:
        overrides["LOAD_BUFFER_PATH"] = args.load_buffer
    if args.profile:
        overrides["PROFILE_WORKERS"] = True
    if args.device is not None:
        overrides["DEVICE"] = args.device

    telemetry_config = None
    if args.no_telemetry or args.watchdog_deadline is not None:
        from .config import TelemetryConfig

        t_kw: dict = {}
        if args.no_telemetry:
            t_kw["ENABLED"] = False
        if args.watchdog_deadline is not None:
            t_kw["WATCHDOG_DEADLINE_S"] = args.watchdog_deadline
        telemetry_config = TelemetryConfig(**t_kw)

    env_config = model_config = mcts_config = mesh_config = None
    tuned_payload = None
    if args.preset is not None:
        bundle = resolve_preset(args.preset, run_name=args.run_name)
        tuned_payload = bundle.get("tuned")
        env_config = bundle["env"]
        model_config = bundle["model"]
        mcts_config = bundle["mcts"]
        mesh_config = bundle["mesh"]
        train_config = merge_train_overrides(bundle["train"], overrides)
    else:
        train_config = TrainConfig(**overrides)

    if (
        args.fast_sims is not None
        or args.full_search_prob is not None
        or args.gumbel
    ):
        from .config import AlphaTriangleMCTSConfig

        mcts_kw = mcts_config.model_dump() if mcts_config else {}
        if args.fast_sims is not None:
            mcts_kw["fast_simulations"] = args.fast_sims
        if args.full_search_prob is not None:
            mcts_kw["full_search_prob"] = args.full_search_prob
        if args.gumbel:
            mcts_kw["root_selection"] = "gumbel"
        if (
            args.full_search_prob is not None
            and mcts_kw.get("fast_simulations") is None
        ):
            raise SystemExit(
                "--full-search-prob has no effect without --fast-sims "
                "(playout cap randomization stays disabled)."
            )
        mcts_config = AlphaTriangleMCTSConfig(**mcts_kw)

    persistence_config = None
    if args.root_dir is not None or args.keep_checkpoints is not None:
        p_kw: dict = {"RUN_NAME": train_config.RUN_NAME}
        if args.root_dir is not None:
            p_kw["ROOT_DATA_DIR"] = args.root_dir
        if args.keep_checkpoints is not None:
            p_kw["KEEP_LAST_CHECKPOINTS"] = args.keep_checkpoints
        persistence_config = PersistenceConfig(**p_kw)
    distributed_config = None
    if args.distributed or args.coordinator is not None:
        distributed_config = DistributedConfig(
            ENABLED=True,
            COORDINATOR_ADDRESS=args.coordinator,
            NUM_PROCESSES=args.num_processes,
            PROCESS_ID=args.process_id,
        )
    rc = run_training(
        train_config=train_config,
        env_config=env_config,
        model_config=model_config,
        mcts_config=mcts_config,
        mesh_config=mesh_config,
        persistence_config=persistence_config,
        distributed_config=distributed_config,
        telemetry_config=telemetry_config,
        log_level=args.log_level,
        use_tensorboard=not args.no_tensorboard,
        dry_setup=args.dry_setup,
    )
    if rc == 0 and tuned_payload is not None and not args.dry_setup:
        # Close the autotuner's loop: ledger predicted-vs-observed so
        # the next `cli tune --calibrate` sharpens its model
        # (docs/AUTOTUNE.md).
        from .autotune import ledger_tune_outcome

        p_cfg = persistence_config or PersistenceConfig(
            RUN_NAME=train_config.RUN_NAME
        )
        record = ledger_tune_outcome(
            p_cfg.get_run_base_dir(), tuned_payload
        )
        if record is not None:
            ratio = record.get("observed_over_predicted")
            print(
                "tune-outcome: observed/predicted games/h = "
                f"{ratio if ratio is not None else 'n/a'} "
                "(ledgered for future `cli tune --calibrate`)."
            )
    return rc


def _launch_ui(tool: str, argv: list[str], module: str | None = None) -> int:
    """Run a dashboard tool in the foreground (reference `cli.py:85-137`).

    `module`: the runnable module when it differs from the import name
    (tensorboard's entry point is tensorboard.main, not the package).
    """
    try:
        __import__(tool)
    except ImportError:
        print(
            f"{tool} is not installed in this environment. "
            f"Install it to use this command.",
            file=sys.stderr,
        )
        return 1
    cmd = [sys.executable, "-m", module or tool, *argv]
    print(f"Launching: {' '.join(cmd)} (Ctrl-C to stop)")
    try:
        return subprocess.call(cmd)
    except KeyboardInterrupt:
        return 0


def cmd_tb(args: argparse.Namespace) -> int:
    from .config import PersistenceConfig

    root = args.root_dir or PersistenceConfig().ROOT_DATA_DIR
    return _launch_ui(
        "tensorboard",
        ["--logdir", root, "--port", str(args.port)],
        module="tensorboard.main",
    )


def cmd_ml(args: argparse.Namespace) -> int:
    from .config import PersistenceConfig

    root = args.root_dir or PersistenceConfig().ROOT_DATA_DIR
    return _launch_ui(
        "mlflow", ["ui", "--backend-store-uri", root, "--port", str(args.port)]
    )


def _resolve_run_dir(
    run_name: str | None, root_dir: str | None
) -> "Path | None":
    """Run directory for a (run name, runs root) pair; latest run when
    the name is omitted. Never imports JAX (safe beside a sick chip)."""
    from .config import PersistenceConfig
    from .stats.watch import find_latest_run_dir

    persistence = PersistenceConfig(RUN_NAME=run_name or "latest")
    if root_dir:
        persistence = persistence.model_copy(
            update={"ROOT_DATA_DIR": root_dir}
        )
    if run_name:
        return persistence.get_run_base_dir()
    run_dir = find_latest_run_dir(persistence.get_runs_root_dir())
    if run_dir is None:
        print(
            f"no runs under {persistence.get_runs_root_dir()}",
            file=sys.stderr,
        )
    return run_dir


def cmd_health(args: argparse.Namespace) -> int:
    """Heartbeat check for a run: pretty-print `health.json` + a
    staleness verdict. Exit 0 = live, 1 = stalled/stale, 2 = no
    heartbeat — so a supervisor (or a cron) can gate on it
    without parsing anything."""
    from .telemetry.health import health_verdict, read_health

    run_dir = _resolve_run_dir(args.run, args.root_dir)
    if run_dir is None:
        return 2
    if getattr(args, "probe", False):
        # Machine mode (docs/OBSERVABILITY.md "Probe"): ONE JSON line +
        # the probe exit-code contract (0 live / 1 stalled-or-stale /
        # 2 missing / 3 unsealed dispatch past deadline). The same
        # implementation the fleet router's admission gate uses, so
        # external orchestrators and the fleet agree on readiness.
        import json as _json

        from .telemetry.health import probe_run

        result = probe_run(run_dir, deadline_s=args.deadline)
        print(_json.dumps(result))
        return int(result["code"])
    path = run_dir / "health.json"
    payload = read_health(path)
    if payload is None:
        print(f"no readable heartbeat at {path}", file=sys.stderr)
        return 2
    ok, age, reason = health_verdict(payload, deadline_s=args.deadline)
    verdict = "LIVE" if ok else "STALLED"
    print(f"run {payload.get('run') or run_dir.name}: {verdict} ({reason})")
    print(
        f"  heartbeat    {age:,.0f}s ago (pid {payload.get('pid')}, "
        f"uptime {payload.get('uptime_s', 0):,.0f}s)"
    )
    learner_age = payload.get("learner_age_s")
    rollout_age = payload.get("rollout_age_s")
    print(
        f"  learner      step {payload.get('learner_step', 0):,}"
        + (
            f", last step {learner_age:,.0f}s before the heartbeat"
            if learner_age is not None
            else " (no step yet)"
        )
    )
    print(
        f"  self-play    {payload.get('episodes_played', 0):,} episodes, "
        f"{payload.get('experiences_added', 0):,} experiences"
        + (
            f", last harvest {rollout_age:,.0f}s before the heartbeat"
            if rollout_age is not None
            else ""
        )
    )
    print(
        f"  buffer       {payload.get('buffer_size', 0):,} | stalls "
        f"{payload.get('stall_count', 0)} | deadline "
        f"{payload.get('watchdog_deadline_s')}s"
    )
    for mem in payload.get("device_memory") or []:
        in_use = mem.get("bytes_in_use") or 0
        limit = mem.get("bytes_limit") or 0
        peak = mem.get("peak_bytes_in_use") or 0
        pct = f" ({100.0 * in_use / limit:.0f}%)" if limit else ""
        print(
            f"  device {mem.get('device')} [{mem.get('kind')}]  "
            f"{in_use / 2**30:.2f} GiB in use"
            + (f", peak {peak / 2**30:.2f} GiB" if peak else "")
            + (f" / {limit / 2**30:.2f} GiB{pct}" if limit else "")
        )
    return 0 if ok else 1


def cmd_trace(args: argparse.Namespace) -> int:
    """Summarize a run's host span trace (`trace.json`): per-span-name
    totals, busiest first, plus the file path for Perfetto/chrome
    loading. The spans are wall-clock, so they line up with any
    `--profile` xplane device traces from the same run.

    `--fleet` instead fuses a fleet-parent run dir's evidence — the
    parent's route brackets + fleet.jsonl lifecycle + every replica's
    flight ring and trace.json, clock-calibrated per process — into
    ONE Perfetto timeline with flow arrows following each trace_id
    from router queue-wait to the replica's `serve/b<B>` dispatch
    wall (telemetry/merge.py)."""
    from .telemetry.tracer import summarize_trace_file

    run_dir = _resolve_run_dir(args.run, args.root_dir)
    if run_dir is None:
        return 1
    if args.fleet:
        from .telemetry.merge import merge_fleet_trace

        try:
            result = merge_fleet_trace(run_dir)
        except FileNotFoundError:
            print(
                f"no fleet evidence in {run_dir} (fleet.jsonl missing — "
                "not a fleet-parent run dir?)",
                file=sys.stderr,
            )
            return 1
        print(
            f"merged {result['events']:,} events from "
            f"{result['processes']} process(es), "
            f"{result['replicas']} replica dir(s) -> {result['path']}"
        )
        print(
            f"  route spans {result['route_spans']:,}   "
            f"flow arrows {result['flows']:,} over "
            f"{len(result['flow_trace_ids']):,} trace id(s)"
        )
        print(
            f"\nfull fleet timeline: load {result['path']} in "
            "https://ui.perfetto.dev or chrome://tracing"
        )
        return 0
    path = run_dir / "trace.json"
    try:
        rows = summarize_trace_file(path, top=args.top)
    except (OSError, ValueError) as exc:
        print(f"no readable span trace at {path} ({exc})", file=sys.stderr)
        return 1
    if not rows:
        print(f"{path}: no complete spans recorded.")
        return 0
    width = max(max(len(r["name"]) for r in rows), 5)
    print(
        f"{'span':<{width}}  {'count':>7}  {'total s':>9}  {'self s':>9}  "
        f"{'mean ms':>9}  {'max ms':>9}  {'threads':>7}"
    )
    for r in rows:
        print(
            f"{r['name']:<{width}}  {r['count']:>7d}  "
            f"{r['total_ms'] / 1e3:>9.2f}  {r['self_ms'] / 1e3:>9.2f}  "
            f"{r['mean_ms']:>9.2f}  {r['max_ms']:>9.2f}  {r['threads']:>7d}"
        )
    print(
        f"\nfull timeline: load {path} in https://ui.perfetto.dev "
        "or chrome://tracing"
    )
    return 0


def cmd_watch(args: argparse.Namespace) -> int:
    """Live-run console: tail a run's `live_metrics.jsonl` and render
    games/h, learner steps/s, replay ratio, staleness, queue depth —
    the observability the reference served via its Ray dashboard +
    MLflow UI (`alphatriangle/cli.py:301-326`). Never imports JAX, so
    it is safe to run beside a training process on a sick-chip day."""
    import time as _time

    from .stats.watch import (
        FleetWatchState,
        WatchState,
        fleet_line,
        render_frame,
        tail_fleet,
        tail_flight,
        tail_ledger_utils,
        tail_live_metrics,
    )
    from .telemetry.flight import FLIGHT_FILENAME
    from .telemetry.health import read_health

    run_dir = _resolve_run_dir(args.run_name, args.root_dir)
    if run_dir is None:
        return 1
    live = run_dir / "live_metrics.jsonl"
    ledger = run_dir / "metrics.jsonl"
    flight = run_dir / FLIGHT_FILENAME
    fleet_ledger = run_dir / "fleet.jsonl"
    heartbeat = run_dir / "health.json"
    state = WatchState()
    fleet_state = FleetWatchState()
    offset = tail_live_metrics(live, state, 0)
    ledger_offset = tail_ledger_utils(ledger, state, 0)
    flight_offset = tail_flight(flight, state, 0)
    fleet_offset = tail_fleet(fleet_ledger, fleet_state, 0)

    def fleet_extra() -> str:
        """Fleet-parent run dirs get the routing vitals + the SLO
        roll-up appended under the standard frame; training run dirs
        (no fleet.jsonl) render nothing extra."""
        fl = fleet_line(fleet_state)
        if fl is None:
            return ""
        extra = "\n" + fl
        try:
            from .telemetry.slo import evaluate_slos, slo_status_line

            extra += "\n  " + slo_status_line(evaluate_slos(run_dir))
        except Exception:  # the SLO line must never kill the console
            pass
        return extra

    if not live.exists() and not fleet_ledger.exists():
        print(
            f"waiting for {live} (run still starting?) — Ctrl-C to stop",
            file=sys.stderr,
        )
    frame = (
        render_frame(state, run_dir.name, health=read_health(heartbeat))
        + fleet_extra()
    )
    print(frame, flush=True)
    if args.once:
        return 0
    try:
        while True:
            _time.sleep(args.interval)
            offset = tail_live_metrics(live, state, offset)
            ledger_offset = tail_ledger_utils(ledger, state, ledger_offset)
            flight_offset = tail_flight(flight, state, flight_offset)
            fleet_offset = tail_fleet(fleet_ledger, fleet_state, fleet_offset)
            # Redraw in place: move up over the previous frame.
            height = frame.count("\n") + 1
            frame = (
                render_frame(
                    state, run_dir.name, health=read_health(heartbeat)
                )
                + fleet_extra()
            )
            print(f"\x1b[{height}F\x1b[0J" + frame, flush=True)
    except KeyboardInterrupt:
        return 0


def _fmt_cell(value, spec: str = ",.2f", scale: float = 1.0, unit: str = "") -> str:
    if not isinstance(value, (int, float)):
        return "—"
    return f"{value * scale:{spec}}{unit}"


def cmd_perf(args: argparse.Namespace) -> int:
    """Windowed performance summary of a run's metrics ledger: p50/p95
    step time, MFU, throughput and its trend. Reads `metrics.jsonl`
    only — never imports JAX, safe beside a wedged chip. Exit 0 on a
    usable summary, 2 when the ledger is missing or holds no
    utilization records (the schema-gate `make perf-smoke` relies on)."""
    import json as _json

    from .telemetry.ledger import read_ledger, resolve_ledger_path
    from .telemetry.perf import summarize_utilization

    target = Path(args.run) if args.run else None
    if target is not None and target.exists():
        ledger = resolve_ledger_path(target)
    else:
        run_dir = _resolve_run_dir(args.run, args.root_dir)
        if run_dir is None:
            return 2
        ledger = resolve_ledger_path(run_dir)
        if ledger is None:
            print(f"no metrics ledger in {run_dir}", file=sys.stderr)
            return 2
    if ledger is None:
        print(f"no metrics ledger at {args.run}", file=sys.stderr)
        return 2
    # No kinds= pre-filter: summarize_utilization itself tolerates
    # kind-less legacy util ticks that the filter would drop.
    summary = summarize_utilization(
        read_ledger(ledger), window=args.window
    )
    if summary is None:
        print(
            f"{ledger}: no utilization records (run predates the "
            "ledger, or telemetry was disabled)",
            file=sys.stderr,
        )
        return 2
    # Static memory budget rides the summary (compare gates it as
    # `memory_budget_bytes` next to the observed peak).
    mem_budget = None
    mem_records = read_ledger(ledger, kinds={"memory"})
    if mem_records:
        from .telemetry.memory import compose_budget

        budget = compose_budget(mem_records)
        if budget["total_bytes"] > 0:
            mem_budget = budget["total_bytes"]
            summary["memory_budget_bytes"] = mem_budget
    # Per-program device time from the flight recorder's sealed
    # records (telemetry/flight.py): measured dispatch->fetch walls
    # per compiled program, the rows `cli tune --calibrate` feeds on.
    from .telemetry.flight import FLIGHT_FILENAME, read_flight, summarize_flight

    programs = summarize_flight(read_flight(ledger.parent / FLIGHT_FILENAME))
    if programs:
        summary["programs"] = programs
    # League flywheel fold (league/flywheel.py `kind:"league"` records):
    # flywheel runs gain the league_* fields and the league line below.
    from .telemetry.perf import summarize_league

    league = summarize_league(read_ledger(ledger, kinds={"league"}))
    if league is not None:
        summary.update(league)
    # Fleet fold (serving/fleet.py fleet.jsonl decision ledger, beside
    # the metrics ledger): fleet runs gain the fleet_* fields and the
    # fleet line below.
    from .telemetry.perf import summarize_fleet

    fleet_path = ledger.parent / "fleet.jsonl"
    fleet = (
        summarize_fleet(read_ledger(fleet_path))
        if fleet_path.is_file()
        else None
    )
    if fleet is not None:
        summary.update(fleet)
    # Device-stats fold (telemetry/device_stats.py `kind:"device_stats"`
    # records — the in-program stat-packs): ds_* fields + the line
    # below. None on legacy/stats-off ledgers, zero new output then.
    from .telemetry.device_stats import summarize_device_stats

    devstats = summarize_device_stats(
        read_ledger(ledger, kinds={"device_stats"})
    )
    if devstats is not None:
        summary.update(devstats)
    # Roofline fold (telemetry/roofline.py): compiler cost records
    # (`kind:"cost"`) joined against flight-seal walls → roofline_*
    # fields, per-program intensity/bound columns, and the idle line.
    # Gated on cost records existing, so pre-roofline ledgers render
    # with ZERO new fields even though they carry a flight ring.
    roof = None
    cost_records = read_ledger(ledger, kinds={"cost"})
    if cost_records:
        from .telemetry.roofline import summarize_roofline

        roof = summarize_roofline(
            cost_records,
            read_flight(ledger.parent / FLIGHT_FILENAME),
            device_kind=summary.get("device_kind") or "",
            peak_tflops=summary.get("peak_bf16_tflops"),
            trace_path=ledger.parent / "trace.json",
        )
    if roof is not None:
        if roof.get("machine_balance_flops_per_byte") is not None:
            summary["roofline_machine_balance_flops_per_byte"] = roof[
                "machine_balance_flops_per_byte"
            ]
            summary["roofline_peak_hbm_gbps"] = roof.get("peak_hbm_gbps")
        attrib = roof.get("attribution")
        if attrib:
            summary["roofline_chip_idle_fraction"] = attrib.get(
                "chip_idle_fraction"
            )
            summary["roofline_attributed_fraction"] = attrib.get(
                "attributed_fraction"
            )
            summary["roofline_dispatch_s"] = attrib.get("dispatch_s")
            summary["roofline_gap_s"] = attrib.get("gap_s")
            for cat, s in (attrib.get("gaps") or {}).items():
                summary[f"roofline_gap_{cat}_s"] = s
        rows = {r["program"]: r for r in roof.get("programs") or []}
        for p in programs or []:
            r = rows.get(p.get("program"))
            if r is not None:
                p["intensity"] = r.get("intensity")
                p["bound"] = r.get("bound")
                p["roofline_fraction"] = r.get("roofline_fraction")
    if args.json:
        summary["source"] = str(ledger)
        print(_json.dumps(summary))
        return 0
    peak = summary.get("peak_bf16_tflops")
    trend = summary.get("throughput_trend")
    print(f"perf {ledger}")
    print(
        f"  window       {summary['ticks']} tick(s)"
        f" ({summary['ticks_total']} on record),"
        f" steps {summary.get('first_step')}→{summary.get('last_step')},"
        f" {_fmt_cell(summary.get('wall_seconds'), ',.0f', 1, 's')} wall"
    )
    print(
        f"  device       {summary.get('device_kind') or '?'}"
        f"   peak bf16 {_fmt_cell(peak, ',.0f', 1, ' TFLOP/s') if peak else 'unknown'}"
        + (
            f" [{summary.get('peak_source')}]"
            if summary.get("peak_source")
            else ""
        )
    )
    print(
        f"  learner      {_fmt_cell(summary.get('learner_steps_per_sec'))} steps/s"
        f"   step p50 {_fmt_cell(summary.get('step_time_ms_p50'), ',.1f', 1, 'ms')}"
        f"   p95 {_fmt_cell(summary.get('step_time_ms_p95'), ',.1f', 1, 'ms')}"
    )
    print(
        f"  self-play    {_fmt_cell(summary.get('games_per_hour'), ',.1f')} games/h"
        f"   {_fmt_cell(summary.get('moves_per_sec'), ',.1f')} moves/s"
        f"   {_fmt_cell(summary.get('sims_per_sec'), ',.0f')} sims/s"
    )
    print(
        f"  utilization  MFU {_fmt_cell(summary.get('mfu'), ',.2f', 100.0, '%')}"
        f" (max {_fmt_cell(summary.get('mfu_max'), ',.2f', 100.0, '%')})"
        f"   {_fmt_cell(summary.get('tflops_per_sec'))} TFLOP/s"
    )
    print(
        f"  transfers    h2d {_fmt_cell(summary.get('transfer_h2d_ms'), ',.1f', 1, 'ms')}"
        f"   d2h {_fmt_cell(summary.get('transfer_d2h_ms'), ',.1f', 1, 'ms')}"
        f"   buffer fill {_fmt_cell(summary.get('buffer_fill_last'), ',.2f', 100.0, '%')}"
        f"   compile hits {_fmt_cell(summary.get('compile_cache_hit_rate'), ',.0f', 100.0, '%')}"
        f"   dispatch/iter {_fmt_cell(summary.get('dispatches_per_iteration'), ',.1f')}"
    )
    mem_peak = summary.get("mem_peak_bytes_in_use")
    if mem_peak is not None or mem_budget is not None:
        from .telemetry.memory import fmt_bytes as _fmt_bytes

        print(
            f"  memory       peak {_fmt_bytes(mem_peak)}"
            f"   in use {_fmt_bytes(summary.get('mem_bytes_in_use_last'))}"
            f"   limit {_fmt_bytes(summary.get('mem_bytes_limit'))}"
            f"   est budget {_fmt_bytes(mem_budget)} (cli mem)"
        )
    if devstats is not None:
        # In-program search health (device-stats plane): entropy/
        # occupancy are window means, value/occupancy maxes are
        # run-wide excursions.
        print(
            f"  search       entropy {_fmt_cell(summary.get('ds_root_entropy'), ',.2f')}"
            f" (min {_fmt_cell(summary.get('ds_root_entropy_min'), ',.2f')})"
            f"   |v|max {_fmt_cell(summary.get('ds_value_abs_max'), ',.2f')}"
            f"   occupancy {_fmt_cell(summary.get('ds_tree_occupancy'), ',.0f', 100.0, '%')}"
            f" (max {_fmt_cell(summary.get('ds_tree_occupancy_max'), ',.0f', 100.0, '%')})"
            f"   reuse {_fmt_cell(summary.get('ds_reuse_frac'), ',.0f', 100.0, '%')}"
            f"   records {_fmt_cell(summary.get('ds_records'), ',.0f')}"
        )
        if summary.get("ds_grad_norm_max") is not None or summary.get(
            "ds_priority_skew"
        ) is not None:
            print(
                f"  ingest/per   priority skew {_fmt_cell(summary.get('ds_priority_skew'), ',.1f')}"
                f"   IS w min {_fmt_cell(summary.get('ds_is_weight_min'), ',.3f')}"
                f"   grad max {_fmt_cell(summary.get('ds_grad_norm_max'), ',.2f')}"
                f"   update max {_fmt_cell(summary.get('ds_update_norm_max'), ',.3f')}"
            )
    if summary.get("serve_move_latency_ms_p95") is not None:
        # Policy-service SLO line (serving/service.py; docs/SERVING.md):
        # p50 averages tick windows, p95 is the WORST window.
        print(
            f"  serving      move p50 {_fmt_cell(summary.get('serve_move_latency_ms_p50'), ',.1f', 1, 'ms')}"
            f"   p95 {_fmt_cell(summary.get('serve_move_latency_ms_p95'), ',.1f', 1, 'ms')}"
            f"   wait p95 {_fmt_cell(summary.get('serve_queue_wait_ms_p95'), ',.1f', 1, 'ms')}"
            f"   {_fmt_cell(summary.get('serve_requests_per_sec'), ',.1f')} req/s"
            f"   fill {_fmt_cell(summary.get('serve_batch_fill'), ',.0f', 100.0, '%')}"
            f"   reloads {_fmt_cell(summary.get('serve_weight_reloads'), ',.0f')}"
        )
        if summary.get("serve_bucket") is not None:
            # Micro-batcher ladder line (serving/buckets.py): the rung
            # the run ended on, mean wave fill, and switch count.
            print(
                f"  serve ladder bucket b{summary.get('serve_bucket')}"
                f"   fill {_fmt_cell(summary.get('serve_fill'), ',.0f', 100.0, '%')}"
                f"   switches {_fmt_cell(summary.get('serve_rung_switches'), ',.0f')}"
            )
    if league is not None:
        print(
            f"  league       pool {_fmt_cell(summary.get('league_pool_size'), ',.0f')}"
            f"   rounds {_fmt_cell(summary.get('league_rounds'), ',.0f')}"
            f"   ingest {_fmt_cell(summary.get('league_ingested_moves_per_sec'), ',.1f')} moves/s"
            f" ({_fmt_cell(summary.get('league_moves_ingested'), ',.0f')} total)"
            f"   staleness {_fmt_cell(summary.get('league_mean_staleness'), ',.1f')}"
            f"   stale dropped {_fmt_cell(summary.get('league_stale_dropped'), ',.0f')}"
            f"   promotions {_fmt_cell(summary.get('league_promotions'), ',.0f')}"
            f"   live elo {_fmt_cell(summary.get('league_live_elo'), ',.1f')}"
        )
    if fleet is not None:
        # Fleet churn + storm SLOs (serving/fleet.py; fleet.jsonl):
        # latency is end-to-end as the router saw it, retries/hedges
        # included.
        print(
            f"  fleet        move p50 {_fmt_cell(summary.get('fleet_move_latency_ms_p50'), ',.1f', 1, 'ms')}"
            f"   p95 {_fmt_cell(summary.get('fleet_move_latency_ms_p95'), ',.1f', 1, 'ms')}"
            f"   {_fmt_cell(summary.get('fleet_requests_per_sec'), ',.1f')} req/s"
            f"   deaths {_fmt_cell(summary.get('fleet_deaths'), ',.0f')}"
            f"   respawns {_fmt_cell(summary.get('fleet_respawns'), ',.0f')}"
            f"   readmits {_fmt_cell(summary.get('fleet_readmissions'), ',.0f')}"
            f"   sheds {_fmt_cell(summary.get('fleet_sheds'), ',.0f')}"
            f"   lost {_fmt_cell(summary.get('fleet_lost'), ',.0f')}"
        )
    if roof is not None and roof.get("attribution"):
        attrib = roof["attribution"]
        gaps = attrib.get("gaps") or {}
        gap_text = "  ".join(
            f"{cat} {_fmt_cell(s, ',.1f', 1, 's')}"
            for cat, s in gaps.items()
            if isinstance(s, (int, float)) and s > 0
        )
        print(
            f"  roofline     idle {_fmt_cell(attrib.get('chip_idle_fraction'), ',.1f', 100.0, '%')}"
            f"   dispatch {_fmt_cell(attrib.get('dispatch_s'), ',.1f', 1, 's')}"
            f"   attributed {_fmt_cell(attrib.get('attributed_fraction'), ',.1f', 100.0, '%')}"
            + (f"   gaps: {gap_text}" if gap_text else "")
        )
    if programs:
        # Measured per-program device time (flight recorder seals) —
        # busiest first; errors are ok:false seals (failed dispatches).
        # Roofline columns (intensity FLOP/byte, bound, fraction of the
        # roofline ceiling) appear only when cost records exist; rows
        # without a cost sidecar degrade to "—" cells, never raise.
        width = max(max(len(p["program"]) for p in programs), 7)
        head = f"  {'program':<{width}}  {'count':>6}  {'p50':>9}  {'p95':>9}  {'total':>9}  err"
        if roof is not None:
            head += f"  {'intensity':>10}  {'bound':>7}  {'roofline':>8}"
        print(head)
        for p in programs:
            line = (
                f"  {p['program']:<{width}}"
                f"  {p['count']:>6}"
                f"  {_fmt_cell(p['wall_s_p50'], ',.1f', 1e3, 'ms'):>9}"
                f"  {_fmt_cell(p['wall_s_p95'], ',.1f', 1e3, 'ms'):>9}"
                f"  {_fmt_cell(p['wall_s_total'], ',.1f', 1, 's'):>9}"
                f"  {p['errors']}"
            )
            if roof is not None:
                line += (
                    f"  {_fmt_cell(p.get('intensity'), ',.1f'):>10}"
                    f"  {p.get('bound') or '—':>7}"
                    f"  {_fmt_cell(p.get('roofline_fraction'), ',.2f', 100.0, '%'):>8}"
                )
            print(line)
    print(
        f"  trend        {_fmt_cell(trend, '+,.1f', 100.0, '%')} "
        "(2nd-half vs 1st-half throughput)"
    )
    return 0


def cmd_compare(args: argparse.Namespace) -> int:
    """Aligned-metric regression report between two runs (or a run and
    a `cli perf --json` summary snapshot). Exit 0 = parity or better,
    1 = at least one metric regressed past --threshold, 2 = either side
    unreadable — so a CI job or a supervisor can gate on it."""
    import json as _json

    from .telemetry.perf import compare_summaries, load_comparable

    a, label_a = load_comparable(args.run_a, args.root_dir)
    b, label_b = load_comparable(args.run_b, args.root_dir)
    for side, loaded, label in (("A", a, label_a), ("B", b, label_b)):
        if loaded is None:
            print(f"compare: side {side}: {label}", file=sys.stderr)
    if a is None or b is None:
        return 2
    metrics = (
        tuple(m for m in args.metrics.split(",") if m)
        if args.metrics
        else None
    )
    rows, regressions = compare_summaries(
        a, b, threshold=args.threshold, metrics=metrics
    )
    compared = [r for r in rows if r[4] != "n/a"]
    if not compared:
        print(
            "compare: no aligned metrics between the two sides",
            file=sys.stderr,
        )
        return 2
    if args.json:
        print(
            _json.dumps(
                {
                    "a": label_a,
                    "b": label_b,
                    "threshold": args.threshold,
                    "rows": [
                        {
                            "metric": m,
                            "a": va,
                            "b": vb,
                            "ratio": ratio,
                            "status": status,
                        }
                        for m, va, vb, ratio, status in rows
                    ],
                    "regressions": regressions,
                }
            )
        )
        return 1 if regressions else 0
    print(f"compare  A = {label_a}")
    print(f"         B = {label_b}   (threshold {args.threshold:.0%})")
    width = max(len(r[0]) for r in rows)
    print(
        f"  {'metric':<{width}}  {'A':>12}  {'B':>12}  {'A/B':>7}  verdict"
    )
    for metric, va, vb, ratio, status in rows:
        print(
            f"  {metric:<{width}}  {_fmt_cell(va, ',.3f'):>12}  "
            f"{_fmt_cell(vb, ',.3f'):>12}  "
            f"{_fmt_cell(ratio, '.3f'):>7}  {status}"
        )
    if regressions:
        print(
            f"REGRESSION: {', '.join(regressions)} worse than baseline "
            f"by more than {args.threshold:.0%}"
        )
        return 1
    print("parity: no metric regressed past the threshold")
    return 0


def cmd_devices(_args: argparse.Namespace) -> int:
    import jax

    print(f"backend: {jax.default_backend()}")
    for d in jax.devices():
        print(f"  {d.id}: {getattr(d, 'device_kind', d.platform)}")
    return 0


def cmd_analyze(args: argparse.Namespace) -> int:
    from .profiling import analyze_profile_dir

    return analyze_profile_dir(args.profile_dir, top=args.top)


def cmd_eval(args: argparse.Namespace) -> int:
    """Arena evaluation: greedy-MCTS play from a checkpoint, with a
    uniform-random baseline (the reference evaluates strength only via
    training-run score metrics; this makes it a standalone command)."""
    import json as _json

    import numpy as np

    from .utils.helpers import enforce_platform

    enforce_platform(args.device or "auto")

    from .config import (
        AlphaTriangleMCTSConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from .config.run_configs import load_run_configs_or_default
    from .env.engine import TriangleEnv
    from .features.core import get_feature_extractor
    from .mcts import BatchedMCTS
    from .nn.network import NeuralNetwork
    from .rl import Trainer
    from .stats.persistence import CheckpointManager
    from .utils.helpers import enable_persistent_compilation_cache

    # Eval compiles the same flagship search programs training does.
    enable_persistent_compilation_cache()

    def run_base_dir(run_name: str):
        persistence = PersistenceConfig(RUN_NAME=run_name)
        if args.root_dir:
            persistence = persistence.model_copy(
                update={"ROOT_DATA_DIR": args.root_dir}
            )
        return persistence.get_run_base_dir()

    # Evaluate on the RUN'S OWN board/net configs when available
    # (configs.json in the run dir) — the flagship defaults only apply
    # to runs that actually used them. An explicit --checkpoint without
    # --run-name still has a run dir: checkpoints live at
    # <run>/checkpoints/step_XXXXXXXX, so the run's configs.json sits
    # two parents up from the step directory.
    if args.run_name:
        cfg_dir = run_base_dir(args.run_name)
    elif args.checkpoint:
        cfg_dir = Path(args.checkpoint).resolve().parent.parent
    else:
        cfg_dir = Path("/nonexistent")
    env_cfg, model_cfg = load_run_configs_or_default(cfg_dir)
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=args.sims)
    train_cfg = TrainConfig(RUN_NAME=args.run_name or "eval")

    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)

    def restore_net(
        checkpoint: str | None, run_name: str | None, net_model_cfg=None
    ):
        """Fresh net, optionally restored from a checkpoint path or a
        run's latest checkpoint. Returns (net, source-label)."""
        n = NeuralNetwork(net_model_cfg or model_cfg, env_cfg, seed=0)
        label = "untrained"
        if checkpoint or run_name:
            trainer = Trainer(n, train_cfg)
            persistence = PersistenceConfig(
                RUN_NAME=run_name or "eval_tmp"
            )
            if args.root_dir:
                persistence = persistence.model_copy(
                    update={"ROOT_DATA_DIR": args.root_dir}
                )
            mgr = CheckpointManager(persistence)
            loaded = (
                mgr.restore_path(checkpoint, trainer.state)
                if checkpoint
                else mgr.restore(trainer.state)
            )
            if loaded.train_state is None:
                print("No checkpoint found; evaluating the untrained net.")
            else:
                trainer.set_state(loaded.train_state)
                trainer.sync_to_network()
                label = f"step {loaded.global_step}"
                if run_name and not checkpoint:
                    # Only attribute to the run when the run's own
                    # latest checkpoint was what we restored (an
                    # explicit --checkpoint path wins the ternary and
                    # may come from a different run).
                    label = f"{run_name} {label}"
        return n, label

    def build_search(n, net_model_cfg=None):
        # Each net searches with features built from ITS OWN model
        # config: a --vs-run trained with different feature-affecting
        # settings (e.g. GRID_INPUT_CHANNELS) must not be evaluated on
        # run A's feature layout.
        ext = (
            get_feature_extractor(env, net_model_cfg)
            if net_model_cfg is not None
            else extractor
        )
        if args.gumbel:
            # Gumbel-aware evaluation: exploit mode (no root Gumbel
            # sample) — deterministic argmax of logits + sigma(q).
            from .mcts import GumbelMCTS

            return GumbelMCTS(
                env, ext, n.model, mcts_cfg, n.support, exploit=True
            )
        return BatchedMCTS(env, ext, n.model, mcts_cfg, n.support)

    from .arena import play as arena_play, play_service
    from .serving import PolicyService

    net, source = restore_net(args.checkpoint, args.run_name)
    mcts = build_search(net)
    B = args.games
    rng = np.random.default_rng(args.seed)

    def serve_play(n, m):
        """Search policies run through the policy service's session
        API (serving/service.py): eval traffic and served "human"
        traffic exercise one code path — admit/dispatch/retire over
        the compiled `serve/b<B>` search shape."""
        service = PolicyService(
            env, m.extractor, n, m, slots=B, use_gumbel=args.gumbel
        )
        return play_service(service, B, args.max_moves, args.seed)

    def random_policy(states, move):
        masks = np.asarray(env.valid_mask_batch(states))
        logits = np.where(masks, rng.random(masks.shape), -np.inf)
        return np.where(masks.any(axis=1), logits.argmax(axis=1), 0)

    print(f"Evaluating {source} net: {B} games, {args.sims} sims/move...")
    scores, lengths, done = serve_play(net, mcts)
    r_scores, r_lengths, _ = arena_play(
        env, random_policy, B, args.max_moves, args.seed
    )
    # Both policies start from the SAME reset keys, and hand draws
    # depend only on the step index (the key chain splits every step
    # regardless of action), so game i sees the same shape sequence
    # under both policies: the comparison is PAIRED, which strips the
    # hand-luck variance that dominates this game.
    diffs = scores - r_scores
    report = {
        "source": source,
        "games": B,
        "sims": args.sims,
        "mcts_mean_score": round(float(scores.mean()), 2),
        "mcts_max_score": round(float(scores.max()), 2),
        "mcts_mean_length": round(float(lengths.mean()), 1),
        "finished_fraction": round(float(done.mean()), 3),
        "random_mean_score": round(float(r_scores.mean()), 2),
        "score_vs_random": round(
            float(scores.mean() / max(r_scores.mean(), 1e-9)), 3
        ),
        "paired_mean_diff": round(float(diffs.mean()), 3),
        "paired_win_rate": round(
            float((diffs > 0).mean() + 0.5 * (diffs == 0).mean()), 3
        ),
    }

    # Head-to-head: a second checkpoint plays the SAME paired hands.
    if args.vs_checkpoint or args.vs_run:
        from .config.run_configs import load_run_configs

        model_cfg_b = None
        if args.vs_run:
            cfg_dir_b = run_base_dir(args.vs_run)
        else:
            cfg_dir_b = Path(args.vs_checkpoint).resolve().parent.parent
        loaded_b = load_run_configs(cfg_dir_b)
        if loaded_b:
            env_b, model_cfg_b = loaded_b["env"], loaded_b["model"]
            if env_b != env_cfg:
                raise SystemExit(
                    "Head-to-head needs both runs on the same env "
                    "config; the --vs side trained on a different "
                    "board."
                )
        net_b, source_b = restore_net(
            args.vs_checkpoint, args.vs_run, model_cfg_b
        )
        mcts_b = build_search(net_b, model_cfg_b)
        b_scores, _, _ = serve_play(net_b, mcts_b)
        h2h = scores - b_scores
        report.update(
            {
                "vs_source": source_b,
                "vs_mean_score": round(float(b_scores.mean()), 2),
                "h2h_paired_mean_diff": round(float(h2h.mean()), 3),
                "h2h_win_rate": round(
                    float((h2h > 0).mean() + 0.5 * (h2h == 0).mean()), 3
                ),
            }
        )

    print(_json.dumps(report))
    return 0


def cmd_play(args: argparse.Namespace) -> int:
    """Interactive text play (reference `trianglengin play/debug` CLI,
    its README.md:199-205). Prefers the native C++ engine (instant
    startup); falls back to the jitted JAX engine."""
    import numpy as np

    from .utils.helpers import enforce_platform

    # Interactive play is host-side work; never wake the accelerator
    # (whose init can hang on a sick chip) just to render a board.
    enforce_platform("cpu")

    from .config import EnvConfig
    from .env.engine import TriangleEnv
    from .env.native import native_available, native_build_error
    from .env.render import render_grid, render_shape
    from .env.shapes import bank_shape_triangles

    env_cfg = EnvConfig()
    env = TriangleEnv(env_cfg)
    use_native = args.engine == "native" or (
        args.engine == "auto" and native_available()
    )
    if args.engine == "native" and not native_available():
        print(f"native engine unavailable: {native_build_error()}")
        return 1

    if use_native:
        from .env.native import NativeTriangleEnv

        native = NativeTriangleEnv(env)
        batch = native.new_batch(1, seed=args.seed)

        def state_view():
            return (
                env.unpack_grid_np(batch.occupied[0]),
                batch.shape_idx[0],
                float(batch.score[0]),
                bool(batch.done[0]),
            )

        def do_step(action):
            rewards, _ = native.step(
                batch, np.asarray([action], np.int32)
            )
            return float(rewards[0])

        def valid_mask():
            return native.valid_mask(batch)[0]

    else:
        from .env.game_state import GameState

        game = GameState(env_cfg, initial_seed=args.seed)

        def state_view():
            grid = game.get_grid_data_np()
            hand = [
                -1 if s is None else 0 for s in game.get_shapes()
            ]  # display only
            return (
                grid["occupied"],
                np.asarray(
                    [
                        -1 if s is None else i
                        for i, s in enumerate(game.get_shapes())
                    ]
                ),
                game.game_score(),
                game.is_over(),
            )

        def do_step(action):
            reward, _ = game.step(action)
            return reward

        def valid_mask():
            mask = np.zeros(env_cfg.action_dim, dtype=bool)
            mask[game.valid_actions()] = True
            return mask

    death = env.geometry.death
    cells = env_cfg.ROWS * env_cfg.COLS
    moves = 0
    script = list(args.script.split(";")) if args.script else None
    print(
        f"Board {env_cfg.ROWS}x{env_cfg.COLS}, "
        f"{env_cfg.NUM_SHAPE_SLOTS} shape slots, engine="
        f"{'native' if use_native else 'jax'}."
    )
    print("Moves: 'SLOT ROW COL' | 'v' valid count | 'q' quit.")
    while True:
        occ, hand, score, done = state_view()
        print()
        print(render_grid(occ, death))
        print(f"score={score:.1f}  moves={moves}")
        for slot in range(env_cfg.NUM_SHAPE_SLOTS):
            sidx = int(hand[slot])
            if use_native:
                label = (
                    "(consumed)"
                    if sidx < 0
                    else "\n".join(
                        "    " + line
                        for line in render_shape(
                            bank_shape_triangles(env.bank, sidx)
                        ).splitlines()
                    )
                )
            else:
                shapes = game.get_shapes()
                label = (
                    "(consumed)"
                    if shapes[slot] is None
                    else "\n".join(
                        "    " + line
                        for line in render_shape(
                            shapes[slot].triangles
                        ).splitlines()
                    )
                )
            print(f"  slot {slot}:")
            print(label)
        if done:
            print("GAME OVER.")
            return 0
        if script is not None:
            if not script:
                return 0
            line = script.pop(0).strip()
            print(f"> {line}")
        else:
            try:
                line = input("> ").strip()
            except EOFError:
                return 0
        if line in ("q", "quit", "exit"):
            return 0
        if line == "v":
            print(f"valid placements: {int(valid_mask().sum())}")
            continue
        try:
            slot, r, c = (int(x) for x in line.split())
            action = slot * cells + r * env_cfg.COLS + c
        except ValueError:
            print("Expected: SLOT ROW COL")
            continue
        if not 0 <= action < env_cfg.action_dim:
            print("Out of range.")
            continue
        if not valid_mask()[action]:
            print("Invalid placement (would forfeit); pick another.")
            continue
        reward = do_step(action)
        moves += 1
        print(f"reward {reward:+.1f}")


def cmd_warm(args: argparse.Namespace) -> int:
    """AOT-precompile the hot programs of the run `cli train --preset
    <target>` starts, so that run begins in seconds instead of paying
    the first-dispatch compiles (docs/COMPILE_CACHE.md): afterwards the
    persistent + AOT executable caches hold that run's exact programs.
    Exit 0 when every requested program is AOT-ready, 1 when any fell
    back or failed.
    """
    import json as _json

    from .utils.helpers import enforce_platform

    bundle = resolve_preset(args.target)
    enforce_platform(args.device or "auto")

    from .utils.helpers import enable_persistent_compilation_cache
    from .warm import warm_programs

    enable_persistent_compilation_cache()
    programs = set(args.programs.split(",")) if args.programs else None
    report = warm_programs(
        bundle,
        jobs=args.jobs,
        programs=programs,
        progress=lambda msg: print(msg, file=sys.stderr, flush=True),
    )
    print(_json.dumps(report))
    # "skipped-cpu" rows are deliberate (learner programs never AOT on
    # the CPU backend; rl/trainer.py cpu_aot note) — they must not fail
    # the warm, but at least one program must actually be AOT-ready.
    rows = report["programs"]
    ok = all(r["status"] in ("aot", "skipped-cpu") for r in rows)
    return 0 if (ok and any(r["status"] == "aot" for r in rows)) else 1


def cmd_serve(args: argparse.Namespace) -> int:
    """Policy-serving front end (docs/SERVING.md): a continuous-batching
    inference service over the lockstep wave search. Many concurrent
    game sessions multiplex onto ONE compiled `serve/b<B>` search shape
    (serving/service.py); sessions admit/retire between dispatches and
    partial batches pad with frozen lanes, so fluctuating load never
    recompiles.

    Startup composes the training plumbing the ROADMAP names: AOT warm
    start through the compile cache (~0.5s when `cli warm` ran first),
    a `cli fit`-style OOM pre-flight from the serve program's AOT
    memory analysis (exit 1 when over budget — refuse to serve rather
    than OOM a shared chip), then a `health.json` heartbeat + stall
    watchdog and per-request latency records into the metrics ledger
    (`cli perf` summarizes p50/p95 per-move latency; `cli compare`
    gates the SLO).

    Traffic is the built-in simulated-session generator (`--smoke` for
    the bounded CI variant); a network transport plugs in at
    `PolicyService.open_session`/`request_move`/`dispatch`. With
    `--run-name`, `--reload-every` polls the run's checkpoints and
    hot-swaps weights between dispatches without recompiling.
    """
    import json as _json
    import os as _os
    import time as _time

    from .utils.helpers import enforce_platform

    enforce_platform(args.device or ("cpu" if args.smoke else "auto"))

    import jax

    from .utils.helpers import enable_persistent_compilation_cache

    enable_persistent_compilation_cache()

    from .config import (
        AlphaTriangleMCTSConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from .config.run_configs import load_run_configs_or_default
    from .env.engine import TriangleEnv
    from .features.core import get_feature_extractor
    from .mcts import BatchedMCTS, GumbelMCTS
    from .nn.network import NeuralNetwork
    from .serving import (
        PolicyService,
        build_serve_telemetry,
        run_simulated_load,
    )
    from .stats.persistence import CheckpointManager
    from .telemetry.health import device_memory_stats
    from .telemetry.memory import (
        BYTES_LIMIT_ENV,
        FIT_OVER,
        fit_verdict,
        fmt_bytes,
        serve_budget_bytes,
    )

    def say(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def persistence_for(run_name: str) -> "PersistenceConfig":
        p = PersistenceConfig(RUN_NAME=run_name)
        if args.root_dir:
            p = p.model_copy(update={"ROOT_DATA_DIR": args.root_dir})
        return p

    # Board/net configs: the served run's own configs.json when
    # available (the same resolution `cli eval` uses), flagship
    # defaults otherwise.
    if args.run_name:
        cfg_dir = persistence_for(args.run_name).get_run_base_dir()
    elif args.checkpoint:
        cfg_dir = Path(args.checkpoint).resolve().parent.parent
    else:
        cfg_dir = Path("/nonexistent")
    env_cfg, model_cfg = load_run_configs_or_default(cfg_dir)
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=args.sims)
    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)

    # Restore weights (optional — an untrained net still serves, which
    # is what the smoke uses).
    trainer = mgr = None
    source = "untrained"
    if args.checkpoint or args.run_name:
        from .rl import Trainer

        trainer = Trainer(net, TrainConfig(RUN_NAME=args.run_name or "serve"))
        mgr = CheckpointManager(persistence_for(args.run_name or "serve"))
        loaded = (
            mgr.restore_path(args.checkpoint, trainer.state)
            if args.checkpoint
            else mgr.restore(trainer.state)
        )
        if loaded.train_state is None:
            say("serve: no checkpoint found; serving the untrained net")
        else:
            trainer.set_state(loaded.train_state)
            trainer.sync_to_network()
            source = f"step {loaded.global_step}"

    if args.gumbel:
        mcts = GumbelMCTS(
            env, extractor, net.model, mcts_cfg, net.support, exploit=True
        )
    else:
        mcts = BatchedMCTS(env, extractor, net.model, mcts_cfg, net.support)

    serve_run = args.serve_run_name or (
        f"serve_{args.run_name}" if args.run_name else "serve"
    )
    run_dir = persistence_for(serve_run).get_run_base_dir()
    telemetry = build_serve_telemetry(
        run_dir, serve_run, env_cfg, model_cfg
    )
    from .compile_cache import get_compile_cache

    get_compile_cache().set_tracer(telemetry.tracer)
    service = PolicyService(
        env,
        extractor,
        net,
        mcts,
        slots=args.slots,
        use_gumbel=args.gumbel,
        telemetry=telemetry,
        rng_seed=args.seed,
        ladder=args.buckets,
    )
    ladder_note = (
        f", ladder {','.join(str(r) for r in service.ladder.rungs)}"
        if args.buckets
        else ""
    )
    say(
        f"serve: {source} net, board {env_cfg.ROWS}x{env_cfg.COLS}, "
        f"{args.slots} slots{ladder_note}, {args.sims} sims/move, "
        f"precision {model_cfg.INFERENCE_PRECISION}, run dir {run_dir}"
    )

    # AOT warm start: deserialize (or compile+serialize) the serve
    # search BEFORE admitting traffic — a `cli warm`-ed cache makes
    # this the ~0.5s path (docs/COMPILE_CACHE.md).
    if not args.no_warm:
        t0 = _time.time()
        aot = service.warm()
        say(
            f"serve: warm {'aot' if aot else 'jit-fallback'} "
            f"({_time.time() - t0:.1f}s)"
        )

    # OOM pre-flight (docs/OBSERVABILITY.md "Memory"): the serve
    # program's resident arguments + dispatch transient vs the device
    # limit — answered before a session is admitted.
    if not args.no_preflight:
        # Pre-flight EVERY ladder rung (a fixed-shape service is a
        # one-rung ladder): the micro-batcher may dispatch any of
        # them mid-stream, so the gate is the worst rung's budget.
        record, budget = None, 0
        for rung in service.ladder.rungs:
            rec = service.analyze(persist=True, rung=rung)
            b = serve_budget_bytes(rec)
            if rec is not None and b >= budget:
                record, budget = rec, b
        limit = None
        override = (args.limit_gb, _os.environ.get(BYTES_LIMIT_ENV, "").strip())
        if override[0] is not None:
            limit = override[0] * 2**30
        elif override[1]:
            try:
                limit = float(override[1])
            except ValueError:
                pass
        if limit is None:
            limits = [
                m.get("bytes_limit")
                for m in device_memory_stats()
                if isinstance(m.get("bytes_limit"), (int, float))
                and m.get("bytes_limit") > 0
            ]
            limit = min(limits) if limits else None
        if budget > 0:
            code, reason = fit_verdict(budget, limit)
            say(f"serve: pre-flight {fmt_bytes(budget)} — {reason}")
            if code == FIT_OVER:
                say("serve: refusing to serve an over-budget config")
                return 1
        else:
            say("serve: pre-flight skipped (no memory analysis available)")
        telemetry.record_memory(record)

    # Hot weight reload: poll the served run's checkpoints between
    # dispatches; a new step restores + swaps variables with zero
    # recompiles (the compiled search reads variables as an input).
    reload_state = {"step": mgr.latest_step() if mgr else None}

    def reload_hook(svc, dispatches: int) -> None:
        if (
            mgr is None
            or trainer is None
            or args.reload_every <= 0
            or dispatches % args.reload_every
        ):
            return
        latest = mgr.latest_step()
        if latest is None or latest == reload_state["step"]:
            return
        loaded = mgr.restore(trainer.state)
        if loaded.train_state is None:
            return
        trainer.set_state(loaded.train_state)
        trainer.sync_to_network()
        reload_state["step"] = latest
        svc.reload_weights()
        say(f"serve: hot-reloaded weights at checkpoint step {latest}")

    telemetry.start()
    waves = []
    try:
        deadline = (
            None
            if args.duration is None
            else _time.monotonic() + args.duration
        )
        while True:
            stats = run_simulated_load(
                service,
                total_sessions=args.sessions,
                # Under a ladder, demand may exceed the base rung —
                # that sustained pressure is what drives the
                # micro-batcher's walk-up (loadgen clamps to the
                # ladder's top rung).
                concurrency=(
                    service.max_slots if args.buckets else args.slots
                ),
                max_moves=args.max_moves,
                seed=args.seed + len(waves),
                tick_every=args.tick_every,
                reload_hook=reload_hook,
                progress=say,
            )
            waves.append(stats)
            if args.smoke or deadline is None:
                break
            if _time.monotonic() >= deadline:
                break
    except KeyboardInterrupt:
        say("serve: interrupted; draining")
    finally:
        service.tick()
        telemetry.close(step=service.dispatch_count)

    report = {
        "run": serve_run,
        "source": source,
        "slots": args.slots,
        "buckets": list(service.ladder.rungs),
        "precision": model_cfg.INFERENCE_PRECISION,
        "rung_switches": service.rung_switches,
        "sims": args.sims,
        "waves": len(waves),
        "sessions_served": sum(w["sessions_served"] for w in waves),
        "moves_served": sum(w["moves_served"] for w in waves),
        "dispatches": service.dispatch_count,
        "weight_reloads": service.weight_reloads,
        "ledger": str(run_dir / "metrics.jsonl"),
        **service.serve_stats(drain=False),
    }
    print(_json.dumps(report))
    # The smoke gate: sessions actually served, latency records on the
    # ledger (`make serve-smoke` then runs cli perf/compare on top).
    if args.smoke:
        ok = report["sessions_served"] >= args.sessions and (
            run_dir / "metrics.jsonl"
        ).exists()
        return 0 if ok else 1
    return 0


def cmd_fleet(args: argparse.Namespace) -> int:
    """Fault-tolerant serve fleet (docs/SERVING.md "Fleet"): N
    PolicyService replica subprocesses behind a least-queue-depth
    router with health-gated admission, per-request timeout + retry
    onto a different replica, optional hedging, and bounded-queue load
    shedding. Replica lifecycle reuses the training supervisor's
    machinery — deaths are doctor-classified since spawn, restarted
    with backoff under a restart budget (a serve-family quarantine
    respawns onto a halved bucket), and every lifecycle/routing
    decision lands crash-safe in the run's fleet.jsonl.

    THIS PARENT NEVER IMPORTS JAX — the same contract as `cli
    supervise`/`cli doctor` (benchmarks/fleet_smoke.py pins it with an
    import guard). JAX lives in the replica children
    (`python -m alphatriangle_tpu.serving.replica`), one compiled
    `serve/b<B>` program each.

    Drives a storm of episode requests through the router and prints
    one JSON report line; `--smoke` additionally gates on the
    zero-lost-requests invariant. `--chaos-kill-after N` /
    `--reload-after N` are the smoke's mid-storm chaos/rolling-swap
    triggers.
    """
    import json as _json
    import threading as _threading
    import time as _time

    from .serving.fleet import (
        FleetSupervisor,
        local_chip_count,
        run_fleet_load,
    )
    from .supervise.policy import RecoveryPolicy

    run_dir = _resolve_run_dir(args.run_name, args.root_dir)
    if run_dir is None:
        return 2
    run_dir.mkdir(parents=True, exist_ok=True)

    def policy_factory() -> RecoveryPolicy:
        return RecoveryPolicy(
            max_restarts=args.max_restarts,
            circuit_breaker_deaths=args.circuit_breaker,
            backoff_base_s=args.backoff_base,
            backoff_max_s=args.backoff_max,
            quarantine_after=args.quarantine_after,
        )

    replica_extra = [
        "--health-interval",
        str(args.replica_health_interval),
        "--dispatch-min-deadline",
        str(args.replica_dispatch_min_deadline),
        "--dispatch-first-deadline",
        str(args.replica_dispatch_first_deadline),
        "--dispatch-watchdog-poll",
        str(args.replica_watchdog_poll),
        "--tick-every",
        str(args.tick_every),
    ]
    if args.buckets:
        # Replicas micro-batch on the SAME rung set the supervisor's
        # quarantine walks down (serving/buckets.py — one ladder, two
        # walkers).
        replica_extra += ["--buckets", args.buckets]
    fleet = FleetSupervisor(
        run_dir,
        replicas=args.replicas,
        slots=args.slots,
        sims=args.sims,
        seed=args.seed,
        configs_dir=run_dir,
        ladder=args.buckets,
        replica_extra_argv=replica_extra,
        policy_factory=policy_factory,
        probe_deadline_s=args.probe_deadline,
        poll_s=args.poll,
        spawn_timeout_s=args.spawn_timeout,
        chips=local_chip_count(),
    )
    router = fleet.build_router(
        timeout_s=args.timeout,
        retries=args.retries,
        backoff_base_s=args.route_backoff_base,
        backoff_max_s=args.route_backoff_max,
        hedge_after_s=args.hedge_after,
        max_inflight=args.max_queue,
    )

    chaos_lock = _threading.Lock()
    state = {"killed": False, "reload": None}

    def on_complete(n: int) -> None:
        with chaos_lock:
            kill_now = (
                args.chaos_kill_after > 0
                and not state["killed"]
                and n >= args.chaos_kill_after
            )
            if kill_now:
                state["killed"] = True
            reload_now = (
                args.reload_after > 0
                and state["reload"] is None
                and n >= args.reload_after
            )
            if reload_now:
                state["reload"] = _threading.Thread(
                    target=fleet.rolling_reload,
                    name="fleet-reload",
                    daemon=True,
                )
        if kill_now:
            victim = fleet.kill_replica()
            print(f"fleet: chaos-killed {victim}", file=sys.stderr)
        if reload_now:
            state["reload"].start()

    print(
        f"fleet: {args.replicas} replicas x {args.slots} slots, "
        f"{args.requests} requests, run dir {run_dir}",
        file=sys.stderr,
    )
    try:
        fleet.start()
        storm = run_fleet_load(
            router,
            fleet,
            requests=args.requests,
            concurrency=args.concurrency,
            max_moves=args.max_moves,
            seed=args.seed,
            timeout_s=args.timeout,
            on_complete=on_complete,
        )
        if state["reload"] is not None:
            state["reload"].join(timeout=180.0)
        # Let pending respawn chains settle so the death -> verdict ->
        # respawn -> readmit sequence completes on fleet.jsonl before
        # the report (and the smoke's ledger assertions) read it.
        deadline = _time.monotonic() + args.settle
        while _time.monotonic() < deadline:
            if all(
                h.name in fleet.gaveup or h.routable for h in fleet.handles
            ):
                break
            _time.sleep(0.2)
    finally:
        fleet.stop()

    report = {
        "schema": "alphatriangle.fleet.v1",
        "run": args.run_name or run_dir.name,
        "replicas": args.replicas,
        "slots": args.slots,
        **storm,
        "fleet": fleet.summary(),
        "ledger": str(run_dir / "fleet.jsonl"),
    }
    # Aggregated whole-fleet scrape surface + SLO snapshot
    # (telemetry/slo.py): rejection codes as DISTINCT counters, per-SLO
    # burn rates as gauges — written after the storm so one textfile
    # describes the whole run.
    from .telemetry.ledger import read_ledger as _read_ledger
    from .telemetry.perf import summarize_fleet as _summarize_fleet
    from .telemetry.slo import (
        FLEET_PROM_FILENAME,
        evaluate_slos,
        write_fleet_prometheus,
    )

    slo_report = evaluate_slos(run_dir)
    write_fleet_prometheus(
        run_dir / FLEET_PROM_FILENAME,
        _summarize_fleet(_read_ledger(run_dir / "fleet.jsonl")),
        slo_report,
        run_name=args.run_name or run_dir.name,
    )
    report["slo"] = slo_report["status"]
    print(_json.dumps(report))
    if args.smoke:
        accounted = (
            storm["completed"] + storm["shed"] == storm["terminal"]
            and storm["terminal"] == storm["requests"]
        )
        ok = storm["lost"] == 0 and storm["completed"] > 0 and accounted
        return 0 if ok else 1
    return 0


def cmd_league(args: argparse.Namespace) -> int:
    """Experience-flywheel mode (docs/LEAGUE.md): one process runs the
    learner while a `PolicyService` plays matchmade games against a
    league of past checkpoints, the served trajectories flowing into
    the replay ring interleaved with self-play at --mix. The pool is
    seeded from --pool-from's checkpoints; the flywheel run's own
    promotions grow it. Board/net configs come from the pool run's
    configs.json so pool checkpoints actually load.

    Emits one JSON report line (pool size, ratings, promotions,
    ingest) — the `make league-smoke` contract."""
    import json as _json

    from .config import (
        AlphaTriangleMCTSConfig,
        LeagueConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from .config.run_configs import load_run_configs_or_default
    from .league import LEAGUE_FILENAME, LIVE_ID, LeaguePool, run_flywheel

    def persistence_for(run_name: str) -> "PersistenceConfig":
        p = PersistenceConfig(RUN_NAME=run_name)
        if args.root_dir:
            p = p.model_copy(update={"ROOT_DATA_DIR": args.root_dir})
        return p

    overrides: dict = {
        # Auto-resume would redirect RUN_NAME at the newest
        # checkpointed run — typically the --pool-from source itself —
        # and train INTO it. The flywheel names its run explicitly.
        "AUTO_RESUME_LATEST": False,
    }
    if args.run_name is not None:
        overrides["RUN_NAME"] = args.run_name
    if args.seed is not None:
        overrides["RANDOM_SEED"] = args.seed
    if args.steps is not None:
        overrides["MAX_TRAINING_STEPS"] = args.steps
    if args.self_play_batch is not None:
        overrides["SELF_PLAY_BATCH_SIZE"] = args.self_play_batch
    if args.batch_size is not None:
        overrides["BATCH_SIZE"] = args.batch_size
    if args.buffer_capacity is not None:
        overrides["BUFFER_CAPACITY"] = args.buffer_capacity
    if args.min_buffer is not None:
        overrides["MIN_BUFFER_SIZE_TO_TRAIN"] = args.min_buffer
    if args.rollout_chunk is not None:
        overrides["ROLLOUT_CHUNK_MOVES"] = args.rollout_chunk
    if args.checkpoint_freq is not None:
        overrides["CHECKPOINT_SAVE_FREQ_STEPS"] = args.checkpoint_freq
    if args.device_replay is not None:
        overrides["DEVICE_REPLAY"] = args.device_replay
    if args.max_moves is not None:
        overrides["MAX_EPISODE_MOVES"] = args.max_moves
    if args.device is not None:
        overrides["DEVICE"] = args.device
    train_config = TrainConfig(**overrides)

    league_kw: dict = {}
    if args.slots is not None:
        league_kw["LEAGUE_SLOTS"] = args.slots
    if args.games is not None:
        league_kw["GAMES_PER_ROUND"] = args.games
    if args.mix is not None:
        league_kw["LEAGUE_MIX_RATIO"] = args.mix
    if args.max_moves is not None:
        league_kw["MAX_GAME_MOVES"] = args.max_moves
    if args.reload_every is not None:
        league_kw["RELOAD_EVERY_STEPS"] = args.reload_every
    if args.staleness_window is not None:
        league_kw["STALENESS_WINDOW"] = args.staleness_window
    if args.promotion_games is not None:
        league_kw["PROMOTION_MIN_GAMES"] = args.promotion_games
    if args.promotion_win_rate is not None:
        league_kw["PROMOTION_WIN_RATE"] = args.promotion_win_rate
    if args.exploration_floor is not None:
        league_kw["EXPLORATION_FLOOR"] = args.exploration_floor
    league_config = LeagueConfig(**league_kw)

    # Board/net configs from the pool source run: the pool's
    # checkpoints must restore into this geometry.
    cfg_dir = persistence_for(args.pool_from).get_run_base_dir()
    env_config, model_config = load_run_configs_or_default(cfg_dir)
    mcts_config = (
        AlphaTriangleMCTSConfig(max_simulations=args.sims)
        if args.sims is not None
        else None
    )

    telemetry_config = None
    if args.no_telemetry:
        from .config import TelemetryConfig

        telemetry_config = TelemetryConfig(ENABLED=False)

    persistence_config = persistence_for(train_config.RUN_NAME)
    code = run_flywheel(
        train_config=train_config,
        league_config=league_config,
        env_config=env_config,
        model_config=model_config,
        mcts_config=mcts_config,
        persistence_config=persistence_config,
        telemetry_config=telemetry_config,
        pool_from=args.pool_from,
        use_tensorboard=False,
    )

    run_dir = persistence_config.get_run_base_dir()
    pool = LeaguePool(run_dir / LEAGUE_FILENAME)
    report = {
        "run": train_config.RUN_NAME,
        "pool_from": args.pool_from,
        "exit": code,
        "pool_size": len(pool),
        "promotions": pool.promotions,
        "live_elo": round(pool.rating(LIVE_ID), 2),
        "ratings": {
            m: round(pool.rating(m), 2) for m in pool.member_ids()
        },
        "league_jsonl": str(run_dir / LEAGUE_FILENAME),
        "ledger": str(run_dir / "metrics.jsonl"),
    }
    print(_json.dumps(report))
    return code


def cmd_fit(args: argparse.Namespace) -> int:
    """OOM pre-flight gate (docs/OBSERVABILITY.md "Memory"): compose
    the static per-device memory budget of the run `cli train --preset
    <target>` starts —
    train-state tree bytes + replay-ring bytes + AOT-analyzed program
    memory (`compiled.memory_analysis()`, never executed) — and check
    it against the device byte limit BEFORE a scarce accelerator
    window is burned on an OOM. Exit 0 = fits, 1 = over budget, 2 =
    no device limit known (set ALPHATRIANGLE_DEVICE_BYTES_LIMIT or
    --limit-gb to assert one)."""
    import json as _json
    import os as _os

    from .utils.helpers import enforce_platform

    bundle = resolve_preset(args.target)
    enforce_platform(args.device or "auto")

    import jax

    from .autotune.artifact import serve_ladder
    from .telemetry.memory import (
        estimate_fit,
        fit_verdict,
        fmt_bytes,
        resolve_bytes_limit,
    )
    from .training.setup import wants_device_ring
    from .utils.helpers import enable_persistent_compilation_cache

    backend = jax.default_backend()
    enable_persistent_compilation_cache()
    environ = dict(_os.environ)
    train = bundle["train"]
    device_replay = wants_device_ring(train)
    fused_k = max(1, train.FUSED_LEARNER_STEPS)
    if train.FUSED_MEGASTEP:
        # One megastep holds a rollout's whole learner share.
        fused_k = train.LEARNER_STEPS_PER_ROLLOUT or fused_k
    label = bundle["description"]
    print(
        f"fit: backend={backend} {label}: "
        f"batch={train.SELF_PLAY_BATCH_SIZE} "
        f"chunk={train.ROLLOUT_CHUNK_MOVES} lbatch={train.BATCH_SIZE} "
        f"k={fused_k} ring={train.BUFFER_CAPACITY} "
        f"device_replay={device_replay}",
        file=sys.stderr,
        flush=True,
    )
    report = estimate_fit(
        bundle["env"],
        bundle["model"],
        bundle["mcts"],
        train,
        fused_k=fused_k,
        device_replay=device_replay,
        # The megastep's argument list includes the ring, so analyzing
        # it allocates the run's own ring (rl/megastep.py): done for
        # the runs that dispatch it.
        megastep=train.FUSED_MEGASTEP,
        # --serve additionally analyzes the policy service's
        # `serve/b<B>` search program at the lane count and persists
        # its .mem.json sidecar (serving/service.py; docs/SERVING.md);
        # a tuned artifact's ladder is analyzed rung by rung, since the
        # micro-batcher can dispatch any of them.
        serve=args.serve,
        serve_buckets=serve_ladder(bundle),
        progress=lambda msg: print(msg, file=sys.stderr, flush=True),
    )
    budget = report["budget"]
    # Per-device byte limit: explicit flag wins, then the env override,
    # then the smallest limit any local device reports (conservative).
    limit, source = resolve_bytes_limit(args.limit_gb, environ)
    code, reason = fit_verdict(budget["total_bytes"], limit)
    if args.json:
        print(
            _json.dumps(
                {
                    "schema": "alphatriangle.fit.v1",
                    "scale": label,
                    "backend": backend,
                    "budget": budget,
                    "bytes_limit": limit,
                    "limit_source": source,
                    "exit": code,
                    "reason": reason,
                    "records": report["records"],
                }
            )
        )
        return code
    print(f"fit {label} on {backend}")
    for label, key in (
        ("train state", "train_state_bytes"),
        ("replay ring (device)", "replay_ring_bytes"),
        ("rollout residency", "rollout_resident_bytes"),
        ("program transient", "program_transient_bytes"),
    ):
        print(f"  {label:<22} {fmt_bytes(budget[key]):>12}")
    print(f"  {'TOTAL (per device)':<22} {fmt_bytes(budget['total_bytes']):>12}")
    print(
        f"  limit                  {fmt_bytes(limit):>12}"
        + (f"  [{source}]" if limit is not None else "")
    )
    print(reason)
    return code


def cmd_mem(args: argparse.Namespace) -> int:
    """Memory-attribution table for a run, rendered from its artifacts
    alone (`metrics.jsonl` `kind: "memory"` + `"util"` records) —
    never imports JAX, safe beside a wedged chip. Exit 0 on a usable
    table, 2 when the run has no memory records (predates the memory
    ledger, or telemetry was disabled)."""
    import json as _json

    from .telemetry.ledger import read_ledger, resolve_ledger_path
    from .telemetry.memory import (
        attribution_rows,
        compose_budget,
        fmt_bytes,
    )

    target = Path(args.run) if args.run else None
    if target is not None and target.exists():
        ledger = resolve_ledger_path(target)
    else:
        run_dir = _resolve_run_dir(args.run, args.root_dir)
        if run_dir is None:
            return 2
        ledger = resolve_ledger_path(run_dir)
    if ledger is None:
        print(f"no metrics ledger for {args.run}", file=sys.stderr)
        return 2
    records = read_ledger(ledger, kinds={"memory"})
    utils = read_ledger(ledger, kinds={"util"})
    observed = next(
        (
            u
            for u in reversed(utils)
            if isinstance(u.get("mem_bytes_in_use"), (int, float))
        ),
        None,
    )
    if not records and observed is None:
        print(
            f"{ledger}: no memory records (run predates the memory "
            "ledger, or telemetry was disabled)",
            file=sys.stderr,
        )
        return 2
    budget = compose_budget(records)
    if args.json:
        print(
            _json.dumps(
                {
                    "source": str(ledger),
                    "records": records,
                    "budget": budget,
                    "observed": observed,
                }
            )
        )
        return 0
    print(f"mem {ledger}")
    rows = attribution_rows(records)
    if rows:
        width = max(max(len(r[0]) for r in rows), 9)
        print(f"  {'component':<{width}}  {'bytes':>12}  detail")
        for component, total, detail in rows:
            print(f"  {component:<{width}}  {fmt_bytes(total):>12}  {detail}")
        print(
            f"  static budget (per device): "
            f"{fmt_bytes(budget['total_bytes'])} = "
            f"state {fmt_bytes(budget['train_state_bytes'])}"
            f" + ring {fmt_bytes(budget['replay_ring_bytes'])}"
            f" + rollout {fmt_bytes(budget['rollout_resident_bytes'])}"
            f" + transient {fmt_bytes(budget['program_transient_bytes'])}"
        )
    if observed is not None:
        limit = observed.get("mem_bytes_limit")
        util = observed.get("mem_utilization")
        print(
            f"  observed: {fmt_bytes(observed.get('mem_bytes_in_use'))} "
            f"in use, peak {fmt_bytes(observed.get('mem_peak_bytes_in_use'))}"
            + (
                f", limit {fmt_bytes(limit)}"
                + (f" ({util:.1%} used)" if isinstance(util, (int, float)) else "")
                if limit
                else ""
            )
            + f" (step {observed.get('step')})"
        )
    return 0


def cmd_roofline(args: argparse.Namespace) -> int:
    """Roofline attribution report for a run: per-program arithmetic
    intensity vs the device machine balance (compute- vs memory-bound,
    achieved-vs-roofline fraction) plus chip-idle gap forensics over
    the flight timeline (docs/OBSERVABILITY.md "Roofline & gap
    attribution"). Rendered from run artifacts alone (`metrics.jsonl`
    `kind:"cost"` records, `flight.jsonl`, `trace.json`) — never
    imports JAX, safe beside a wedged chip. Missing/corrupt/legacy
    cost sidecars degrade to "—" cells, never raise. Exit 0 on a
    usable report, 2 when the run has neither cost records nor a
    flight timeline (predates the roofline plane, or telemetry was
    disabled)."""
    import json as _json

    from .telemetry.flight import FLIGHT_FILENAME, read_flight
    from .telemetry.ledger import read_ledger, resolve_ledger_path
    from .telemetry.perf import summarize_utilization
    from .telemetry.roofline import summarize_roofline

    target = Path(args.run) if args.run else None
    if target is not None and target.exists():
        ledger = resolve_ledger_path(target)
    else:
        run_dir = _resolve_run_dir(args.run, args.root_dir)
        if run_dir is None:
            return 2
        ledger = resolve_ledger_path(run_dir)
    if ledger is None:
        print(f"no metrics ledger for {args.run}", file=sys.stderr)
        return 2
    run_dir = ledger.parent
    records = read_ledger(ledger)
    # Device identity + peak FLOP/s from the same summary `cli perf`
    # renders (the writer stamped them onto util records).
    util = summarize_utilization(records) or {}
    summary = summarize_roofline(
        [r for r in records if r.get("kind") == "cost"],
        read_flight(run_dir / FLIGHT_FILENAME),
        device_kind=util.get("device_kind") or "",
        peak_tflops=util.get("peak_bf16_tflops"),
        trace_path=run_dir / "trace.json",
    )
    if summary is None:
        print(
            f"{run_dir}: no cost records or flight timeline (run "
            "predates the roofline plane, or telemetry was disabled)",
            file=sys.stderr,
        )
        return 2
    if args.json:
        summary["source"] = str(ledger)
        print(_json.dumps(summary))
        return 0
    peak = summary.get("peak_bf16_tflops")
    hbm = summary.get("peak_hbm_gbps")
    print(f"roofline {run_dir}")
    print(
        f"  device       {summary.get('device_kind') or '?'}"
        f"   peak bf16 {_fmt_cell(peak, ',.0f', 1, ' TFLOP/s') if peak else 'unknown'}"
        f"   hbm {_fmt_cell(hbm, ',.0f', 1, ' GB/s') if hbm else 'unknown'}"
        + (
            f" [{summary.get('peak_hbm_source')}]"
            if summary.get("peak_hbm_source") not in (None, "unknown")
            else ""
        )
        + (
            f"   balance {_fmt_cell(summary.get('machine_balance_flops_per_byte'), ',.0f', 1, ' FLOP/B')}"
            if summary.get("machine_balance_flops_per_byte") is not None
            else ""
        )
    )
    attrib = summary.get("attribution")
    if attrib:
        print(
            f"  attribution  wall {_fmt_cell(attrib.get('wall_s'), ',.1f', 1, 's')}"
            f"   dispatch {_fmt_cell(attrib.get('dispatch_s'), ',.1f', 1, 's')}"
            f"   idle {_fmt_cell(attrib.get('chip_idle_fraction'), ',.1f', 100.0, '%')}"
            f"   attributed {_fmt_cell(attrib.get('attributed_fraction'), ',.1f', 100.0, '%')}"
            f"   dispatches {_fmt_cell(attrib.get('dispatches'), ',.0f')}"
        )
        gaps = attrib.get("gaps") or {}
        gap_text = "   ".join(
            f"{cat} {_fmt_cell(s, ',.2f', 1, 's')}"
            for cat, s in gaps.items()
            if isinstance(s, (int, float))
        )
        if gap_text:
            print(f"  gaps         {gap_text}")
    else:
        print("  attribution  — (no flight timeline)")
    programs = summary.get("programs") or []
    if programs:
        width = max(max(len(p["program"]) for p in programs), 7)
        print(
            f"  {'program':<{width}}  {'count':>6}  {'p50':>9}  {'total':>9}"
            f"  {'gflops':>9}  {'intensity':>10}  {'bound':>7}  {'roofline':>8}"
        )
        for p in programs:
            print(
                f"  {p['program']:<{width}}"
                f"  {_fmt_cell(p.get('count'), ',.0f'):>6}"
                f"  {_fmt_cell(p.get('wall_s_p50'), ',.1f', 1e3, 'ms'):>9}"
                f"  {_fmt_cell(p.get('wall_s_total'), ',.1f', 1, 's'):>9}"
                f"  {_fmt_cell(p.get('flops'), ',.2f', 1e-9):>9}"
                f"  {_fmt_cell(p.get('intensity'), ',.1f'):>10}"
                f"  {p.get('bound') or '—':>7}"
                f"  {_fmt_cell(p.get('roofline_fraction'), ',.2f', 100.0, '%'):>8}"
            )
    return 0


def cmd_lint(args: argparse.Namespace) -> int:
    """graftlint: the JAX-hazard static analyzer (docs/ANALYSIS.md).

    Walks the package AST for the six hazard classes this repo has
    actually hit (use-after-donation, host-sync-in-hot-path,
    mixed-placement-dispatch, unbracketed-hot-dispatch, debug-artifact,
    untracked-rng). Never imports JAX — runs in CI images and beside
    a process that holds the chip, like `cli mem` and `cli doctor`
    (pinned by an import-guard test).

    Exit 0 clean / 1 findings or stale baseline entries / 2 parse
    error (or unknown --rule)."""
    import json as _json

    from .analysis import run_lint, write_baseline

    root = Path(args.path) if args.path else Path(__file__).resolve().parent
    if not root.exists():
        print(f"lint root {root} does not exist", file=sys.stderr)
        return 2
    if args.baseline is not None:
        baseline = Path(args.baseline)
    else:
        # Checked-in default: lint_baseline.json beside the scanned
        # tree (repo root for the package default), else inside it.
        candidates = [
            root.parent / "lint_baseline.json",
            root / "lint_baseline.json",
        ]
        baseline = next((c for c in candidates if c.exists()), None)
    try:
        report = run_lint(
            root, rule_names=args.rule or None, baseline_path=baseline
        )
    except ValueError as e:  # unknown rule / corrupt baseline
        print(f"lint: {e}", file=sys.stderr)
        return 2
    if args.write_baseline:
        target = baseline or root.parent / "lint_baseline.json"
        write_baseline(target, report.findings)
        print(
            f"baseline written: {target} "
            f"({len(report.findings)} entr"
            f"{'y' if len(report.findings) == 1 else 'ies'})"
        )
        return 0
    if args.json:
        payload = report.as_dict()
        payload["baseline_path"] = str(baseline) if baseline else None
        print(_json.dumps(payload))
    else:
        print(report.render())
    return report.exit_code


def cmd_slo(args: argparse.Namespace) -> int:
    """Fleet SLO report (telemetry/slo.py): availability, p95 move
    latency, and dispatch success evaluated as error budgets with
    multi-window burn-rate alerts, purely from records the fleet
    already ledgered. Never imports JAX.

    Exit code IS the alert state: 0 every SLO within budget, 1 at
    least one window burning past its threshold, 2 no data (not a
    fleet run dir, or nothing ledgered yet) — pinned by tests and the
    trace-smoke's healthy/brownout contract."""
    import json as _json

    from .telemetry.slo import (
        FLEET_PROM_FILENAME,
        SLO_EXIT_CODES,
        evaluate_slos,
        slo_status_line,
        write_fleet_prometheus,
    )

    target = Path(args.run) if args.run else None
    if target is not None and target.is_dir():
        run_dir = target
    else:
        run_dir = _resolve_run_dir(args.run, args.root_dir)
        if run_dir is None:
            return SLO_EXIT_CODES["no-data"]
    windows = None
    if args.window:
        try:
            windows = tuple(
                (float(w.split(":")[0]), float(w.split(":")[1]))
                for w in args.window
            )
        except (ValueError, IndexError):
            print(
                f"bad --window {args.window!r}: want SECONDS:BURN "
                "(e.g. 300:14.4)",
                file=sys.stderr,
            )
            return SLO_EXIT_CODES["no-data"]
    kw = {"windows": windows} if windows else {}
    report = evaluate_slos(
        run_dir,
        now=args.now,
        latency_threshold_ms=args.latency_threshold,
        **kw,
    )
    if args.prom:
        from .telemetry.ledger import read_ledger
        from .telemetry.perf import summarize_fleet

        write_fleet_prometheus(
            run_dir / FLEET_PROM_FILENAME,
            summarize_fleet(read_ledger(run_dir / "fleet.jsonl")),
            report,
            run_name=run_dir.name,
        )
    if args.json:
        print(_json.dumps(report))
        return int(report["exit_code"])
    print(f"slo {run_dir}")
    print(f"  {slo_status_line(report)}")
    for slo in report["slos"]:
        print(
            f"  {slo['name']:<18} objective {slo['objective']:.2%}  "
            f"budget {slo['error_budget']:.2%}  [{slo['status']}]"
        )
        for w in slo["windows"]:
            flag = "  BURNING" if w["burning"] else ""
            print(
                f"    window {w['window_s']:>6g}s  "
                f"total {w['total']:>10,.0f}  bad {w['bad']:>8,.0f}  "
                f"err {w['error_rate']:.4f}  "
                f"burn x{w['burn_rate']:,.1f} "
                f"(alert at x{w['burn_threshold']:g}){flag}"
            )
    print(
        f"  status    {report['status']} "
        f"(exit {report['exit_code']})"
    )
    return int(report["exit_code"])


def cmd_doctor(args: argparse.Namespace) -> int:
    """Postmortem window forensics: classify how a run ended from its
    on-disk evidence alone (flight ring + health.json + wedge report +
    metrics ledger). Never imports JAX — safe to run beside (or after)
    a wedged chip, which is the whole point: the chip window that
    produced the artifacts may be unusable.

    Exit code IS the verdict (telemetry/flight.py DOCTOR_EXIT_CODES):
    0 clean, 2 never-started, 3 compile-hung, 4 dispatch-hung,
    5 host-stall, 6 oom, 7 preempted.
    (Related process exit codes, docs/OBSERVABILITY.md: 113 = dispatch
    watchdog wedge, 114 = preemption absorbed, 115 = `cli supervise`
    gave up.)"""
    import json as _json

    from .telemetry.flight import (
        FLIGHT_FILENAME,
        PREEMPT_REPORT_FILENAME,
        WEDGE_REPORT_FILENAME,
        classify_run,
        read_flight,
        read_preempt_report,
        read_wedge_report,
    )
    from .telemetry.health import read_health
    from .telemetry.ledger import read_ledger, resolve_ledger_path

    target = Path(args.run) if args.run else None
    if target is not None and target.exists():
        run_dir = target if target.is_dir() else target.parent
    else:
        run_dir = _resolve_run_dir(args.run, args.root_dir)
        if run_dir is None:
            return 2
    if (run_dir / "fleet.jsonl").exists():
        # Fleet-parent run dir: no learner heartbeat, no device
        # dispatches of its own — classify_run would misread it as
        # never-started. Classify from the fleet ledger + per-replica
        # death verdicts instead (serving/fleet.py classify_fleet).
        from .serving.fleet import classify_fleet

        verdict = classify_fleet(run_dir)
        if args.json:
            verdict["run_dir"] = str(run_dir)
            print(_json.dumps(verdict))
            return int(verdict["exit_code"])
        ev = verdict["evidence"]
        print(f"doctor {run_dir} (fleet parent)")
        print(
            f"  verdict   {verdict['verdict']}"
            + (
                f"  ({verdict['program']} [{verdict['family']}])"
                if verdict.get("program")
                else ""
            )
        )
        if verdict.get("detail"):
            print(f"  detail    {verdict['detail']}")
        print(
            f"  evidence  {ev['fleet_events']} fleet events, "
            f"{ev['deaths']} deaths, {ev['respawns']} respawns, "
            f"{ev['evictions']} evictions, {len(ev['gaveup'])} gave up"
            + (", fleet-stop" if ev["fleet_stop"] else ", NO fleet-stop")
            + (", storm summary" if ev.get("storm_summary") else "")
            + (
                f", {ev['unsealed_route_intents']} unsealed route "
                "intent(s)"
                if ev.get("unsealed_route_intents")
                else ""
            )
        )
        return int(verdict["exit_code"])
    flight = read_flight(run_dir / FLIGHT_FILENAME)
    health = read_health(run_dir / "health.json")
    wedge = read_wedge_report(run_dir / WEDGE_REPORT_FILENAME)
    preempt = read_preempt_report(run_dir / PREEMPT_REPORT_FILENAME)
    ledger = resolve_ledger_path(run_dir)
    utils = read_ledger(ledger, kinds={"util"}) if ledger else []
    # Progress-beacon forensics (telemetry/device_stats.py): the newest
    # beacons.jsonl row names the phase a hung program last announced.
    # Missing file (legacy run / never armed) -> None, zero new output.
    from .telemetry.device_stats import describe_beacon, last_beacon

    beacon = last_beacon(run_dir)
    verdict = classify_run(
        flight,
        health=health,
        utils=utils,
        wedge=wedge,
        preempt=preempt,
        beacon=beacon,
    )
    if args.json:
        verdict["run_dir"] = str(run_dir)
        print(_json.dumps(verdict))
        return int(verdict["exit_code"])
    ev = verdict["evidence"]
    print(f"doctor {run_dir}")
    print(
        f"  verdict   {verdict['verdict']}"
        + (
            f"  ({verdict['program']} [{verdict['family']}])"
            if verdict.get("program")
            else ""
        )
    )
    if verdict.get("detail"):
        print(f"  detail    {verdict['detail']}")
    if verdict.get("last_beacon"):
        print(f"  beacon    {describe_beacon(verdict['last_beacon'])}")
    print(
        f"  evidence  {ev['intents']} intents, {ev['seals']} seals, "
        f"{ev['unsealed']} unsealed"
        + (", wedge report" if ev["wedge_report"] else "")
        + (", preempt report" if ev.get("preempt_report") else "")
        + (", stalled heartbeat" if ev["stalled"] else "")
        + (
            f", mem {ev['mem_utilization']:.0%}"
            if isinstance(ev.get("mem_utilization"), float)
            else ""
        )
    )
    return int(verdict["exit_code"])


def cmd_supervise(args: argparse.Namespace) -> int:
    """Self-healing parent for `cli train` / `cli league`: spawn the
    child, classify every death with the doctor's evidence, and apply
    the verdict->action matrix (restart from the latest committed
    checkpoint with backoff, degrade on OOM, quarantine a repeatedly
    wedging program family, give up past the restart budget). JAX-free
    like `cli doctor` — the parent outlives a wedged chip.

    Exits 0 when the child completes, 115 when the policy gives up,
    or the child's own code after a forwarded SIGTERM/SIGINT (114 for
    an absorbed preemption). Events land in runs/<run>/supervisor.jsonl
    (docs/ROBUSTNESS.md)."""
    from .supervise import RecoveryPolicy, Supervisor

    child = list(args.child or [])
    if child and child[0] == "--":
        child = child[1:]
    if not child:
        child = ["train"]
    if child[0] in ("train", "league"):
        # Pin the child to the supervised run dir: the restarted child
        # must resume ITS run, not auto-resume-redirect to whichever
        # run dir is newest, and train/league both restore from their
        # named run's latest valid checkpoint unconditionally.
        if "--run-name" not in child:
            child += ["--run-name", args.run_name]
        if args.root_dir and "--root-dir" not in child:
            child += ["--root-dir", args.root_dir]
        if child[0] == "train" and "--no-auto-resume" not in child:
            child.append("--no-auto-resume")
    run_dir = _resolve_run_dir(args.run_name, args.root_dir)
    if run_dir is None:
        return 2
    policy = RecoveryPolicy(
        max_restarts=args.max_restarts,
        circuit_breaker_deaths=args.circuit_breaker,
        backoff_base_s=args.backoff_base,
        backoff_max_s=args.backoff_max,
        quarantine_after=args.quarantine_after,
    )
    argv = [sys.executable, "-m", "alphatriangle_tpu.cli", *child]
    print(f"supervise: {run_dir}\n  child: {' '.join(child)}")
    return Supervisor(argv, run_dir, policy=policy).run()


def _tune_axes(train, fused_k: int, device_count: int) -> tuple:
    """Default (batches, capacities, chunks, fused_ks, dps) around a
    base TrainConfig.

    Grids bracket the base shapes: the point of the search is to
    discover how much LARGER than the hand-picked config the chip can
    actually go, so each axis extends above the base value. Every axis
    has a flag that replaces it."""
    b0 = train.SELF_PLAY_BATCH_SIZE
    cap0 = train.BUFFER_CAPACITY
    t0 = train.ROLLOUT_CHUNK_MOVES
    batches = [max(1, b0 // 2), b0, b0 * 2, b0 * 4]
    capacities = [cap0, cap0 * 5, cap0 * 10]
    chunks = [t0, t0 * 2]
    fused_ks = [fused_k, fused_k * 2]
    dps = [1]
    if device_count > 1:
        dps.append(device_count)
    return batches, capacities, chunks, fused_ks, dps


def cmd_tune(args: argparse.Namespace) -> int:
    """Fit-driven offline autotuner (docs/AUTOTUNE.md).

    Searches the (SELF_PLAY_BATCH_SIZE, BUFFER_CAPACITY, chunk T,
    fused K, dp, geometry) space for the feasible config maximizing
    PREDICTED games/hour — feasibility from `estimate_fit`'s AOT
    memory analysis (programs are compiled, never executed; no chip
    window is burned), the objective from the analytic FLOPs model
    calibrated against ledger history (`--calibrate`). Emits
    `runs/<run>/tuned_preset.json`, consumable by `cli train --preset`,
    `cli warm` and `cli fit`.

    Exit 0: winner found + artifact written. Exit 1: no feasible
    candidate under the limit. Exit 2: no device byte limit known
    (set --limit-gb or ALPHATRIANGLE_DEVICE_BYTES_LIMIT).
    """
    import json as _json
    import os as _os

    from .utils.helpers import enforce_platform

    bundle = resolve_preset(args.target)
    enforce_platform(args.device or "auto")

    import jax

    from .autotune import (
        SearchSpace,
        build_tuned_preset,
        calibration_from_targets,
        default_artifact_path,
        run_search,
        write_tuned_preset,
    )
    from .telemetry.memory import (
        FIT_OVER,
        FIT_UNKNOWN,
        fmt_bytes,
        resolve_bytes_limit,
    )
    from .training.setup import wants_device_ring
    from .utils.flops import peak_bf16_tflops_info
    from .utils.helpers import enable_persistent_compilation_cache

    backend = jax.default_backend()
    enable_persistent_compilation_cache()
    environ = dict(_os.environ)
    base_train = bundle["train"]
    scale = (
        f"tuned_{bundle['tuned'].get('scale', 'preset')}"
        if "tuned" in bundle
        else f"preset_{args.target}"
    )

    limit, limit_source = resolve_bytes_limit(args.limit_gb, environ)
    if limit is None:
        print(
            "tune: no per-device byte limit known — pass --limit-gb or "
            "set ALPHATRIANGLE_DEVICE_BYTES_LIMIT (a search without a "
            "memory budget has no feasibility oracle).",
            file=sys.stderr,
        )
        return FIT_UNKNOWN

    device_kind = jax.devices()[0].device_kind
    peak, peak_source = peak_bf16_tflops_info(device_kind)
    device_count = jax.device_count()

    # Loop mode being tuned: the fused megastep when the base config
    # gets the device ring on this backend, else the sync loop. On the
    # CPU that is sync — the megastep still dispatches there but its
    # learner programs cannot AOT (rl/trainer.py cpu_aot).
    device_replay = wants_device_ring(base_train)
    mode = args.mode
    if mode == "auto":
        mode = "megastep" if device_replay else "sync"

    batches, capacities, chunks, fused_ks, dps = _tune_axes(
        base_train, max(1, base_train.FUSED_LEARNER_STEPS), device_count
    )
    if args.batches:
        batches = [int(v) for v in args.batches.split(",")]
    if args.capacities:
        capacities = [int(v) for v in args.capacities.split(",")]
    if args.chunks:
        chunks = [int(v) for v in args.chunks.split(",")]
    if args.fused_k:
        fused_ks = [int(v) for v in args.fused_k.split(",")]
    if args.dp:
        dps = [int(v) for v in args.dp.split(",")]
    geometries = (
        args.geometries.split(",") if args.geometries else ["plan"]
    )
    kernel_backends = (
        args.kernel_backends.split(",")
        if getattr(args, "kernel_backends", None)
        else ["xla"]
    )
    precisions = (
        args.precisions.split(",")
        if getattr(args, "precisions", None)
        else ["float32"]
    )
    tree_reuses = (
        [v.strip() == "on" for v in args.tree_reuse.split(",")]
        if getattr(args, "tree_reuse", None)
        else [False]
    )
    serve_ladders = (
        ["" if v.strip() in ("off", "") else v.strip() for v in args.serve_buckets]
        if getattr(args, "serve_buckets", None)
        else [""]
    )
    space = SearchSpace(
        geometries=geometries,
        batches=batches,
        capacities=capacities,
        chunks=chunks,
        fused_ks=fused_ks,
        dps=dps,
        backup_updates=kernel_backends,
        per_samples=kernel_backends,
        precisions=precisions,
        serve_bucket_ladders=serve_ladders,
        tree_reuses=tree_reuses,
    )

    calibration = calibration_from_targets(
        args.calibrate or [], root_dir=args.root_dir
    )
    def say(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    say(
        f"tune: backend={backend} scale={scale} mode={mode} "
        f"space={space.size()} candidates limit={fmt_bytes(limit)} "
        f"[{limit_source}] peak={peak or 'unknown'} TFLOP/s "
        f"[{peak_source}] calibration={','.join(calibration.sources)}"
    )

    result = run_search(
        space,
        bundle["env"],
        bundle["model"],
        bundle["mcts"],
        base_train,
        limit,
        calibration=calibration,
        peak_tflops=peak,
        mode=mode,
        device_replay=device_replay or mode == "megastep",
        progress=say,
    )

    run_name = args.run_name or f"tune_{scale}"
    payload = None
    out_path = None
    if result.best is not None:
        from .autotune.search import candidate_mcts, materialize_candidate

        env_cfg, model_cfg, train_cfg = materialize_candidate(
            result.best, bundle["env"], bundle["model"], base_train, mode
        )
        train_cfg = train_cfg.model_copy(update={"RUN_NAME": run_name})
        payload = build_tuned_preset(
            result,
            env_cfg,
            model_cfg,
            candidate_mcts(bundle["mcts"], result.best),
            train_cfg,
            scale=scale,
            mode=mode,
            backend=backend,
            device_kind=device_kind,
            limit_bytes=limit,
            limit_source=limit_source,
            calibration=calibration,
            run_name=run_name,
        )
        out_path = Path(
            args.out
            or default_artifact_path(run_name, root_dir=args.root_dir)
        )
        write_tuned_preset(payload, out_path)

    if args.json:
        print(
            _json.dumps(
                {
                    "schema": "alphatriangle.tune_report.v1",
                    "scale": scale,
                    "backend": backend,
                    "mode": mode,
                    "bytes_limit": limit,
                    "limit_source": limit_source,
                    "rows": result.rows,
                    "oracle_calls": result.oracle_calls,
                    "best": payload,
                    "artifact": str(out_path) if out_path else None,
                    "exit": 0 if result.best is not None else FIT_OVER,
                },
                default=str,
            )
        )
    else:
        hdr = (
            f"{'geometry':<9} {'B':>6} {'cap':>8} {'T':>4} {'K':>4} "
            f"{'dp':>3} {'pred games/h':>13} {'budget':>10}  status"
        )
        print(f"tune {scale} on {backend} (mode {mode})")
        print(hdr)
        for row in result.rows:
            pred = row["predicted"] or {}
            gph = pred.get("games_per_hour")
            gph_s = (
                f"{gph:.1f}" if isinstance(gph, (int, float)) else "n/a"
            )
            budget = row["budget_total_bytes"]
            budget_s = fmt_bytes(budget) if budget else "n/a"
            detail = f" ({row['detail']})" if row["detail"] else ""
            print(
                f"{row['geometry']:<9} {row['sp_batch']:>6} "
                f"{row['capacity']:>8} {row['chunk']:>4} "
                f"{row['fused_k']:>4} {row['dp']:>3} {gph_s:>13} "
                f"{budget_s:>10}  {row['status']}{detail}"
            )
        if result.best is not None:
            pred = result.best_prediction or {}
            print(
                f"tune: best {result.best.label()} — predicted "
                f"{pred.get('games_per_hour', 0.0):.1f} games/h, "
                f"budget {fmt_bytes(result.best_budget['total_bytes'])} "
                f"of {fmt_bytes(limit)} "
                f"({result.oracle_calls} oracle call(s))"
            )
            print(f"tune: wrote {out_path}")
            print(
                f"tune: consume with `cli train --preset {out_path}`, "
                f"`cli warm {out_path}` or `cli fit {out_path}`"
            )
        else:
            print(
                f"tune: no feasible candidate under {fmt_bytes(limit)} "
                f"({result.oracle_calls} oracle call(s), "
                f"{len(result.rows)} candidates examined)"
            )
    return 0 if result.best is not None else FIT_OVER


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="alphatriangle-tpu",
        description="TPU-native AlphaZero training for the triangle puzzle.",
    )
    sub = parser.add_subparsers(dest="command", required=True)

    _add_train_parser(sub)

    tb = sub.add_parser("tb", help="Launch TensorBoard over the runs root.")
    tb.add_argument("--root-dir", default=None)
    tb.add_argument("--port", type=int, default=6006)

    ml = sub.add_parser("ml", help="Launch MLflow UI (when installed).")
    ml.add_argument("--root-dir", default=None)
    ml.add_argument("--port", type=int, default=5000)

    sub.add_parser("devices", help="Show the JAX backend and devices.")

    watch = sub.add_parser(
        "watch",
        help="Live console for a training run (tails live_metrics.jsonl).",
    )
    watch.add_argument(
        "--run-name", default=None, help="Default: most recent run."
    )
    watch.add_argument("--root-dir", default=None)
    watch.add_argument("--interval", type=float, default=2.0)
    watch.add_argument(
        "--once", action="store_true", help="Render one frame and exit."
    )

    doctor = sub.add_parser(
        "doctor",
        help="Postmortem window forensics from the flight ring + "
        "health.json + wedge report — names the program a dead run "
        "hung inside; exit code is the verdict. No JAX import.",
    )
    doctor.add_argument(
        "run",
        nargs="?",
        default=None,
        help="Run name, run dir, or flight.jsonl path "
        "(default: latest run).",
    )
    doctor.add_argument("--root-dir", default=None)
    doctor.add_argument(
        "--json",
        action="store_true",
        help="Emit the verdict as one JSON line (for scripts).",
    )

    slo = sub.add_parser(
        "slo",
        help="Fleet SLO report: error budgets + multi-window burn-rate "
        "alerts from the fleet's ledgers. Exit 0 within budget, "
        "1 burning, 2 no data. No JAX import.",
    )
    slo.add_argument(
        "run",
        nargs="?",
        default=None,
        help="Run name or fleet-parent run dir (default: latest run).",
    )
    slo.add_argument("--root-dir", default=None)
    slo.add_argument(
        "--json",
        action="store_true",
        help="Emit the full alphatriangle.slo.v1 report as one JSON line.",
    )
    slo.add_argument(
        "--latency-threshold",
        type=float,
        default=500.0,
        help="p95 move-latency SLO threshold in ms (default 500).",
    )
    slo.add_argument(
        "--window",
        action="append",
        default=None,
        metavar="SECONDS:BURN",
        help="Override burn-rate windows (repeatable), e.g. 300:14.4 "
        "3600:6. Default: the SRE fast-page/slow-ticket pair.",
    )
    slo.add_argument(
        "--now",
        type=float,
        default=None,
        help="Evaluate at this epoch time instead of the newest record "
        "(replay the alert state mid-brownout).",
    )
    slo.add_argument(
        "--prom",
        action="store_true",
        help="Also (re)write the aggregated fleet.prom textfile.",
    )

    supervise = sub.add_parser(
        "supervise",
        help="Self-healing parent for train/league: restart a dead "
        "child from its latest committed checkpoint per the doctor "
        "verdict (backoff, OOM degrade, family quarantine, circuit "
        "breaker). JAX-free; events -> runs/<run>/supervisor.jsonl.",
    )
    supervise.add_argument(
        "--run-name",
        required=True,
        help="Run directory to supervise (injected into the child's "
        "argv when absent there).",
    )
    supervise.add_argument("--root-dir", default=None)
    supervise.add_argument(
        "--max-restarts",
        type=int,
        default=8,
        metavar="N",
        help="Total restart budget before giving up (exit 115).",
    )
    supervise.add_argument(
        "--circuit-breaker",
        type=int,
        default=3,
        metavar="N",
        help="Consecutive deaths without a new committed checkpoint "
        "that trip the breaker (exit 115).",
    )
    supervise.add_argument(
        "--backoff-base", type=float, default=5.0, metavar="SECONDS"
    )
    supervise.add_argument(
        "--backoff-max", type=float, default=300.0, metavar="SECONDS"
    )
    supervise.add_argument(
        "--quarantine-after",
        type=int,
        default=2,
        metavar="N",
        help="Wedges on one program family before its riskiest knob is "
        "quarantined (megastep -> sync, learner -> K=1, rollout -> "
        "sync rollouts).",
    )
    supervise.add_argument(
        "child",
        nargs=argparse.REMAINDER,
        help="Child subcommand + flags after '--' "
        "(default: train --run-name <run>).",
    )

    health = sub.add_parser(
        "health",
        help="Heartbeat check: pretty-print a run's health.json with a "
        "staleness verdict (exit 0 live / 1 stalled / 2 missing).",
    )
    health.add_argument(
        "run", nargs="?", default=None, help="Run name (default: latest)."
    )
    health.add_argument("--root-dir", default=None)
    health.add_argument(
        "--deadline",
        type=float,
        default=None,
        metavar="SECONDS",
        help="Staleness deadline override (default: the run's "
        "watchdog deadline).",
    )
    health.add_argument(
        "--probe",
        action="store_true",
        help="Machine mode: one JSON line + exit-code contract "
        "(0 live / 1 stalled / 2 missing / 3 dispatch-overdue) — the "
        "probe the fleet router and external orchestrators share "
        "(docs/OBSERVABILITY.md).",
    )

    perf = sub.add_parser(
        "perf",
        help="Performance summary of a run's metrics ledger "
        "(p50/p95 step time, MFU, throughput trend).",
    )
    perf.add_argument(
        "run",
        nargs="?",
        default=None,
        help="Run name, run dir, or metrics.jsonl path "
        "(default: latest run).",
    )
    perf.add_argument("--root-dir", default=None)
    perf.add_argument(
        "--window",
        type=int,
        default=None,
        metavar="N",
        help="Summarize only the newest N utilization records "
        "(default: the whole run).",
    )
    perf.add_argument(
        "--json",
        action="store_true",
        help="Emit the summary as one JSON line (comparable input for "
        "`compare`).",
    )

    comp = sub.add_parser(
        "compare",
        help="Aligned-metric regression report between two runs (or a "
        "run and a `cli perf --json` summary snapshot); exit 0 "
        "parity, 1 regression, 2 unreadable.",
    )
    comp.add_argument(
        "run_a", help="Candidate: run name/dir, metrics.jsonl, or JSON."
    )
    comp.add_argument(
        "run_b", help="Baseline: run name/dir, metrics.jsonl, or a "
        "perf-summary JSON.",
    )
    comp.add_argument("--root-dir", default=None)
    comp.add_argument(
        "--threshold",
        type=float,
        default=0.1,
        metavar="FRAC",
        help="Regression tolerance: fail when a metric drops more than "
        "this fraction below the baseline (default 0.1).",
    )
    comp.add_argument(
        "--json", action="store_true", help="Emit the report as JSON."
    )
    comp.add_argument(
        "--metrics",
        default=None,
        metavar="M1[,M2...]",
        help="Compare only these metrics (default: the full aligned "
        "set, telemetry/perf.py COMPARE_METRICS). serve-smoke gates "
        "the serving SLO rows alone with this.",
    )

    trace = sub.add_parser(
        "trace",
        help="Summarize a run's host span trace (trace.json; "
        "Perfetto/chrome-loadable).",
    )
    trace.add_argument(
        "run", nargs="?", default=None, help="Run name (default: latest)."
    )
    trace.add_argument("--root-dir", default=None)
    trace.add_argument("--top", type=int, default=20)
    trace.add_argument(
        "--fleet",
        action="store_true",
        help="Fuse a fleet-parent run dir (parent route brackets + "
        "fleet.jsonl + every replica's flight ring and trace.json, "
        "clock-calibrated) into one Perfetto timeline with flow "
        "arrows per trace_id (trace_fleet.json).",
    )

    an = sub.add_parser(
        "analyze", help="Summarize per-phase timer dumps from a profile run."
    )
    an.add_argument("profile_dir", help="runs/<run>/profile_data directory.")
    an.add_argument("--top", type=int, default=20)

    ev = sub.add_parser(
        "eval", help="Arena evaluation of a checkpoint (greedy MCTS play)."
    )
    ev.add_argument("--checkpoint", default=None, metavar="PATH")
    ev.add_argument("--run-name", default=None)
    ev.add_argument(
        "--vs-checkpoint",
        default=None,
        metavar="PATH",
        help="Head-to-head opponent checkpoint (plays the same paired "
        "hands).",
    )
    ev.add_argument(
        "--vs-run",
        default=None,
        help="Head-to-head opponent: latest checkpoint of this run.",
    )
    ev.add_argument("--root-dir", default=None)
    ev.add_argument("--games", type=int, default=64)
    ev.add_argument("--sims", type=int, default=64)
    ev.add_argument("--max-moves", type=int, default=200)
    ev.add_argument("--seed", type=int, default=0)
    ev.add_argument(
        "--gumbel",
        action="store_true",
        help="Evaluate with exploit-mode Gumbel search (deterministic "
        "logits + sigma(q) argmax) instead of greedy PUCT.",
    )
    ev.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )

    warm = sub.add_parser(
        "warm",
        help="AOT-precompile the hot programs of a preset's run "
        "(rollout chunk, fused learner group, megastep, serve rungs) "
        "into the executable cache so `cli train --preset` with the "
        "same target skips its first-dispatch compiles.",
    )
    warm.add_argument(
        "target",
        metavar="N|PATH",
        help="What to warm: " + _PRESET_TARGET_HELP,
    )
    warm.add_argument(
        "--jobs",
        type=int,
        default=4,
        metavar="N",
        help="Programs compiled in parallel threads (XLA releases the "
        "GIL during compilation).",
    )
    warm.add_argument(
        "--programs",
        default=None,
        metavar="SUBSTR[,SUBSTR...]",
        help="Only warm programs whose name contains one of these "
        "substrings (e.g. 'self_play,learner_step').",
    )
    warm.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )

    fit = sub.add_parser(
        "fit",
        help="OOM pre-flight: compose the static per-device memory "
        "budget (params + opt state + replay ring + AOT-analyzed "
        "program memory) against the device byte limit; exit 0 fits / "
        "1 over budget / 2 unknown device limit.",
    )
    fit.add_argument(
        "target",
        metavar="N|PATH",
        help="The run to check: " + _PRESET_TARGET_HELP,
    )
    fit.add_argument(
        "--limit-gb",
        type=float,
        default=None,
        metavar="GIB",
        help="Assert a per-device byte limit (GiB) instead of asking "
        "the backend (also: ALPHATRIANGLE_DEVICE_BYTES_LIMIT, bytes).",
    )
    fit.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )
    fit.add_argument(
        "--json", action="store_true", help="Emit the report as JSON."
    )
    fit.add_argument(
        "--serve",
        action="store_true",
        help="Additionally AOT-analyze the policy service's "
        "serve/b<B> search program and persist its .mem.json sidecar "
        "(the `cli serve` pre-flight reads it; docs/SERVING.md).",
    )

    serve = sub.add_parser(
        "serve",
        help="Policy-serving front end: continuous-batching inference "
        "service over the batched wave search, with AOT-warmed "
        "startup, OOM pre-flight, heartbeat, and per-request latency "
        "SLOs in the metrics ledger (docs/SERVING.md).",
    )
    serve.add_argument(
        "--run-name",
        default=None,
        help="Serve this run's latest checkpoint (and its board/net "
        "configs); with --reload-every, newer checkpoints hot-swap in.",
    )
    serve.add_argument("--checkpoint", default=None, metavar="PATH")
    serve.add_argument("--root-dir", default=None)
    serve.add_argument(
        "--serve-run-name",
        default=None,
        help="Run dir for the service's own telemetry "
        "(default: serve_<run-name> or 'serve').",
    )
    serve.add_argument(
        "--slots",
        type=int,
        default=64,
        metavar="B",
        help="Concurrent session slots = the compiled serve/b<B> "
        "search batch shape (default 64).",
    )
    serve.add_argument(
        "--buckets",
        default=None,
        metavar="RUNGS",
        help="Serve-shape ladder as a CSV rung list (e.g. 16,64,256 — "
        "serving/buckets.py). The micro-batcher walks between rungs "
        "with sustained load; every rung is AOT-warmed up front so a "
        "switch never recompiles. Default: a single fixed rung at "
        "--slots.",
    )
    serve.add_argument("--sims", type=int, default=64)
    serve.add_argument(
        "--sessions",
        type=int,
        default=96,
        metavar="N",
        help="Simulated sessions per traffic wave (the smoke serves "
        "exactly one wave).",
    )
    serve.add_argument("--max-moves", type=int, default=200)
    serve.add_argument("--seed", type=int, default=0)
    serve.add_argument(
        "--gumbel",
        action="store_true",
        help="Serve exploit-mode Gumbel search instead of greedy PUCT.",
    )
    serve.add_argument(
        "--smoke",
        action="store_true",
        help="Bounded CI mode: serve one wave of --sessions simulated "
        "sessions with churn, assert the latency ledger landed, exit "
        "0/1 (make serve-smoke drives this on CPU).",
    )
    serve.add_argument(
        "--duration",
        type=float,
        default=None,
        metavar="SECONDS",
        help="Serve traffic waves until this wall budget elapses "
        "(default: one wave, or Ctrl-C).",
    )
    serve.add_argument(
        "--tick-every",
        type=int,
        default=8,
        metavar="DISPATCHES",
        help="Ledger/heartbeat tick cadence in dispatches (default 8).",
    )
    serve.add_argument(
        "--reload-every",
        type=int,
        default=32,
        metavar="DISPATCHES",
        help="Poll the run's checkpoints for hot weight reload every "
        "N dispatches (0 disables; needs --run-name).",
    )
    serve.add_argument(
        "--limit-gb",
        type=float,
        default=None,
        metavar="GIB",
        help="Pre-flight device byte limit override "
        "(also: ALPHATRIANGLE_DEVICE_BYTES_LIMIT).",
    )
    serve.add_argument(
        "--no-warm",
        action="store_true",
        help="Skip the AOT warm-start step.",
    )
    serve.add_argument(
        "--no-preflight",
        action="store_true",
        help="Skip the OOM pre-flight gate.",
    )
    serve.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )

    fleet = sub.add_parser(
        "fleet",
        help="Fault-tolerant serve fleet: N PolicyService replica "
        "subprocesses behind a health-gated least-queue-depth router "
        "with retry/hedge/shed, verdict-driven replica restarts, and "
        "a crash-safe fleet.jsonl decision ledger (docs/SERVING.md "
        "'Fleet'). The parent never imports JAX.",
    )
    fleet.add_argument(
        "--run-name",
        default="fleet",
        help="Fleet run dir name (replica run dirs nest inside; a "
        "configs.json there supplies the board/net).",
    )
    fleet.add_argument("--root-dir", default=None)
    fleet.add_argument("--replicas", type=int, default=2, metavar="N")
    fleet.add_argument(
        "--slots",
        type=int,
        default=8,
        metavar="B",
        help="Session slots per replica = its compiled serve/b<B> "
        "bucket (a quarantined replica respawns onto the next ladder "
        "rung down).",
    )
    fleet.add_argument(
        "--buckets",
        default=None,
        metavar="RUNGS",
        help="Serve-shape ladder as a CSV rung list shared by every "
        "replica's micro-batcher AND the quarantine walk-down "
        "(serving/buckets.py). Default: the halving ladder under "
        "--slots (reproduces the legacy 0.5-multiplier buckets).",
    )
    fleet.add_argument("--sims", type=int, default=4)
    fleet.add_argument("--seed", type=int, default=0)
    fleet.add_argument(
        "--requests",
        type=int,
        default=32,
        metavar="N",
        help="Episode requests in the storm.",
    )
    fleet.add_argument("--concurrency", type=int, default=8)
    fleet.add_argument("--max-moves", type=int, default=12)
    fleet.add_argument(
        "--timeout",
        type=float,
        default=30.0,
        metavar="SECONDS",
        help="Per-attempt request timeout (a timed-out attempt "
        "retries on a different replica).",
    )
    fleet.add_argument(
        "--retries",
        type=int,
        default=2,
        help="Retry budget per request after the first attempt.",
    )
    fleet.add_argument(
        "--route-backoff-base", type=float, default=0.1, metavar="SECONDS"
    )
    fleet.add_argument(
        "--route-backoff-max", type=float, default=2.0, metavar="SECONDS"
    )
    fleet.add_argument(
        "--hedge-after",
        type=float,
        default=None,
        metavar="SECONDS",
        help="Hedge a straggling request onto a second replica after "
        "this long; first result wins (default: off).",
    )
    fleet.add_argument(
        "--max-queue",
        type=int,
        default=64,
        metavar="N",
        help="Bounded admission: in-flight requests past this are "
        "shed with rejection code 'queue-full'.",
    )
    fleet.add_argument(
        "--probe-deadline",
        type=float,
        default=10.0,
        metavar="SECONDS",
        help="Heartbeat staleness deadline for the routability probe.",
    )
    fleet.add_argument(
        "--poll",
        type=float,
        default=0.25,
        metavar="SECONDS",
        help="Fleet monitor poll cadence (deaths, probes, respawns).",
    )
    fleet.add_argument(
        "--spawn-timeout",
        type=float,
        default=300.0,
        metavar="SECONDS",
        help="Budget for a replica to warm + report ready.",
    )
    fleet.add_argument(
        "--settle",
        type=float,
        default=30.0,
        metavar="SECONDS",
        help="Post-storm wait for pending respawn/readmit chains to "
        "land on fleet.jsonl.",
    )
    fleet.add_argument("--max-restarts", type=int, default=8)
    fleet.add_argument("--circuit-breaker", type=int, default=3)
    fleet.add_argument(
        "--backoff-base",
        type=float,
        default=5.0,
        metavar="SECONDS",
        help="Replica restart backoff base (RecoveryPolicy).",
    )
    fleet.add_argument(
        "--backoff-max", type=float, default=300.0, metavar="SECONDS"
    )
    fleet.add_argument(
        "--quarantine-after",
        type=int,
        default=2,
        metavar="N",
        help="Wedges on the serve family before the replica respawns "
        "onto a halved bucket (SERVE_SLOTS__scale).",
    )
    fleet.add_argument("--tick-every", type=int, default=8)
    fleet.add_argument(
        "--replica-health-interval", type=float, default=1.0
    )
    fleet.add_argument(
        "--replica-dispatch-min-deadline", type=float, default=60.0
    )
    fleet.add_argument(
        "--replica-dispatch-first-deadline", type=float, default=900.0
    )
    fleet.add_argument(
        "--replica-watchdog-poll", type=float, default=5.0
    )
    fleet.add_argument(
        "--chaos-kill-after",
        type=int,
        default=0,
        metavar="N",
        help="SIGKILL one replica after N completed requests "
        "(the fleet smoke's deterministic chaos trigger; 0 = off).",
    )
    fleet.add_argument(
        "--reload-after",
        type=int,
        default=0,
        metavar="N",
        help="Start a rolling weight swap after N completed requests "
        "(0 = off).",
    )
    fleet.add_argument(
        "--smoke",
        action="store_true",
        help="Gate on the zero-lost-requests invariant "
        "(make fleet-smoke drives this on CPU).",
    )

    league = sub.add_parser(
        "league",
        help="Experience-flywheel mode: learner + matchmade league "
        "games through a PolicyService in one process, served "
        "trajectories flowing into the replay ring alongside "
        "self-play (docs/LEAGUE.md).",
    )
    league.add_argument(
        "--pool-from",
        required=True,
        metavar="RUN",
        help="Seed the opponent pool from this run's checkpoints (its "
        "configs.json also supplies the board/net geometry).",
    )
    league.add_argument("--run-name", default=None)
    league.add_argument("--root-dir", default=None)
    league.add_argument("--steps", type=int, default=None, metavar="N",
                        help="MAX_TRAINING_STEPS for the learner.")
    league.add_argument(
        "--mix",
        type=float,
        default=None,
        metavar="RATIO",
        help="Fraction of iterations that play a league round instead "
        "of a self-play chunk (default 0.25).",
    )
    league.add_argument(
        "--slots",
        type=int,
        default=None,
        metavar="B",
        help="League service session slots (= serve/b<B> shape).",
    )
    league.add_argument(
        "--games",
        type=int,
        default=None,
        metavar="G",
        help="Games per side per matchmade pairing.",
    )
    league.add_argument("--sims", type=int, default=None)
    league.add_argument("--max-moves", type=int, default=None)
    league.add_argument(
        "--reload-every",
        type=int,
        default=None,
        metavar="STEPS",
        help="Broadcast fresh learner params to the league service "
        "every N learner steps (default 8).",
    )
    league.add_argument(
        "--staleness-window",
        type=int,
        default=None,
        metavar="RELOADS",
        help="Drop harvested rows more than this many reloads behind "
        "the learner (default 4; negative disables).",
    )
    league.add_argument("--promotion-games", type=int, default=None)
    league.add_argument("--promotion-win-rate", type=float, default=None)
    league.add_argument("--exploration-floor", type=float, default=None)
    league.add_argument("--seed", type=int, default=None)
    league.add_argument("--self-play-batch", type=int, default=None)
    league.add_argument("--batch-size", type=int, default=None)
    league.add_argument("--buffer-capacity", type=int, default=None)
    league.add_argument("--min-buffer", type=int, default=None)
    league.add_argument("--rollout-chunk", type=int, default=None)
    league.add_argument("--checkpoint-freq", type=int, default=None)
    league.add_argument(
        "--device-replay", default=None, choices=["auto", "on", "off"]
    )
    league.add_argument("--no-telemetry", action="store_true")
    league.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )

    lint = sub.add_parser(
        "lint",
        help="graftlint: AST-based JAX-hazard analyzer (donation, host "
        "syncs, placement, flight coverage, debug artifacts, RNG) — "
        "no JAX import; exit 0 clean / 1 findings / 2 parse error "
        "(docs/ANALYSIS.md).",
    )
    lint.add_argument(
        "path",
        nargs="?",
        default=None,
        help="Tree to lint (default: the installed alphatriangle_tpu "
        "package).",
    )
    lint.add_argument(
        "--rule",
        action="append",
        default=None,
        metavar="NAME",
        help="Run only this rule (repeatable).",
    )
    lint.add_argument(
        "--baseline",
        default=None,
        metavar="PATH",
        help="Baseline file of grandfathered finding keys (default: "
        "lint_baseline.json beside the linted tree). Stale entries "
        "fail the lint.",
    )
    lint.add_argument(
        "--write-baseline",
        action="store_true",
        help="Grandfather every current finding into the baseline file "
        "and exit 0.",
    )
    lint.add_argument(
        "--json",
        action="store_true",
        help='One-line JSON verdict (leads with "schema": '
        f'"alphatriangle.lint.v1"), for scripts.',
    )

    mem = sub.add_parser(
        "mem",
        help="Memory-attribution table for a run (programs, train "
        "state, replay ring, observed in-use/peak) from its "
        "metrics.jsonl alone — no JAX import.",
    )
    mem.add_argument(
        "run",
        nargs="?",
        default=None,
        help="Run name, run dir, or metrics.jsonl path "
        "(default: latest run).",
    )
    mem.add_argument("--root-dir", default=None)
    mem.add_argument(
        "--json", action="store_true", help="Emit records + budget as JSON."
    )

    roofline = sub.add_parser(
        "roofline",
        help="Roofline attribution for a run: per-program intensity "
        "vs machine balance + chip-idle gap forensics, from its "
        "artifacts alone — no JAX import.",
    )
    roofline.add_argument(
        "run",
        nargs="?",
        default=None,
        help="Run name, run dir, or metrics.jsonl path "
        "(default: latest run).",
    )
    roofline.add_argument("--root-dir", default=None)
    roofline.add_argument(
        "--json",
        action="store_true",
        help="Emit the roofline summary as one JSON line.",
    )

    tune = sub.add_parser(
        "tune",
        help="Fit-driven offline autotuner: search batch/capacity/"
        "chunk/K/dp/geometry for the feasible config maximizing "
        "predicted games/h — AOT memory analysis as the oracle, no "
        "chip execution — and emit a tuned_preset.json "
        "(docs/AUTOTUNE.md).",
    )
    tune.add_argument(
        "target",
        metavar="N|PATH",
        help="Base configuration to search around: " + _PRESET_TARGET_HELP,
    )
    tune.add_argument(
        "--limit-gb",
        type=float,
        default=None,
        metavar="GIB",
        help="Per-device byte limit (GiB) the search must fit under "
        "(default: backend-reported; also "
        "ALPHATRIANGLE_DEVICE_BYTES_LIMIT, bytes).",
    )
    tune.add_argument(
        "--json",
        action="store_true",
        help="Emit the full search report (rows + winner) as JSON.",
    )
    tune.add_argument(
        "--out",
        default=None,
        metavar="PATH",
        help="Write tuned_preset.json here "
        "(default: runs/<run-name>/tuned_preset.json).",
    )
    tune.add_argument("--run-name", default=None)
    tune.add_argument("--root-dir", default=None)
    tune.add_argument(
        "--batches",
        default=None,
        help="Override the SELF_PLAY_BATCH_SIZE axis (comma-separated).",
    )
    tune.add_argument(
        "--capacities",
        default=None,
        help="Override the BUFFER_CAPACITY axis (comma-separated).",
    )
    tune.add_argument(
        "--chunks",
        default=None,
        help="Override the rollout chunk T axis (comma-separated).",
    )
    tune.add_argument(
        "--fused-k",
        default=None,
        help="Override the fused learner K axis (comma-separated).",
    )
    tune.add_argument(
        "--dp",
        default=None,
        help="Override the data-parallel shard axis (comma-separated).",
    )
    tune.add_argument(
        "--geometries",
        default=None,
        help="Board geometry presets to search (comma-separated names "
        "from config.GEOMETRY_PRESETS, or 'plan' = the base "
        "configuration's board).",
    )
    tune.add_argument(
        "--kernel-backends",
        default=None,
        metavar="BACKENDS",
        help="Kernel lowerings to search for backup_update and "
        "PER_SAMPLE_BACKEND (comma-separated from xla,pallas — "
        "docs/KERNELS.md). Free axes: memory-neutral variants share "
        "oracle results. Default: xla only.",
    )
    tune.add_argument(
        "--precisions",
        default=None,
        metavar="DTYPES",
        help="INFERENCE_PRECISION values to search (comma-separated "
        "from float32,bfloat16,int8 — int8 is weight-only per-channel "
        "quantization, docs/KERNELS.md). Default: float32 only.",
    )
    tune.add_argument(
        "--serve-buckets",
        action="append",
        default=None,
        metavar="RUNGS",
        help="Serve-shape ladders to search (repeatable; each a CSV "
        "rung list like 64,256,1024 — serving/buckets.py, or 'off' for "
        "the fixed single-rung shape). Serve-side free axis: ladders "
        "share training feasibility answers. Default: off only.",
    )
    tune.add_argument(
        "--tree-reuse",
        default=None,
        metavar="VALUES",
        help="MCTS subtree-reuse settings to search (comma-separated "
        "from off,on — docs/KERNELS.md). Reuse widens the tree planes, "
        "so 'on' candidates get their own feasibility-oracle answers. "
        "Default: off only.",
    )
    tune.add_argument(
        "--calibrate",
        action="append",
        default=None,
        metavar="RUN_OR_JSON",
        help="Calibrate the throughput model against these runs / perf "
        "summaries (repeatable; accepts anything `cli perf compare` "
        "does). Default: the model's conservative built-ins.",
    )
    tune.add_argument(
        "--mode",
        default="auto",
        choices=["auto", "sync", "megastep"],
        help="Loop shape being tuned (auto = megastep when the base "
        "configuration gets the device ring on this backend).",
    )
    tune.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )

    play = sub.add_parser(
        "play", help="Interactive text play on the default board."
    )
    play.add_argument("--seed", type=int, default=0)
    play.add_argument(
        "--engine", choices=["auto", "native", "jax"], default="auto"
    )
    play.add_argument(
        "--script",
        default=None,
        help="Semicolon-separated scripted moves ('0 0 0;1 2 3'); "
        "plays them then exits (demo/testing).",
    )

    args = parser.parse_args(argv)
    handlers = {
        "train": cmd_train,
        "tb": cmd_tb,
        "ml": cmd_ml,
        "devices": cmd_devices,
        "watch": cmd_watch,
        "health": cmd_health,
        "doctor": cmd_doctor,
        "slo": cmd_slo,
        "supervise": cmd_supervise,
        "perf": cmd_perf,
        "compare": cmd_compare,
        "trace": cmd_trace,
        "analyze": cmd_analyze,
        "eval": cmd_eval,
        "play": cmd_play,
        "tune": cmd_tune,
        "warm": cmd_warm,
        "fit": cmd_fit,
        "serve": cmd_serve,
        "fleet": cmd_fleet,
        "league": cmd_league,
        "mem": cmd_mem,
        "roofline": cmd_roofline,
        "lint": cmd_lint,
    }
    return handlers[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
