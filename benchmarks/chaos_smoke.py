"""CI chaos gate: injected faults -> supervised recovery, end to end.

`make chaos-smoke` runs this. It proves, on any machine with no
accelerator, that the self-healing story (docs/ROBUSTNESS.md) actually
closes: the fault injector (supervise/faults.py) kills real training
children in the exact ways TPU runs die, and the `cli supervise`
machinery must bring every scenario home:

1. the supervisor package imports and decides with jax imports hard-
   blocked — the parent must outlive a wedged chip, so this is a
   contract, not a style preference;
2. wedge drill: a dispatch hung mid-run (hang-dispatch fault) dies by
   the real watchdog's exit 113, the `Supervisor` classifies it
   dispatch-hung, restarts from the latest committed checkpoint with
   backoff, and the run completes (exit 0) with step loss bounded by
   one checkpoint cadence — `supervisor.jsonl` carries the full
   death -> verdict -> restart chain;
3. preemption drill: SIGTERM at a mid-run step is absorbed as an
   emergency checkpoint + exit 114, `cli doctor` reads the preempt
   report as verdict `preempted` (exit 7), and a bare rerun resumes
   from the emergency checkpoint and completes;
4. torn-checkpoint drill: SIGKILL in the middle of a checkpoint save
   (after the tree dispatch + meta write, before the commit marker)
   leaves an uncommitted step dir; the supervised restart must resume
   from the prior COMMITTED step, skip the torn one, and complete.

Exit 0 when every stage passes; the first failing stage's code
otherwise. Scenario children run `--child` below (a tiny CPU training
run with a fast dispatch watchdog); the parent stays jax-free.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

MAX_STEPS = 8
CKPT_CADENCE = 2

# Same import-guard preamble as doctor_smoke.py: any jax import in the
# guarded subprocess raises.
_NO_JAX_PREAMBLE = (
    "import builtins, sys;"
    "_real = builtins.__import__;\n"
    "def _guard(name, *a, **k):\n"
    "    if name == 'jax' or name.startswith('jax.'):\n"
    "        raise ImportError('supervisor must not import jax: ' + name)\n"
    "    return _real(name, *a, **k)\n"
    "builtins.__import__ = _guard\n"
)


def tiny_configs(run_name: str):
    """perf_smoke's tiny world plus a fast dispatch watchdog: small
    deadlines so an injected hang dies in seconds, not minutes."""
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        EnvConfig,
        ModelConfig,
        TelemetryConfig,
        TrainConfig,
        expected_other_features_dim,
    )

    env_cfg = EnvConfig(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[(0, 4), (0, 4), (0, 4)],
        NUM_SHAPE_SLOTS=1,
        MAX_SHAPE_TRIANGLES=3,
        LINE_MIN_LENGTH=3,
    )
    model_cfg = ModelConfig(
        GRID_INPUT_CHANNELS=1,
        CONV_FILTERS=[4],
        CONV_KERNEL_SIZES=[3],
        CONV_STRIDES=[1],
        NUM_RESIDUAL_BLOCKS=0,
        RESIDUAL_BLOCK_FILTERS=4,
        USE_TRANSFORMER=False,
        FC_DIMS_SHARED=[16],
        POLICY_HEAD_DIMS=[16],
        VALUE_HEAD_DIMS=[16],
        OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(env_cfg),
        NUM_VALUE_ATOMS=11,
        COMPUTE_DTYPE="float32",
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=4, max_depth=4)
    train_cfg = TrainConfig(
        RUN_NAME=run_name,
        AUTO_RESUME_LATEST=False,
        MAX_TRAINING_STEPS=MAX_STEPS,
        SELF_PLAY_BATCH_SIZE=4,
        ROLLOUT_CHUNK_MOVES=4,
        BATCH_SIZE=8,
        BUFFER_CAPACITY=2000,
        MIN_BUFFER_SIZE_TO_TRAIN=16,
        USE_PER=True,
        PER_BETA_ANNEAL_STEPS=8,
        N_STEP_RETURNS=2,
        WORKER_UPDATE_FREQ_STEPS=2,
        CHECKPOINT_SAVE_FREQ_STEPS=CKPT_CADENCE,
        MAX_EPISODE_MOVES=30,
        RANDOM_SEED=5,
        DEVICE="cpu",
    )
    tele_cfg = TelemetryConfig(
        # Calibrated dispatches wedge after ~2s of silence; a program's
        # first dispatch (its compile) keeps a generous allowance.
        DISPATCH_MIN_DEADLINE_S=2.0,
        DISPATCH_FIRST_DEADLINE_S=120.0,
        DISPATCH_WATCHDOG_POLL_S=0.25,
        HEALTH_WRITE_INTERVAL_S=1.0,
    )
    return env_cfg, model_cfg, mcts_cfg, train_cfg, tele_cfg


def child(args) -> int:
    """One tiny supervised-training child (runs in a subprocess; the
    armed ALPHATRIANGLE_FAULTS env decides how it dies)."""
    from alphatriangle_tpu.config import PersistenceConfig
    from alphatriangle_tpu.training import run_training

    env_cfg, model_cfg, mcts_cfg, train_cfg, tele_cfg = tiny_configs(
        args.run_name
    )
    pc = PersistenceConfig(ROOT_DATA_DIR=args.root_dir, RUN_NAME=args.run_name)
    return run_training(
        train_config=train_cfg,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=pc,
        telemetry_config=tele_cfg,
        use_tensorboard=False,
        log_level="WARNING",
    )


def run_dir_for(root: str, run_name: str) -> Path:
    from alphatriangle_tpu.config import PersistenceConfig

    return PersistenceConfig(
        ROOT_DATA_DIR=root, RUN_NAME=run_name
    ).get_run_base_dir()


def child_argv(root: str, run_name: str) -> list:
    return [
        sys.executable,
        str(Path(__file__).resolve()),
        "--child",
        "--root-dir",
        root,
        "--run-name",
        run_name,
    ]


def committed_steps(run_dir: Path) -> list:
    ckpts = run_dir / "checkpoints"
    if not ckpts.is_dir():
        return []
    steps = []
    for p in ckpts.glob("step_*.commit"):
        stem = p.name[len("step_"):-len(".commit")]
        if stem.isdigit():
            steps.append(int(stem))
    return sorted(steps)


def supervisor_events(run_dir: Path) -> list:
    events = []
    path = run_dir / "supervisor.jsonl"
    if not path.exists():
        return events
    for line in path.read_text().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("kind") == "supervisor":
            events.append(rec)
    return events


class _ArmedFaults:
    """Context manager arming the fault env for children the Supervisor
    spawns (it inherits os.environ), with a fresh sentinel state dir so
    each fault fires exactly once per scenario across restarts."""

    def __init__(self, spec: str, state_dir: Path) -> None:
        self.spec = spec
        self.state_dir = state_dir

    def __enter__(self):
        self.state_dir.mkdir(parents=True, exist_ok=True)
        os.environ["ALPHATRIANGLE_FAULTS"] = self.spec
        os.environ["ALPHATRIANGLE_FAULT_STATE_DIR"] = str(self.state_dir)
        return self

    def __exit__(self, *exc):
        os.environ.pop("ALPHATRIANGLE_FAULTS", None)
        os.environ.pop("ALPHATRIANGLE_FAULT_STATE_DIR", None)
        return False


def stage_jax_free_supervisor(root: Path) -> int:
    """The supervisor parent must import + decide with jax blocked."""
    code = (
        _NO_JAX_PREAMBLE
        + "from alphatriangle_tpu.supervise import (\n"
        + "    RecoveryPolicy, Supervisor, diagnose, latest_committed_step)\n"
        + "policy = RecoveryPolicy(backoff_base_s=1.0)\n"
        + "action = policy.decide(verdict='dispatch-hung', exit_code=113,\n"
        + "                       family='rollout')\n"
        + "assert action.kind == 'restart', action\n"
        + f"assert latest_committed_step({str(root)!r}) is None\n"
        + f"verdict = diagnose({str(root)!r})\n"
        + "assert verdict['verdict'] == 'never-started', verdict\n"
        + "print('supervise decided jax-free:', action.kind)\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)},
        capture_output=True,
        text=True,
        timeout=120,
    )
    if proc.returncode != 0:
        print(
            f"chaos-smoke: jax-free supervisor gate failed "
            f"(rc={proc.returncode})\nstdout: {proc.stdout}\n"
            f"stderr: {proc.stderr}",
            file=sys.stderr,
        )
        return 2
    print("chaos-smoke: supervise package imports + decides with jax blocked")
    return 0


def stage_wedge_restart(root: Path) -> int:
    """Injected mid-run hang -> watchdog 113 -> supervised restart from
    the latest committed checkpoint -> completion, chain on disk."""
    from alphatriangle_tpu.supervise import RecoveryPolicy, Supervisor

    run = "chaos_wedge"
    run_dir = run_dir_for(str(root), run)
    # Threshold calibrated against the tiny run's dispatch timeline:
    # ~13 dispatches total, the step-2 checkpoint commits by seq ~5 and
    # step-4 by seq ~8 — seq 9 is mid-run with committed progress.
    with _ArmedFaults("hang-dispatch@after=9", root / "faults_wedge"):
        policy = RecoveryPolicy(backoff_base_s=0.2, backoff_max_s=2.0)
        rc = Supervisor(child_argv(str(root), run), run_dir, policy).run()
    if rc != 0:
        print(
            f"chaos-smoke: supervised wedge run did not complete (rc={rc})",
            file=sys.stderr,
        )
        return 2
    events = supervisor_events(run_dir)
    deaths = [e for e in events if e.get("event") == "death"]
    spawns = [e for e in events if e.get("event") == "spawn"]
    completes = [e for e in events if e.get("event") == "complete"]
    if not deaths or len(spawns) < 2 or not completes:
        print(
            f"chaos-smoke: supervisor.jsonl chain incomplete: "
            f"{len(spawns)} spawns, {len(deaths)} deaths, "
            f"{len(completes)} completes",
            file=sys.stderr,
        )
        return 2
    death = deaths[0]
    if (
        death.get("rc") != 113
        or death.get("verdict") != "dispatch-hung"
        or death.get("action") != "restart"
        or not death.get("program")
        or death.get("delay_s", 0) <= 0
    ):
        print(
            f"chaos-smoke: death event misclassified: {death}",
            file=sys.stderr,
        )
        return 2
    progress = death.get("progress_step")
    if progress is None or progress < CKPT_CADENCE:
        print(
            f"chaos-smoke: no committed checkpoint at death "
            f"(progress_step={progress}) — the wedge fired before the "
            "first commit; raise the hang-dispatch threshold",
            file=sys.stderr,
        )
        return 2
    # Step loss <= one checkpoint cadence: the step the dead child had
    # reached (its last ledger record before the death event) minus the
    # committed step the restart resumed from.
    death_t = float(death.get("time") or 0.0)
    last_step = 0
    ledger = run_dir / "metrics.jsonl"
    if ledger.exists():
        for line in ledger.read_text().splitlines():
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue
            step = rec.get("step")
            if (
                isinstance(step, (int, float))
                and float(rec.get("time") or 0.0) < death_t
            ):
                last_step = max(last_step, int(step))
    if last_step - progress > CKPT_CADENCE:
        print(
            f"chaos-smoke: step loss {last_step - progress} exceeds the "
            f"checkpoint cadence {CKPT_CADENCE} (died at ~{last_step}, "
            f"resumed from {progress})",
            file=sys.stderr,
        )
        return 2
    final = committed_steps(run_dir)
    if not final or final[-1] != MAX_STEPS:
        print(
            f"chaos-smoke: run did not finish at a committed step "
            f"{MAX_STEPS} (committed: {final})",
            file=sys.stderr,
        )
        return 2
    print(
        f"chaos-smoke: wedge -> 113 -> {death['verdict']} "
        f"({death['program']}) -> restart from step {progress} after "
        f"{death['delay_s']:.1f}s -> completed at step {final[-1]} "
        f"(lost <= {CKPT_CADENCE} steps)"
    )
    return 0


def stage_preempt(root: Path) -> int:
    """SIGTERM mid-run -> emergency checkpoint + exit 114, doctor says
    `preempted`, a bare rerun resumes and completes."""
    run = "chaos_preempt"
    run_dir = run_dir_for(str(root), run)
    argv = child_argv(str(root), run)
    with _ArmedFaults("sigterm@step=3", root / "faults_preempt") as armed:
        env = {**os.environ, "PYTHONPATH": str(REPO)}
        first = subprocess.run(argv, cwd=str(REPO), env=env, timeout=600)
        if first.returncode != 114:
            print(
                f"chaos-smoke: preempted child exited {first.returncode}, "
                "expected 114",
                file=sys.stderr,
            )
            return 2
        report_path = run_dir / "preempt_report.json"
        try:
            report = json.loads(report_path.read_text())
        except (OSError, ValueError) as exc:
            print(
                f"chaos-smoke: no parseable {report_path}: {exc}",
                file=sys.stderr,
            )
            return 2
        ckpt_step = report.get("checkpointed_step")
        if ckpt_step is None or ckpt_step < 3:
            print(
                f"chaos-smoke: emergency checkpoint missing from the "
                f"preempt report: {report}",
                file=sys.stderr,
            )
            return 2
        if ckpt_step not in committed_steps(run_dir):
            print(
                f"chaos-smoke: emergency checkpoint step {ckpt_step} has "
                f"no commit marker (committed: {committed_steps(run_dir)})",
                file=sys.stderr,
            )
            return 2
        # A JAX-free doctor invocation must read the report.
        code = (
            _NO_JAX_PREAMBLE
            + "from alphatriangle_tpu.cli import main\n"
            + f"sys.exit(main(['doctor', {str(run_dir)!r}, '--json']))\n"
        )
        doc = subprocess.run(
            [sys.executable, "-c", code],
            cwd=str(REPO),
            env=env,
            capture_output=True,
            text=True,
            timeout=120,
        )
        verdict = None
        for line in doc.stdout.splitlines():
            if line.strip().startswith("{"):
                try:
                    verdict = json.loads(line)
                except json.JSONDecodeError:
                    pass
        if (
            doc.returncode != 7
            or verdict is None
            or verdict.get("verdict") != "preempted"
        ):
            print(
                f"chaos-smoke: doctor on a preempted run gave "
                f"rc={doc.returncode}, verdict={verdict}\n"
                f"stderr: {doc.stderr}",
                file=sys.stderr,
            )
            return 2
        # Rerun (sentinel already claimed: the fault cannot refire) and
        # require completion from the emergency checkpoint.
        assert armed  # env still armed: the sentinel is what protects us
        second = subprocess.run(argv, cwd=str(REPO), env=env, timeout=600)
    if second.returncode != 0:
        print(
            f"chaos-smoke: preempt resume failed (rc={second.returncode})",
            file=sys.stderr,
        )
        return 2
    final = committed_steps(run_dir)
    if not final or final[-1] != MAX_STEPS:
        print(
            f"chaos-smoke: preempt resume did not reach a committed "
            f"step {MAX_STEPS} (committed: {final})",
            file=sys.stderr,
        )
        return 2
    print(
        f"chaos-smoke: SIGTERM@step3 -> exit 114 + emergency checkpoint "
        f"at step {ckpt_step} (committed) -> doctor 'preempted' (exit 7) "
        f"-> resume completed at step {final[-1]}"
    )
    return 0


def stage_torn_checkpoint(root: Path) -> int:
    """SIGKILL mid-checkpoint-save -> the uncommitted step dir is
    skipped and the supervised restart resumes from the prior committed
    step."""
    from alphatriangle_tpu.supervise import RecoveryPolicy, Supervisor

    run = "chaos_torn"
    run_dir = run_dir_for(str(root), run)
    with _ArmedFaults("sigkill-save@step=4", root / "faults_torn"):
        policy = RecoveryPolicy(backoff_base_s=0.2, backoff_max_s=2.0)
        rc = Supervisor(child_argv(str(root), run), run_dir, policy).run()
    if rc != 0:
        print(
            f"chaos-smoke: supervised torn-checkpoint run did not "
            f"complete (rc={rc})",
            file=sys.stderr,
        )
        return 2
    deaths = [
        e for e in supervisor_events(run_dir) if e.get("event") == "death"
    ]
    if not deaths:
        print(
            "chaos-smoke: no death event after the sigkill-save fault",
            file=sys.stderr,
        )
        return 2
    death = deaths[0]
    progress = death.get("progress_step")
    # Killed DURING the step-4 save, before its commit marker: the
    # supervisor must report the prior committed step as the restart
    # point, never the torn step-4 directory.
    if progress != 4 - CKPT_CADENCE:
        print(
            f"chaos-smoke: expected restart from the prior committed "
            f"step {4 - CKPT_CADENCE}, supervisor saw "
            f"progress_step={progress} ({death})",
            file=sys.stderr,
        )
        return 2
    final = committed_steps(run_dir)
    if not final or final[-1] != MAX_STEPS:
        print(
            f"chaos-smoke: torn-checkpoint run did not finish at a "
            f"committed step {MAX_STEPS} (committed: {final})",
            file=sys.stderr,
        )
        return 2
    print(
        f"chaos-smoke: SIGKILL mid-save at step 4 -> torn dir skipped, "
        f"restart from committed step {progress} -> completed at step "
        f"{final[-1]}"
    )
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    parser.add_argument("--root-dir", default=None)
    parser.add_argument("--run-name", default="chaos_child")
    args = parser.parse_args()

    if args.child:
        return child(args)

    root = Path(args.root_dir or tempfile.mkdtemp(prefix="at_chaos_smoke_"))
    t0 = time.monotonic()
    try:
        for stage in (
            stage_jax_free_supervisor,
            stage_wedge_restart,
            stage_preempt,
            stage_torn_checkpoint,
        ):
            rc = stage(root)
            if rc != 0:
                return rc
    finally:
        if args.root_dir is None:
            shutil.rmtree(root, ignore_errors=True)
    print(f"chaos-smoke: OK ({time.monotonic() - t0:.0f}s)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
