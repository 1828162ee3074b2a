"""Self-healing run supervisor (`cli supervise`).

A JAX-free parent that runs a training (or league) child, classifies
every death with the same evidence `cli doctor` reads, and applies the
`RecoveryPolicy` verdict->action matrix: restart from the latest
committed checkpoint with backoff, degrade/quarantine knobs, or give
up with `SUPERVISOR_GIVEUP_EXIT_CODE` when the chip is permanently
sick. Podracer-style (arXiv:2104.06272): preemptible accelerators are
the NORMAL case, so checkpoint-restart is the availability story, not
an operator heroic.

Everything is logged to `runs/<run>/supervisor.jsonl` as crash-safe
one-line events (`MetricsLedger` append discipline): spawn, death
(with verdict + evidence + the action taken), give-up, complete: the
death->verdict->restart chain of the run.

JAX-free contract: like `cli doctor`, this module must keep working
beside a wedged chip — it imports only stdlib + the telemetry readers
+ the policy. The child is where JAX lives. (Pinned by the import
guard in benchmarks/chaos_smoke.py.)
"""

import json
import logging
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

from ..telemetry.flight import (
    FLIGHT_FILENAME,
    PREEMPT_EXIT_CODE,
    PREEMPT_REPORT_FILENAME,
    SUPERVISOR_GIVEUP_EXIT_CODE,
    WEDGE_REPORT_FILENAME,
    WEDGE_STACKS_FILENAME,
    classify_run,
    read_flight,
    read_preempt_report,
    read_wedge_report,
)
from ..telemetry import tracectx
from ..telemetry.ledger import MetricsLedger, read_ledger, resolve_ledger_path
from .policy import Action, RecoveryPolicy

logger = logging.getLogger(__name__)

SUPERVISOR_FILENAME = "supervisor.jsonl"

#: Env var carrying the accumulated recovery overrides to the child
#: (JSON object; applied by training/runner.py onto TrainConfig).
OVERRIDES_ENV = "ALPHATRIANGLE_SUPERVISE_OVERRIDES"


def latest_committed_step(run_dir: Path | str) -> "int | None":
    """Newest trustworthy checkpoint step in a run dir, read straight
    off the filesystem (this parent must stay JAX-free, so it cannot
    import stats.persistence — same marker semantics though: commit
    markers when the run has any, meta-parseable step dirs otherwise)."""
    ckpt_dir = Path(run_dir) / "checkpoints"
    if not ckpt_dir.is_dir():
        return None
    committed = set()
    for p in ckpt_dir.glob("step_*.commit"):
        stem = p.name[len("step_"):-len(".commit")]
        if stem.isdigit():
            committed.add(int(stem))
    if committed:
        return max(committed)
    steps = []
    for p in ckpt_dir.iterdir():
        if not (p.is_dir() and p.name.startswith("step_")):
            continue
        suffix = p.name[len("step_"):]
        if not suffix.isdigit():
            continue
        meta = ckpt_dir / f"{p.name}.meta.json"
        try:
            json.loads(meta.read_text())
        except (OSError, ValueError):
            continue
        steps.append(int(suffix))
    return max(steps) if steps else None


def diagnose(run_dir: Path | str, since: float = 0.0) -> dict:
    """`cli doctor`'s classification over the run dir's evidence,
    restricted to records from the current attempt (`since`, an epoch
    time): a prior attempt's torn intent or stale heartbeat must not
    pollute the verdict for THIS death."""
    run_dir = Path(run_dir)
    flight = [
        r
        for r in read_flight(run_dir / FLIGHT_FILENAME)
        if float(r.get("time") or 0.0) >= since
    ]
    health = None
    try:
        payload = json.loads((run_dir / "health.json").read_text())
        if (
            isinstance(payload, dict)
            and float(payload.get("time") or 0.0) >= since
        ):
            health = payload
    except (OSError, ValueError):
        pass
    ledger = resolve_ledger_path(run_dir)
    utils = [
        r
        for r in (read_ledger(ledger, kinds={"util"}) if ledger else [])
        if float(r.get("time") or 0.0) >= since
    ]
    wedge = read_wedge_report(run_dir / WEDGE_REPORT_FILENAME)
    if wedge is not None and float(wedge.get("time") or 0.0) < since:
        wedge = None
    preempt = read_preempt_report(run_dir / PREEMPT_REPORT_FILENAME)
    if preempt is not None and float(preempt.get("time") or 0.0) < since:
        preempt = None
    return classify_run(
        flight, health=health, utils=utils, wedge=wedge, preempt=preempt
    )


class Supervisor:
    """Spawn/classify/recover loop around one child command.

    `popen` and `sleep` are injectable for tests; the production path
    is `subprocess.Popen` + `time.sleep`.
    """

    def __init__(
        self,
        child_argv: list[str],
        run_dir: Path | str,
        policy: "RecoveryPolicy | None" = None,
        *,
        popen=subprocess.Popen,
        sleep=time.sleep,
        now=time.time,
    ) -> None:
        self.child_argv = list(child_argv)
        self.run_dir = Path(run_dir)
        self.policy = policy or RecoveryPolicy()
        self._popen = popen
        self._sleep = sleep
        self._now = now
        self.run_dir.mkdir(parents=True, exist_ok=True)
        self._ledger = MetricsLedger(self.run_dir / SUPERVISOR_FILENAME)
        # Supervision-lifetime root trace (telemetry/tracectx.py):
        # every attempt gets a child context, stamped on its
        # supervisor.jsonl events and handed to the child via the
        # traceparent env seam (its flight ring adopts it), so one
        # trace_id links a spawn to everything that attempt dispatched.
        self.trace_ctx = tracectx.mint(parent=tracectx.from_env())
        self._attempt_ctx: "tracectx.TraceContext | None" = None
        self._child = None
        self._terminating = False

    # --- events -----------------------------------------------------------

    def _event(self, event: str, **fields) -> None:
        ctx = self._attempt_ctx or self.trace_ctx
        self._ledger.append(
            {
                "kind": "supervisor",
                "event": event,
                "time": self._now(),
                "pid": os.getpid(),
                **ctx.fields(),
                **fields,
            }
        )

    # --- signals ----------------------------------------------------------

    def _forward_signal(self, signum, frame) -> None:
        self._terminating = True
        child = self._child
        self._event("forward-signal", signum=int(signum))
        if child is not None and child.poll() is None:
            try:
                child.send_signal(signum)
            except OSError:
                pass

    # --- restart hygiene --------------------------------------------------

    def _archive_attempt_reports(self, attempt: int) -> None:
        """Move the one-shot report files aside so the next attempt's
        diagnosis can't read this attempt's death certificate."""
        for name in (
            WEDGE_REPORT_FILENAME,
            PREEMPT_REPORT_FILENAME,
            WEDGE_STACKS_FILENAME,
        ):
            path = self.run_dir / name
            if path.exists():
                try:
                    os.replace(path, self.run_dir / f"{name}.attempt{attempt}")
                except OSError:
                    pass

    # --- main loop --------------------------------------------------------

    def run(self) -> int:
        """Supervise until the child completes (0), the policy gives up
        (115), or a forwarded SIGTERM/SIGINT ends the window (child's
        own exit code, normally 114)."""
        overrides: dict = {}
        installed = threading.current_thread() is threading.main_thread()
        prev_handlers = {}
        if installed:
            for sig in (signal.SIGTERM, signal.SIGINT):
                prev_handlers[sig] = signal.signal(sig, self._forward_signal)
        attempt = 0
        try:
            while True:
                attempt += 1
                self._attempt_ctx = self.trace_ctx.child()
                env = tracectx.child_env(self._attempt_ctx)
                if overrides:
                    env[OVERRIDES_ENV] = json.dumps(overrides)
                spawn_t = self._now()
                self._event(
                    "spawn",
                    attempt=attempt,
                    argv=self.child_argv,
                    overrides=overrides,
                )
                self._child = self._popen(self.child_argv, env=env)
                rc = self._child.wait()
                self._child = None
                if self._terminating:
                    self._event("terminated", attempt=attempt, rc=rc)
                    return rc if rc else PREEMPT_EXIT_CODE
                if rc == 0:
                    self._event("complete", attempt=attempt)
                    return 0
                verdict = diagnose(self.run_dir, since=spawn_t)
                progress = latest_committed_step(self.run_dir)
                action = self.policy.decide(
                    verdict=verdict["verdict"],
                    exit_code=rc,
                    family=verdict.get("family"),
                    progress_step=progress,
                )
                self._event(
                    "death",
                    attempt=attempt,
                    rc=rc,
                    verdict=verdict["verdict"],
                    program=verdict.get("program"),
                    family=verdict.get("family"),
                    detail=verdict.get("detail"),
                    progress_step=progress,
                    action=action.kind,
                    delay_s=action.delay_s,
                    overrides=action.overrides,
                    reason=action.reason,
                )
                logger.warning(
                    "child died (rc=%d, verdict=%s, progress=%s) -> %s: %s",
                    rc,
                    verdict["verdict"],
                    progress,
                    action.kind,
                    action.reason,
                )
                if action.kind != "restart":
                    self._event("give-up", reason=action.reason)
                    return SUPERVISOR_GIVEUP_EXIT_CODE
                self._archive_attempt_reports(attempt)
                overrides = action.overrides
                if action.delay_s > 0:
                    self._sleep(action.delay_s)
        finally:
            if installed:
                for sig, handler in prev_handlers.items():
                    signal.signal(sig, handler)


def supervise_command(
    child_argv: list[str],
    run_dir: Path | str,
    policy: "RecoveryPolicy | None" = None,
) -> int:
    """Convenience wrapper for `cli supervise`."""
    return Supervisor(child_argv, run_dir, policy=policy).run()
