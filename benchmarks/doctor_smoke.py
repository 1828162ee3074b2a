"""CI window-forensics gate: torn flight ring -> `cli doctor` verdict.

`make doctor-smoke` runs this. It proves, with no accelerator and no
training run, that the postmortem pipeline (docs/OBSERVABILITY.md
"Flight recorder & forensics") still closes end to end:

1. a synthetic run dir with sealed flight records, a final UNSEALED
   intent and byte-torn trailing junk — the exact artifact a SIGKILLed
   run leaves — must classify as dispatch-hung naming the hung program,
   via a `cli doctor` subprocess with JAX imports hard-blocked;
2. a simulated over-deadline dispatch (real `FlightRecorder` +
   `DispatchWatchdog` with a frozen clock and exit-on-wedge off) must
   dump stacks, write `wedge_report.json`, and doctor to the same
   verdict with the wedge report as evidence;
3. sealed flight records beside a minimal metrics ledger must surface
   as per-program device-time rows in `cli perf --json`.

Exit 0 when every stage passes; the first failing stage's code
otherwise.
"""

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

# Hard import-guard preamble for the doctor subprocess: any jax import
# on the doctor path raises, exactly like tests/test_flight.py's guard.
_NO_JAX_PREAMBLE = (
    "import builtins, sys;"
    "_real = builtins.__import__;\n"
    "def _guard(name, *a, **k):\n"
    "    if name == 'jax' or name.startswith('jax.'):\n"
    "        raise ImportError('cli doctor must not import jax: ' + name)\n"
    "    return _real(name, *a, **k)\n"
    "builtins.__import__ = _guard\n"
)


def run_doctor(run_dir: Path) -> "tuple[int, dict | None]":
    """`cli doctor <run_dir> --json` in a subprocess with jax imports
    blocked."""
    code = (
        _NO_JAX_PREAMBLE
        + "from alphatriangle_tpu.cli import main\n"
        + f"sys.exit(main(['doctor', {str(run_dir)!r}, '--json']))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)},
        capture_output=True,
        text=True,
        timeout=120,
    )
    verdict = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                verdict = json.loads(line)
            except json.JSONDecodeError:
                pass
    if verdict is None:
        print(
            f"doctor-smoke: no JSON verdict from cli doctor "
            f"(rc={proc.returncode})\nstdout: {proc.stdout}\n"
            f"stderr: {proc.stderr}",
            file=sys.stderr,
        )
    return proc.returncode, verdict


def flight_line(**fields) -> str:
    return json.dumps({"kind": "flight", **fields}) + "\n"


def stage_torn_ring(root: Path) -> int:
    """A SIGKILLed run's artifact, synthesized byte for byte: sealed
    history, one unsealed intent, a torn trailing line."""
    run_dir = root / "torn_ring"
    run_dir.mkdir(parents=True)
    now = time.time()
    lines = [
        flight_line(
            phase="intent", seq=1, program="self_play_chunk/t4",
            family="rollout", avals="B4xT4", expected_s=None,
            deadline_s=900.0, t_mono=10.0, time=now - 120, pid=4242,
        ),
        flight_line(
            phase="seal", seq=1, program="self_play_chunk/t4",
            family="rollout", wall_s=2.5, ok=True, t_mono=12.5,
            time=now - 117,
        ),
        flight_line(
            phase="intent", seq=2, program="self_play_chunk/t4",
            family="rollout", avals="B4xT4", expected_s=2.5,
            deadline_s=60.0, t_mono=13.0, time=now - 110, pid=4242,
        ),
        # Unsealed: the process was SIGKILLed inside this dispatch.
    ]
    torn = '{"kind": "flight", "phase": "seal", "seq": 2, "wal'
    (run_dir / "flight.jsonl").write_text("".join(lines) + torn)
    # A stale heartbeat (no stall flag — the process just vanished).
    (run_dir / "health.json").write_text(
        json.dumps(
            {"time": now - 110, "stalled": False, "learner_step": 0,
             "watchdog_deadline_s": 300.0}
        )
    )
    rc, verdict = run_doctor(run_dir)
    if verdict is None:
        return 2
    if (
        verdict.get("verdict") != "dispatch-hung"
        or verdict.get("program") != "self_play_chunk/t4"
        or verdict.get("family") != "rollout"
        or rc != 4
    ):
        print(
            f"doctor-smoke: torn ring misclassified: rc={rc}, "
            f"verdict={verdict}",
            file=sys.stderr,
        )
        return 2
    print(
        f"doctor-smoke: torn ring -> {verdict['verdict']} "
        f"({verdict['program']}), exit {rc}, no jax imported"
    )
    return 0


def stage_wedge_watchdog(root: Path) -> int:
    """A live over-deadline dispatch: real recorder + watchdog, frozen
    clock, exit-on-wedge off so the report is observable in-process."""
    from alphatriangle_tpu.telemetry.flight import (
        WEDGE_REPORT_FILENAME,
        WEDGE_STACKS_FILENAME,
        DispatchWatchdog,
        FlightRecorder,
        read_wedge_report,
    )

    run_dir = root / "wedged"
    run_dir.mkdir(parents=True)
    clock = {"t": 1000.0}
    watchdog = DispatchWatchdog(
        run_dir, on_wedge=None, exit_on_wedge=False,
        clock=lambda: clock["t"],
    )
    recorder = FlightRecorder(
        run_dir / "flight.jsonl", watchdog=watchdog,
        min_deadline_s=5.0, first_deadline_s=30.0,
    )
    # One healthy dispatch seals and calibrates the expected duration.
    recorder.begin("megastep", "megastep/t4_k2", avals="B4xT4xK2").seal()
    # The second never seals; advance the frozen clock past deadline.
    recorder.begin("megastep", "megastep/t4_k2", avals="B4xT4xK2")
    if watchdog.check() is not None:
        print(
            "doctor-smoke: watchdog fired before the deadline",
            file=sys.stderr,
        )
        return 2
    clock["t"] += 1e6
    report = watchdog.check()
    if report is None or report.get("program") != "megastep/t4_k2":
        print(
            f"doctor-smoke: watchdog did not fire past deadline "
            f"(report={report})",
            file=sys.stderr,
        )
        return 2
    on_disk = read_wedge_report(run_dir / WEDGE_REPORT_FILENAME)
    stacks = run_dir / WEDGE_STACKS_FILENAME
    if on_disk is None or not stacks.exists() or not stacks.read_text():
        print(
            "doctor-smoke: wedge_report.json or stacks missing",
            file=sys.stderr,
        )
        return 2
    rc, verdict = run_doctor(run_dir)
    if verdict is None:
        return 2
    if (
        verdict.get("verdict") != "dispatch-hung"
        or verdict.get("program") != "megastep/t4_k2"
        or not verdict.get("evidence", {}).get("wedge_report")
        or rc != 4
    ):
        print(
            f"doctor-smoke: wedged run misclassified: rc={rc}, "
            f"verdict={verdict}",
            file=sys.stderr,
        )
        return 2
    print(
        f"doctor-smoke: simulated wedge -> wedge_report.json + stacks, "
        f"doctor {verdict['verdict']} ({verdict['program']}), exit {rc}"
    )
    return 0


def stage_perf_programs(root: Path) -> int:
    """Sealed flight records + a minimal util ledger must yield
    per-program rows in `cli perf --json` (the calibrate feed)."""
    import contextlib
    import io

    from alphatriangle_tpu.cli import main as cli_main

    run_dir = root / "perf_programs"
    run_dir.mkdir(parents=True)
    now = time.time()
    utils = [
        json.dumps(
            {"kind": "util", "step": i, "time": now - 60 + i,
             "window_s": 1.0, "learner_steps_per_sec": 1.0,
             "mfu": 0.01, "tflops_per_sec": 0.01,
             "device_kind": "cpu", "step_time_ms": 10.0}
        )
        for i in range(1, 4)
    ]
    (run_dir / "metrics.jsonl").write_text("\n".join(utils) + "\n")
    lines = []
    for seq, wall in enumerate([0.9, 1.1, 1.0], start=1):
        lines.append(
            flight_line(
                phase="intent", seq=seq, program="learner_fused_steps",
                family="learner", avals="K2xB8", expected_s=None,
                deadline_s=900.0, t_mono=float(seq), time=now - 60 + seq,
                pid=1,
            )
        )
        lines.append(
            flight_line(
                phase="seal", seq=seq, program="learner_fused_steps",
                family="learner", wall_s=wall, ok=True,
                t_mono=float(seq) + wall, time=now - 59 + seq,
            )
        )
    (run_dir / "flight.jsonl").write_text("".join(lines))
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["perf", str(run_dir), "--json"])
    if rc != 0:
        print(f"doctor-smoke: cli perf failed (rc={rc})", file=sys.stderr)
        return 2
    summary = json.loads(buf.getvalue())
    programs = summary.get("programs")
    if not programs:
        print(
            "doctor-smoke: cli perf --json has no programs rows",
            file=sys.stderr,
        )
        return 2
    row = programs[0]
    if (
        row.get("program") != "learner_fused_steps"
        or row.get("count") != 3
        or not isinstance(row.get("wall_s_p50"), (int, float))
        or not isinstance(row.get("wall_s_p95"), (int, float))
    ):
        print(
            f"doctor-smoke: malformed programs row: {row}",
            file=sys.stderr,
        )
        return 2
    print(
        f"doctor-smoke: cli perf --json programs -> "
        f"{row['program']} x{row['count']} "
        f"p50 {row['wall_s_p50']:.2f}s p95 {row['wall_s_p95']:.2f}s"
    )
    return 0


def main() -> int:
    root = Path(tempfile.mkdtemp(prefix="at_doctor_smoke_"))
    try:
        for stage in (stage_torn_ring, stage_wedge_watchdog, stage_perf_programs):
            rc = stage(root)
            if rc != 0:
                return rc
    finally:
        shutil.rmtree(root, ignore_errors=True)
    print("doctor-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
