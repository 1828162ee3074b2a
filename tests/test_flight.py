"""Dispatch flight recorder + window forensics (telemetry/flight.py,
`cli doctor`).

Everything here is JAX-free and fast: the recorder/watchdog/classifier
are pure host-side machinery, and the crash-path tests run real
subprocesses (SIGKILL mid-dispatch, import-guarded doctor) — the same
evidence chain `cli supervise` relies on when a run dies.
Real-dispatch integration (the four hot sites actually sealing
records) is gated by `make perf-smoke`, not here, to keep tier-1 fast.
"""

import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

import pytest

from alphatriangle_tpu.telemetry.flight import (
    DOCTOR_EXIT_CODES,
    FLIGHT_FILENAME,
    WEDGE_EXIT_CODE,
    WEDGE_REPORT_FILENAME,
    WEDGE_STACKS_FILENAME,
    DispatchWatchdog,
    FlightRecorder,
    classify_run,
    family_seconds,
    flight_span,
    program_family,
    read_flight,
    read_wedge_report,
    summarize_flight,
    unsealed_intents,
)

REPO = Path(__file__).resolve().parent.parent


def _flight_line(**fields) -> str:
    return json.dumps({"kind": "flight", **fields}) + "\n"


def _intent(seq, program="megastep/t4_k2", family="megastep", **kw):
    return {
        "kind": "flight", "phase": "intent", "seq": seq,
        "program": program, "family": family, "avals": "B4",
        "expected_s": kw.pop("expected_s", None),
        "deadline_s": kw.pop("deadline_s", 900.0),
        "t_mono": float(seq), "time": kw.pop("time", 100.0 + seq),
        "pid": 1, **kw,
    }


def _seal(seq, program="megastep/t4_k2", family="megastep", **kw):
    return {
        "kind": "flight", "phase": "seal", "seq": seq,
        "program": program, "family": family,
        "wall_s": kw.pop("wall_s", 1.0), "ok": kw.pop("ok", True),
        "t_mono": float(seq) + 1, "time": kw.pop("time", 101.0 + seq),
        **kw,
    }


class TestProgramFamily:
    def test_hot_families(self):
        assert program_family("self_play_chunk/t8") == "rollout"
        assert program_family("learner_step") == "learner"
        assert program_family("learner_fused_from_sharded_ring/s2_dp") == "learner"
        assert program_family("megastep/dp2_t4_k2") == "megastep"
        assert program_family("serve/b64") == "serve"
        assert program_family("reuse/promote_b64") == "reuse"
        assert program_family("warm/xyz") == "warm"


class TestFlightRecorder:
    def test_intent_seal_round_trip(self, tmp_path):
        rec = FlightRecorder(tmp_path / FLIGHT_FILENAME)
        with flight_span(rec, "rollout", "self_play_chunk/t4", avals="B4xT4"):
            pass
        records = read_flight(tmp_path / FLIGHT_FILENAME)
        assert [r["phase"] for r in records] == ["intent", "seal"]
        intent, seal = records
        assert intent["program"] == seal["program"] == "self_play_chunk/t4"
        assert intent["family"] == "rollout"
        assert intent["avals"] == "B4xT4"
        assert intent["seq"] == seal["seq"] == 1
        assert intent["deadline_s"] == rec.first_deadline_s
        assert seal["ok"] is True and seal["wall_s"] >= 0
        assert not unsealed_intents(records)
        assert rec.dispatches == 1

    def test_expected_ewma_calibrates_deadline(self, tmp_path):
        rec = FlightRecorder(
            tmp_path / FLIGHT_FILENAME, min_deadline_s=0.5,
            deadline_factor=10.0,
        )
        rec.begin("learner", "learner_step").seal()
        first_wall = rec.expected_s("learner_step")
        assert first_wall is not None
        rec.begin("learner", "learner_step").seal()
        records = read_flight(tmp_path / FLIGHT_FILENAME)
        second_intent = [r for r in records if r["phase"] == "intent"][1]
        # The record rounds expected_s to 6 decimals.
        assert second_intent["expected_s"] == pytest.approx(
            first_wall, abs=1e-6
        )
        assert second_intent["deadline_s"] == pytest.approx(
            max(0.5, 10.0 * first_wall), abs=1e-3
        )

    def test_new_recorder_inherits_prior_seals(self, tmp_path):
        path = tmp_path / FLIGHT_FILENAME
        path.write_text(
            _flight_line(**_intent(1)) + _flight_line(**_seal(1, wall_s=3.0))
        )
        rec = FlightRecorder(path)
        assert rec.expected_s("megastep/t4_k2") == pytest.approx(3.0)

    def test_error_seal_is_not_torn(self, tmp_path):
        rec = FlightRecorder(tmp_path / FLIGHT_FILENAME)
        with pytest.raises(RuntimeError):
            with flight_span(rec, "learner", "learner_step"):
                raise RuntimeError("boom")
        records = read_flight(tmp_path / FLIGHT_FILENAME)
        seal = records[-1]
        assert seal["phase"] == "seal" and seal["ok"] is False
        assert "boom" in seal["error"]
        assert not unsealed_intents(records)

    def test_span_seal_idempotent(self, tmp_path):
        rec = FlightRecorder(tmp_path / FLIGHT_FILENAME)
        span = rec.begin("serve", "serve/b8")
        span.seal()
        span.seal()
        records = read_flight(tmp_path / FLIGHT_FILENAME)
        assert sum(1 for r in records if r["phase"] == "seal") == 1

    def test_none_recorder_is_noop(self):
        with flight_span(None, "learner", "learner_step") as span:
            assert span is None

    def test_close_writes_overhead_summary(self, tmp_path):
        from alphatriangle_tpu.telemetry.ledger import iter_jsonl_records

        path = tmp_path / FLIGHT_FILENAME
        rec = FlightRecorder(path)
        rec.begin("learner", "learner_step").seal()
        rec.close()
        summaries = [
            r
            for r in iter_jsonl_records(path)
            if r.get("kind") == "flight_overhead"
        ]
        assert len(summaries) == 1
        assert summaries[0]["dispatches"] == 1
        assert summaries[0]["overhead_s"] >= 0

    def test_byte_torn_tail_tolerated(self, tmp_path):
        """Shared-reader regression (the ledger's tolerant tail
        handling must cover the flight ring too): a mid-record SIGKILL
        leaves junk bytes the readers skip without losing the sealed
        history before them."""
        path = tmp_path / FLIGHT_FILENAME
        path.write_text(
            _flight_line(**_intent(1))
            + _flight_line(**_seal(1))
            + _flight_line(**_intent(2))
            + '{"kind": "flight", "phase": "seal", "seq": 2, "wa\x00'
        )
        records = read_flight(path)
        assert len(records) == 3
        torn = unsealed_intents(records)
        assert [t["seq"] for t in torn] == [2]
        # And a fresh recorder over the torn file still seeds from the
        # intact seal.
        rec = FlightRecorder(path)
        assert rec.expected_s("megastep/t4_k2") == pytest.approx(1.0)


class TestSummaries:
    def test_summarize_and_family_seconds(self):
        records = []
        for seq, wall in enumerate([1.0, 2.0, 3.0], 1):
            records.append(_intent(seq, program="learner_step", family="learner"))
            records.append(
                _seal(seq, program="learner_step", family="learner", wall_s=wall)
            )
        records.append(_intent(9, program="serve/b8", family="serve"))
        records.append(
            _seal(9, program="serve/b8", family="serve", ok=False, error="x")
        )
        rows = summarize_flight(records)
        assert [r["program"] for r in rows] == ["learner_step", "serve/b8"]
        top = rows[0]
        assert top["count"] == 3 and top["errors"] == 0
        assert top["wall_s_p50"] == pytest.approx(2.0)
        assert top["wall_s_total"] == pytest.approx(6.0)
        assert rows[1]["errors"] == 1 and rows[1]["count"] == 0
        fams = family_seconds(records)
        assert fams == {"learner": pytest.approx(2.0)}


class TestDispatchWatchdog:
    def _pair(self, tmp_path, **kw):
        clock = {"t": 0.0}
        wd = DispatchWatchdog(
            tmp_path, exit_on_wedge=False, clock=lambda: clock["t"], **kw
        )
        rec = FlightRecorder(
            tmp_path / FLIGHT_FILENAME, watchdog=wd,
            min_deadline_s=5.0, first_deadline_s=10.0,
        )
        return clock, wd, rec

    def test_no_fire_before_deadline(self, tmp_path):
        clock, wd, rec = self._pair(tmp_path)
        rec.begin("learner", "learner_step")
        clock["t"] += 9.0
        assert wd.check() is None

    def test_seal_disarms(self, tmp_path):
        clock, wd, rec = self._pair(tmp_path)
        rec.begin("learner", "learner_step").seal()
        clock["t"] += 1e6
        assert wd.check() is None

    def test_fires_once_with_report_and_stacks(self, tmp_path):
        clock, wd, rec = self._pair(tmp_path)
        hooks = []
        wd.on_wedge = hooks.append
        rec.begin("learner", "learner_step", avals="B8")
        clock["t"] += 11.0
        report = wd.check()
        assert report is not None
        assert report["program"] == "learner_step"
        assert report["elapsed_s"] == pytest.approx(11.0)
        assert report["exit_code"] is None  # exit_on_wedge off
        assert hooks and hooks[0]["program"] == "learner_step"
        on_disk = read_wedge_report(tmp_path / WEDGE_REPORT_FILENAME)
        assert on_disk["program"] == "learner_step"
        assert (tmp_path / WEDGE_STACKS_FILENAME).read_text()
        # Latch: one wedge per process, however long it stays overdue.
        clock["t"] += 100.0
        assert wd.check() is None
        assert wd.wedge_count == 1


class TestClassifyRun:
    def test_never_started(self):
        v = classify_run([])
        assert v["verdict"] == "never-started"
        assert v["exit_code"] == DOCTOR_EXIT_CODES["never-started"] == 2

    def test_clean(self):
        v = classify_run([_intent(1), _seal(1)])
        assert v["verdict"] == "clean" and v["exit_code"] == 0

    def test_compile_hung_on_first_dispatch(self):
        v = classify_run([_intent(1)])
        assert v["verdict"] == "compile-hung" and v["exit_code"] == 3
        assert v["program"] == "megastep/t4_k2"

    def test_dispatch_hung_after_prior_seal(self):
        v = classify_run([_intent(1), _seal(1), _intent(2)])
        assert v["verdict"] == "dispatch-hung" and v["exit_code"] == 4
        assert v["program"] == "megastep/t4_k2"
        assert v["family"] == "megastep"

    def test_wedge_report_is_strongest_evidence(self):
        wedge = {
            "program": "serve/b64", "family": "serve",
            "elapsed_s": 99.0, "deadline_s": 9.0,
        }
        v = classify_run(
            [_intent(1), _seal(1)], wedge=wedge
        )
        assert v["verdict"] == "compile-hung"  # serve/b64 never sealed
        assert v["program"] == "serve/b64"
        assert v["evidence"]["wedge_report"] is True

    def test_oom_precedence_over_hung(self):
        v = classify_run(
            [_intent(1)], utils=[{"kind": "util", "mem_utilization": 0.97}]
        )
        assert v["verdict"] == "oom" and v["exit_code"] == 6
        assert v["program"] == "megastep/t4_k2"

    def test_host_stall_from_stalled_heartbeat(self):
        v = classify_run(
            [_intent(1), _seal(1)],
            health={"time": 200.0, "stalled": True},
        )
        assert v["verdict"] == "host-stall" and v["exit_code"] == 5

    def test_host_stall_from_beating_past_last_seal(self):
        v = classify_run(
            [_intent(1), _seal(1, time=100.0)],
            health={
                "time": 100.0 + 2 * 300.0 + 1,
                "stalled": False,
                "watchdog_deadline_s": 300.0,
            },
        )
        assert v["verdict"] == "host-stall"


# The crash-path child: seals one dispatch, begins a second, announces
# readiness, then sleeps inside the bracket until SIGKILLed.
_CRASH_CHILD = """
import sys, time
from alphatriangle_tpu.telemetry.flight import FlightRecorder, flight_span
rec = FlightRecorder({path!r})
with flight_span(rec, "megastep", "megastep/t4_k2", avals="B4xT4xK2"):
    pass
span = rec.begin("megastep", "megastep/t4_k2", avals="B4xT4xK2")
print("IN_DISPATCH", flush=True)
time.sleep(120)
"""


class TestCrashPath:
    @pytest.fixture()
    def killed_run(self, tmp_path):
        """A real process SIGKILLed mid-dispatch, like a wedge or an
        external kill -9: the flight ring must carry the evidence."""
        path = str(tmp_path / FLIGHT_FILENAME)
        proc = subprocess.Popen(
            [sys.executable, "-c", _CRASH_CHILD.format(path=path)],
            cwd=str(REPO),
            stdout=subprocess.PIPE,
            text=True,
        )
        try:
            line = proc.stdout.readline()
            assert "IN_DISPATCH" in line
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        return tmp_path

    def test_sigkill_leaves_torn_intent(self, killed_run):
        records = read_flight(killed_run / FLIGHT_FILENAME)
        torn = unsealed_intents(records)
        assert len(torn) == 1
        assert torn[0]["program"] == "megastep/t4_k2"
        v = classify_run(records)
        assert v["verdict"] == "dispatch-hung"
        assert v["program"] == "megastep/t4_k2"

    def test_cli_doctor_names_program_without_jax(self, killed_run):
        """The full postmortem invocation: `cli
        doctor` in a subprocess whose import machinery refuses jax,
        exiting nonzero with the hung program named."""
        code = (
            "import builtins, sys\n"
            "real = builtins.__import__\n"
            "def guard(name, *a, **k):\n"
            "    if name == 'jax' or name.startswith('jax.'):\n"
            "        raise AssertionError('cli doctor imported ' + name)\n"
            "    return real(name, *a, **k)\n"
            "builtins.__import__ = guard\n"
            "from alphatriangle_tpu.cli import main\n"
            f"sys.exit(main(['doctor', {str(killed_run)!r}, '--json']))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            cwd=str(REPO),
            timeout=120,
        )
        assert proc.returncode == DOCTOR_EXIT_CODES["dispatch-hung"], (
            proc.stdout + proc.stderr
        )
        verdict = json.loads(proc.stdout.strip().splitlines()[-1])
        assert verdict["verdict"] == "dispatch-hung"
        assert verdict["program"] == "megastep/t4_k2"
        assert verdict["family"] == "megastep"


class TestWatchIntegration:
    def test_fold_flight_line_and_render(self, tmp_path):
        from alphatriangle_tpu.stats.watch import (
            WatchState,
            last_dispatch_line,
            render_frame,
            tail_flight,
        )

        path = tmp_path / FLIGHT_FILENAME
        now = time.time()
        path.write_text(
            _flight_line(**_intent(1, time=now - 30))
            + _flight_line(**_seal(1, time=now - 20, wall_s=2.0))
        )
        state = WatchState()
        offset = tail_flight(path, state, 0)
        assert offset == path.stat().st_size
        line = last_dispatch_line(state, now=now)
        assert "megastep/t4_k2" in line and "sealed" in line
        # A newer unsealed intent flips the line to in-flight with the
        # deadline visible.
        with path.open("a") as f:
            f.write(
                _flight_line(
                    **_intent(2, time=now - 5, expected_s=2.0, deadline_s=20.0)
                )
            )
        tail_flight(path, state, offset)
        line = last_dispatch_line(state, now=now)
        assert "in flight" in line and "deadline" in line
        assert "OVER DEADLINE" not in line
        line_late = last_dispatch_line(state, now=now + 100)
        assert "OVER DEADLINE" in line_late
        frame = render_frame(state, "runx")
        assert "megastep/t4_k2" in frame

    def test_no_flight_records_renders_nothing(self):
        from alphatriangle_tpu.stats.watch import WatchState, last_dispatch_line

        assert last_dispatch_line(WatchState()) is None


class TestCliIntegration:
    def _run_dir(self, tmp_path):
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
        (tmp_path / "metrics.jsonl").write_text("\n".join(utils) + "\n")
        (tmp_path / FLIGHT_FILENAME).write_text(
            _flight_line(**_intent(1, program="serve/b8", family="serve"))
            + _flight_line(
                **_seal(1, program="serve/b8", family="serve", wall_s=0.5)
            )
        )
        return tmp_path

    def test_cli_perf_json_programs(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        run_dir = self._run_dir(tmp_path)
        rc = cli_main(["perf", str(run_dir), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        programs = summary["programs"]
        assert programs[0]["program"] == "serve/b8"
        assert programs[0]["wall_s_p50"] == pytest.approx(0.5)

    def test_cli_doctor_clean_run(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        run_dir = self._run_dir(tmp_path)
        rc = cli_main(["doctor", str(run_dir)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "clean" in out

    def test_cli_doctor_missing_run_exits_2(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        rc = cli_main(["doctor", "no_such_run", "--root-dir", str(tmp_path)])
        assert rc == 2


class TestCalibrationIntegration:
    def test_family_seconds_flow_into_calibration(self, tmp_path):
        from alphatriangle_tpu.autotune.model import (
            Calibration,
            merge_calibrations,
        )

        a = Calibration(family_seconds={"megastep": 2.0, "serve": 0.1})
        b = Calibration(family_seconds={"megastep": 4.0})
        merged = merge_calibrations([a, b])
        assert merged.family_seconds["megastep"] == pytest.approx(3.0)
        assert merged.family_seconds["serve"] == pytest.approx(0.1)
        assert merged.as_dict()["family_seconds"]["megastep"] == pytest.approx(3.0)


class TestWedgeExitCodeContract:
    def test_exit_code_outside_shell_ranges(self):
        """Supervisors branch on 113; it must stay clear of shell
        (1, 2, 126-165, 255) and doctor (0-6) codes."""
        assert WEDGE_EXIT_CODE == 113
        assert WEDGE_EXIT_CODE not in DOCTOR_EXIT_CODES.values()


class TestLegacyBeaconTolerance:
    """Run dirs from BEFORE the beacon channel existed (no
    beacons.jsonl, wedge reports without `last_beacon`) must classify
    and doctor exactly as they always did — no beacon line invented,
    no `last_beacon` key in the verdict."""

    def test_last_beacon_missing_file_is_none(self, tmp_path):
        from alphatriangle_tpu.telemetry.device_stats import last_beacon

        assert last_beacon(tmp_path) is None
        assert last_beacon(tmp_path / "ghost" / "beacons.jsonl") is None

    def test_classify_legacy_wedge_report_no_beacon_key(self):
        wedge = {
            "program": "megastep/t4_k2",
            "family": "megastep",
            "elapsed_s": 99.0,
            "deadline_s": 5.0,
        }
        v = classify_run([_intent(1), _seal(1), _intent(2)], wedge=wedge)
        assert v["verdict"] == "dispatch-hung"
        assert "last_beacon" not in v
        assert "last beacon" not in v["detail"]

    def test_classify_caller_beacon_fallback(self):
        """When the wedge report predates the beacon field, a caller-
        read beacons.jsonl row still names the phase."""
        wedge = {"program": "megastep/t4_k2", "family": "megastep",
                 "elapsed_s": 99.0, "deadline_s": 5.0}
        row = {"program": "megastep/t4_k2", "phase": "learner_step",
               "index": 7, "monotonic": 12.5}
        v = classify_run(
            [_intent(1), _seal(1), _intent(2)], wedge=wedge, beacon=row
        )
        assert v["last_beacon"] == row
        assert "last beacon" in v["detail"]
        assert "learner_step" in v["detail"]

    def test_cli_doctor_legacy_run_prints_no_beacon(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        run = tmp_path / "legacy_run"
        run.mkdir()
        (run / FLIGHT_FILENAME).write_text(
            _flight_line(**_intent(1))
            + _flight_line(**_seal(1))
            + _flight_line(**_intent(2))
        )
        rc = cli_main(["doctor", str(run)])
        out = capsys.readouterr().out
        assert rc == DOCTOR_EXIT_CODES["dispatch-hung"]
        assert "beacon" not in out

    def test_cli_doctor_legacy_json_has_no_beacon_key(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        run = tmp_path / "legacy_run_json"
        run.mkdir()
        (run / FLIGHT_FILENAME).write_text(
            _flight_line(**_intent(1))
            + _flight_line(**_seal(1))
            + _flight_line(**_intent(2))
        )
        rc = cli_main(["doctor", str(run), "--json"])
        verdict = json.loads(capsys.readouterr().out)
        assert rc == DOCTOR_EXIT_CODES["dispatch-hung"]
        assert "last_beacon" not in verdict
