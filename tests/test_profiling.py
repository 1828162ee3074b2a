"""Profiling subsystem tests (reference `worker.py:549-566` +
`analyze_profiles.py:41-78` equivalents)."""

import json
import time

import pytest

from alphatriangle_tpu.profiling import PhaseTimers, ProfileSession


class TestPhaseTimers:
    def test_accumulates_and_reports(self):
        t = PhaseTimers()
        for _ in range(3):
            with t.phase("work"):
                time.sleep(0.002)
        with t.phase("other"):
            pass
        m = t.metrics()
        assert m["Profile/work_ms"] >= 2.0
        s = t.summary()
        assert s["work"]["count"] == 3
        assert s["other"]["count"] == 1

    def test_dump(self, tmp_path):
        t = PhaseTimers()
        with t.phase("x"):
            pass
        t.dump(tmp_path / "sub" / "phase_timers.json")
        data = json.loads((tmp_path / "sub" / "phase_timers.json").read_text())
        assert data["x"]["count"] == 1

    def test_exception_safe(self):
        t = PhaseTimers()
        try:
            with t.phase("boom"):
                raise RuntimeError
        except RuntimeError:
            pass
        assert t.summary()["boom"]["count"] == 1


class TestProfileSession:
    def test_disabled_is_inert(self, tmp_path):
        s = ProfileSession(enabled=False, profile_dir=tmp_path / "p")
        s.on_iteration(0)
        s.on_iteration(1)
        with s.phase("rollout"):
            pass
        s.close()
        assert not (tmp_path / "p").exists()

    def test_trace_window_and_dump(self, tmp_path):
        import jax
        import jax.numpy as jnp

        s = ProfileSession(
            enabled=True,
            profile_dir=tmp_path / "p",
            trace_start=1,
            trace_stop=2,
        )
        for i in range(3):
            s.on_iteration(i)
            with s.phase("rollout"):
                jnp.square(jnp.arange(8.0)).block_until_ready()
        s.close()
        assert (tmp_path / "p" / "phase_timers.json").exists()
        # jax.profiler writes an xplane trace under plugins/profile/.
        traces = list((tmp_path / "p").glob("**/*.xplane.pb"))
        assert traces, "no device trace written"
        del jax

    def test_close_stops_open_trace(self, tmp_path):
        s = ProfileSession(
            enabled=True, profile_dir=tmp_path / "p", trace_start=0,
            trace_stop=99,
        )
        s.on_iteration(0)  # starts trace; stop never reached
        s.close()  # must stop it and dump timers
        assert (tmp_path / "p" / "phase_timers.json").exists()
        assert list((tmp_path / "p").glob("**/*.xplane.pb"))

    def test_close_dumps_timers_even_when_stop_trace_fails(
        self, tmp_path, monkeypatch
    ):
        import jax

        s = ProfileSession(enabled=True, profile_dir=tmp_path / "p")
        with s.phase("rollout"):
            pass
        s._tracing = True  # as if on_iteration had started a trace

        def boom():
            raise RuntimeError("profiler wedged")

        monkeypatch.setattr(jax.profiler, "stop_trace", boom)
        s.close()  # must not raise
        assert not s._tracing
        data = json.loads(
            (tmp_path / "p" / "phase_timers.json").read_text()
        )
        assert data["rollout"]["count"] == 1

    def test_invalid_trace_window_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="trace_stop"):
            ProfileSession(
                enabled=True, profile_dir=tmp_path / "p",
                trace_start=3, trace_stop=3,
            )

    def test_phase_records_spans_on_attached_tracer(self, tmp_path):
        from alphatriangle_tpu.telemetry import SpanTracer

        tracer = SpanTracer()
        s = ProfileSession(
            enabled=False, profile_dir=tmp_path / "p", tracer=tracer
        )
        with s.phase("rollout"):
            pass
        with s.phase("rollout"):
            pass
        # Both surfaces see the phase: whole-run mean AND per-occurrence
        # spans (disabled device profiling doesn't gate the tracer).
        assert s.timers.summary()["rollout"]["count"] == 2
        assert [r[1] for r in tracer.records()] == ["rollout", "rollout"]


class TestXplaneSummary:
    def test_summarize_real_trace(self, tmp_path, capsys):
        """The in-terminal summary parses a real jax trace with
        `jax.profiler.ProfileData` alone: on the CPU backend there is no
        device plane, and the program's own spans (`at:`) are listed."""
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.profiling import summarize_xplane_trace
        from alphatriangle_tpu.telemetry import SpanTracer

        tracer = SpanTracer()
        jax.profiler.start_trace(str(tmp_path / "t"))
        with tracer.span("learner.results", k=1):
            jax.jit(lambda x: x @ x)(jnp.ones((64, 64))).block_until_ready()
        jax.profiler.stop_trace()
        traces = list((tmp_path / "t").glob("**/*.xplane.pb"))
        assert traces
        summarize_xplane_trace(traces[0], top=5)
        out = capsys.readouterr().out
        assert "no device plane" in out
        assert "at:learner.results" in out and "total ms" in out

    def test_unreadable_trace_degrades(self, tmp_path, capsys):
        from alphatriangle_tpu.profiling import summarize_xplane_trace

        bad = tmp_path / "x.xplane.pb"
        bad.write_bytes(b"\x01\x02not a proto")
        summarize_xplane_trace(bad, top=5)
        out = capsys.readouterr().out
        assert "unreadable trace" in out
