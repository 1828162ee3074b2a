"""Self-healing supervisor (alphatriangle_tpu/supervise/, docs/ROBUSTNESS.md).

The policy tests drive the whole verdict->action matrix with a fake
clock and zero subprocesses; the Supervisor tests script child deaths
through an injectable popen/sleep pair and assert the death->verdict->
restart chain lands in supervisor.jsonl exactly as `make chaos-smoke`
sees it from real children. JAX is never needed on these paths (the
jax-free contract itself is pinned by benchmarks/chaos_smoke.py's
import guard, and re-checked here via sys.modules).
"""

import json
import signal
import sys
import time

import pytest

from alphatriangle_tpu.supervise import (
    OVERRIDES_ENV,
    RecoveryPolicy,
    Supervisor,
    latest_committed_step,
)
from alphatriangle_tpu.supervise.faults import parse_spec
from alphatriangle_tpu.supervise.policy import (
    PREEMPT_EXIT_CODE,
    SUPERVISOR_GIVEUP_EXIT_CODE,
    WEDGE_EXIT_CODE,
)


def make_policy(**kw):
    defaults = dict(
        max_restarts=8,
        circuit_breaker_deaths=3,
        backoff_base_s=5.0,
        backoff_max_s=300.0,
        quarantine_after=2,
        clock=lambda: 1000.0,
    )
    defaults.update(kw)
    return RecoveryPolicy(**defaults)


class TestRecoveryPolicy:
    def test_backoff_doubles_without_progress_and_caps(self):
        policy = make_policy(backoff_base_s=5.0, backoff_max_s=18.0,
                             circuit_breaker_deaths=10)
        delays = [
            policy.decide(verdict="clean", exit_code=1).delay_s
            for _ in range(4)
        ]
        assert delays == [5.0, 10.0, 18.0, 18.0]

    def test_checkpoint_progress_resets_the_streak(self):
        policy = make_policy()
        a1 = policy.decide(verdict="clean", exit_code=1, progress_step=2)
        a2 = policy.decide(verdict="clean", exit_code=1, progress_step=4)
        a3 = policy.decide(verdict="clean", exit_code=1, progress_step=6)
        assert [a.delay_s for a in (a1, a2, a3)] == [5.0, 5.0, 5.0]
        assert all(a.kind == "restart" for a in (a1, a2, a3))

    def test_preemption_resets_the_streak(self):
        policy = make_policy()
        policy.decide(verdict="clean", exit_code=1)
        policy.decide(verdict="clean", exit_code=1)
        a = policy.decide(verdict="preempted", exit_code=PREEMPT_EXIT_CODE)
        assert a.kind == "restart"
        assert a.delay_s == 5.0  # streak back to 1

    def test_circuit_breaker_on_no_progress(self):
        policy = make_policy(circuit_breaker_deaths=2)
        assert policy.decide(verdict="clean", exit_code=1).kind == "restart"
        assert policy.decide(verdict="clean", exit_code=1).kind == "restart"
        a = policy.decide(verdict="clean", exit_code=1)
        assert a.kind == "give-up"
        assert "circuit breaker" in a.reason

    def test_restart_budget_exhaustion(self):
        policy = make_policy(max_restarts=2, circuit_breaker_deaths=99)
        step = iter(range(2, 100, 2))
        for _ in range(2):
            a = policy.decide(
                verdict="clean", exit_code=1, progress_step=next(step)
            )
            assert a.kind == "restart"
        a = policy.decide(verdict="clean", exit_code=1, progress_step=next(step))
        assert a.kind == "give-up"
        assert "budget" in a.reason

    def test_second_wedge_on_family_quarantines(self):
        policy = make_policy(quarantine_after=2, circuit_breaker_deaths=99)
        a1 = policy.decide(
            verdict="dispatch-hung",
            exit_code=WEDGE_EXIT_CODE,
            family="megastep",
            progress_step=2,
        )
        # The first wedge arms progress beacons so a repeat names its
        # phase; quarantine waits for the second.
        assert a1.overrides == {"TELEMETRY__BEACONS": True}
        assert "beacons" in a1.reason
        a2 = policy.decide(
            verdict="dispatch-hung",
            exit_code=WEDGE_EXIT_CODE,
            family="megastep",
            progress_step=4,
        )
        assert a2.overrides == {
            "FUSED_MEGASTEP": False,
            "TELEMETRY__BEACONS": True,
        }
        assert "quarantined" in a2.reason
        # A later unrelated death keeps the quarantine (overrides
        # accumulate; a sick megastep stays off).
        a3 = policy.decide(verdict="clean", exit_code=1, progress_step=6)
        assert a3.overrides == {
            "FUSED_MEGASTEP": False,
            "TELEMETRY__BEACONS": True,
        }

    def test_wedge_by_exit_code_alone_counts(self):
        # Evidence can be thin (e.g. verdict unreadable): the watchdog's
        # 113 still counts toward quarantine.
        policy = make_policy(quarantine_after=1, circuit_breaker_deaths=99)
        a = policy.decide(
            verdict="clean", exit_code=WEDGE_EXIT_CODE, family="rollout",
            progress_step=2,
        )
        assert a.overrides == {
            "ASYNC_ROLLOUTS": False,
            "TELEMETRY__BEACONS": True,
        }

    def test_oom_ladder_halves_then_forces_k1(self):
        policy = make_policy(circuit_breaker_deaths=99)
        a1 = policy.decide(verdict="oom", exit_code=1, progress_step=2)
        assert a1.overrides == {"SELF_PLAY_BATCH_SIZE__scale": 0.5}
        a2 = policy.decide(verdict="oom", exit_code=1, progress_step=4)
        assert a2.overrides == {
            "SELF_PLAY_BATCH_SIZE__scale": 0.25,
            "FUSED_LEARNER_STEPS": 1,
        }


class TestParseSpec:
    def test_good_spec(self):
        assert parse_spec("hang-dispatch@after=6,sigterm@step=3") == {
            "hang-dispatch": 6,
            "sigterm": 3,
        }

    def test_malformed_entries_skipped_not_raised(self):
        assert parse_spec("nonsense, sigkill@step=x, crash@step=7,") == {
            "crash": 7
        }
        assert parse_spec("") == {}


class TestLatestCommittedStep:
    def test_markers_win(self, tmp_path):
        ckpts = tmp_path / "checkpoints"
        for step in (2, 4, 6):
            (ckpts / f"step_{step:08d}").mkdir(parents=True)
            (ckpts / f"step_{step:08d}.meta.json").write_text(
                json.dumps({"global_step": step})
            )
        # Only 2 and 4 committed: 6 is a torn save.
        for step in (2, 4):
            (ckpts / f"step_{step:08d}.commit").write_text(
                json.dumps({"global_step": step})
            )
        assert latest_committed_step(tmp_path) == 4

    def test_legacy_run_without_markers_falls_back_to_meta(self, tmp_path):
        ckpts = tmp_path / "checkpoints"
        (ckpts / "step_00000003").mkdir(parents=True)
        (ckpts / "step_00000003.meta.json").write_text("{\"global_step\": 3}")
        (ckpts / "step_00000005").mkdir()
        (ckpts / "step_00000005.meta.json").write_text("{torn")
        assert latest_committed_step(tmp_path) == 3

    def test_empty(self, tmp_path):
        assert latest_committed_step(tmp_path) is None


class FakeChild:
    def __init__(self, rc, on_wait=None):
        self.rc = rc
        self._on_wait = on_wait

    def wait(self):
        if self._on_wait is not None:
            self._on_wait()
        return self.rc

    def poll(self):
        return self.rc

    def send_signal(self, signum):
        pass


def scripted_popen(script):
    """`script` is a list of (rc, on_wait) per spawn; returns (popen,
    calls) where calls records each spawn's argv + env."""
    calls = []

    def popen(argv, env=None):
        rc, on_wait = script[len(calls)]
        calls.append({"argv": list(argv), "env": dict(env or {})})
        return FakeChild(rc, on_wait)

    return popen, calls


def events_of(run_dir):
    path = run_dir / "supervisor.jsonl"
    out = []
    for line in path.read_text().splitlines():
        rec = json.loads(line)
        if rec.get("kind") == "supervisor":
            out.append(rec)
    return out


def write_wedge_evidence(run_dir, family="megastep", program="megastep/t4"):
    """The artifacts a real watchdog 113 leaves: a wedge report plus a
    ring where the program sealed once before hanging (so classify_run
    says dispatch-hung, not compile-hung)."""
    now = time.time()
    (run_dir / "flight.jsonl").write_text(
        json.dumps(
            {"kind": "flight", "phase": "intent", "seq": 1,
             "program": program, "family": family, "time": now}
        )
        + "\n"
        + json.dumps(
            {"kind": "flight", "phase": "seal", "seq": 1, "ok": True,
             "program": program, "family": family, "wall_s": 1.0,
             "time": now}
        )
        + "\n"
        + json.dumps(
            {"kind": "flight", "phase": "intent", "seq": 2,
             "program": program, "family": family, "time": now}
        )
        + "\n"
    )
    (run_dir / "wedge_report.json").write_text(
        json.dumps(
            {"kind": "wedge", "time": now, "program": program,
             "family": family, "seq": 2, "elapsed_s": 99.0,
             "deadline_s": 5.0}
        )
    )


class TestSupervisor:
    def test_wedge_death_restart_chain(self, tmp_path):
        run_dir = tmp_path / "run"
        run_dir.mkdir()
        sleeps = []
        popen, calls = scripted_popen(
            [
                (113, lambda: write_wedge_evidence(run_dir)),
                (0, None),
            ]
        )
        policy = make_policy(backoff_base_s=7.0, quarantine_after=1,
                             clock=time.monotonic)
        sup = Supervisor(
            ["train-child"],
            run_dir,
            policy,
            popen=popen,
            sleep=sleeps.append,
        )
        assert sup.run() == 0

        assert len(calls) == 2
        # The quarantine override (and the wedge's beacon directive)
        # reaches the second child via env.
        overrides = json.loads(calls[1]["env"][OVERRIDES_ENV])
        assert overrides == {
            "FUSED_MEGASTEP": False,
            "TELEMETRY__BEACONS": True,
        }
        assert OVERRIDES_ENV not in calls[0]["env"]
        assert sleeps == [7.0]

        chain = [(e["event"], e.get("verdict")) for e in events_of(run_dir)]
        assert chain == [
            ("spawn", None),
            ("death", "dispatch-hung"),
            ("spawn", None),
            ("complete", None),
        ]
        death = [e for e in events_of(run_dir) if e["event"] == "death"][0]
        assert death["rc"] == 113
        assert death["program"] == "megastep/t4"
        assert death["action"] == "restart"
        assert death["delay_s"] == 7.0
        # The dead attempt's report is archived, not left to pollute the
        # next death's diagnosis.
        assert not (run_dir / "wedge_report.json").exists()
        assert (run_dir / "wedge_report.json.attempt1").exists()

    def test_progress_step_read_from_commit_markers(self, tmp_path):
        run_dir = tmp_path / "run"
        ckpts = run_dir / "checkpoints"
        ckpts.mkdir(parents=True)
        (ckpts / "step_00000004").mkdir()
        (ckpts / "step_00000004.commit").write_text("{\"global_step\": 4}")
        popen, _ = scripted_popen([(1, None), (0, None)])
        sup = Supervisor(
            ["c"], run_dir, make_policy(clock=time.monotonic),
            popen=popen, sleep=lambda s: None,
        )
        assert sup.run() == 0
        death = [e for e in events_of(run_dir) if e["event"] == "death"][0]
        assert death["progress_step"] == 4
        # Empty flight ring + nonzero exit -> never-started.
        assert death["verdict"] == "never-started"

    def test_give_up_returns_115(self, tmp_path):
        run_dir = tmp_path / "run"
        run_dir.mkdir()
        popen, calls = scripted_popen([(1, None), (1, None)])
        policy = make_policy(circuit_breaker_deaths=1, clock=time.monotonic)
        sup = Supervisor(
            ["c"], run_dir, policy, popen=popen, sleep=lambda s: None
        )
        assert sup.run() == SUPERVISOR_GIVEUP_EXIT_CODE
        assert len(calls) == 2
        events = [e["event"] for e in events_of(run_dir)]
        assert events[-1] == "give-up"

    def test_forwarded_signal_ends_the_loop(self, tmp_path):
        run_dir = tmp_path / "run"
        run_dir.mkdir()
        sup_holder = {}

        def on_wait():
            sup_holder["sup"]._forward_signal(signal.SIGTERM, None)

        popen, calls = scripted_popen([(PREEMPT_EXIT_CODE, on_wait)])
        sup = Supervisor(
            ["c"], run_dir, make_policy(clock=time.monotonic),
            popen=popen, sleep=lambda s: None,
        )
        sup_holder["sup"] = sup
        assert sup.run() == PREEMPT_EXIT_CODE
        assert len(calls) == 1  # no restart after a forwarded SIGTERM
        events = [e["event"] for e in events_of(run_dir)]
        assert "forward-signal" in events
        assert events[-1] == "terminated"

    def test_supervise_module_is_jax_free(self):
        """The package import graph must not pull jax (the chaos smoke
        pins this in a blocked subprocess; here we pin the already-
        imported module set for fast feedback)."""
        mods = [
            m
            for m, mod in sys.modules.items()
            if m.startswith("alphatriangle_tpu.supervise")
            and mod is not None
        ]
        assert mods, "supervise modules should be imported by this test"
        for name in mods:
            mod = sys.modules[name]
            assert not getattr(mod, "jax", None), name


@pytest.mark.parametrize(
    "codes",
    [
        {"WEDGE_EXIT_CODE": 113, "PREEMPT_EXIT_CODE": 114,
         "SUPERVISOR_GIVEUP_EXIT_CODE": 115},
    ],
)
def test_exit_code_registry(codes):
    """The exit codes supervisors branch on are a public contract."""
    assert WEDGE_EXIT_CODE == codes["WEDGE_EXIT_CODE"]
    assert PREEMPT_EXIT_CODE == codes["PREEMPT_EXIT_CODE"]
    assert SUPERVISOR_GIVEUP_EXIT_CODE == codes["SUPERVISOR_GIVEUP_EXIT_CODE"]
