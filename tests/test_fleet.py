"""Serve-fleet control plane (alphatriangle_tpu/serving/router.py +
fleet.py, docs/SERVING.md "Fleet").

The router tests drive every routing edge case — all-replicas-unhealthy
shedding, retry-exhaustion surfacing the last error, hedge
cancel-on-first-win, capped backoff math — with fake replica handles,
an injectable clock and ZERO subprocesses; the FleetSupervisor tests
script a replica death through a fake popen and assert the death ->
verdict -> respawn -> re-admission chain lands in fleet.jsonl exactly
as `make fleet-smoke` reads it back from real children
(tests/test_supervise.py style). JAX never loads on these paths — the
contract benchmarks/fleet_smoke.py pins with an import guard.
"""

import json
import sys
import time

import pytest

from alphatriangle_tpu.serving.fleet import FLEET_FILENAME, FleetSupervisor
from alphatriangle_tpu.serving.router import (
    REJECT_NO_HEALTHY,
    REJECT_QUEUE_FULL,
    REJECT_RETRIES_EXHAUSTED,
    ReplicaError,
    ReplicaRouter,
)
from alphatriangle_tpu.supervise.faults import (
    FAULT_STATE_DIR_ENV,
    FAULTS_ENV,
    SITE_FAULTS,
    fault_point,
)
from alphatriangle_tpu.supervise.policy import (
    WEDGE_EXIT_CODE,
    RecoveryPolicy,
)
from alphatriangle_tpu.telemetry.health import (
    PROBE_DISPATCH_OVERDUE,
    PROBE_LIVE,
    PROBE_MISSING,
    PROBE_UNHEALTHY,
    probe_run,
)
from alphatriangle_tpu.telemetry.perf import (
    COMPARE_METRICS,
    LOWER_IS_BETTER,
    summarize_fleet,
)

# --- fakes (router handle protocol, no subprocesses) ---------------------


class FakeClock:
    """Monotonic clock advanced only by `sleep` — the router's polling
    loops and backoff waits move time deterministically."""

    def __init__(self, t: float = 0.0):
        self.t = t
        self.sleeps: list[float] = []

    def __call__(self) -> float:
        return self.t

    def sleep(self, s: float) -> None:
        self.sleeps.append(s)
        self.t += s


class FakePending:
    """Pre-resolved (or never-resolving) future."""

    def __init__(self, value=None, error=None, done=True):
        self.value = value
        self.error = error
        self._done = done
        self.cancelled = False

    def done(self) -> bool:
        return self._done

    def wait(self, timeout=None) -> bool:
        return self._done

    def cancel(self) -> None:
        self.cancelled = True
        if not self._done:
            self.error = ReplicaError("cancelled")
            self._done = True


class ClockPending(FakePending):
    """Resolves once the fake clock reaches `ready_at`."""

    def __init__(self, clock: FakeClock, ready_at: float, value=None):
        super().__init__(value=value, done=False)
        self._clock = clock
        self._ready_at = ready_at

    def done(self) -> bool:
        if not self._done and self._clock.t >= self._ready_at:
            self._done = True
        return self._done


class FakeReplica:
    """Router handle protocol: each submit pops the next scripted
    outcome (a pending, or an exception to raise from submit)."""

    def __init__(
        self, name, *, routable=True, queue_depth=0, bucket=8, outcomes=None
    ):
        self.name = name
        self.routable = routable
        self.queue_depth = queue_depth
        self.bucket = bucket
        self.outcomes = list(outcomes or [])
        self.submits: list[dict] = []

    def submit(self, payload: dict):
        self.submits.append(payload)
        outcome = (
            self.outcomes.pop(0)
            if self.outcomes
            else FakePending(value={"ok": True})
        )
        if isinstance(outcome, Exception):
            raise outcome
        return outcome


def make_router(replicas, clock=None, **kw):
    clock = clock or FakeClock()
    defaults = dict(
        timeout_s=10.0,
        retries=2,
        backoff_base_s=0.1,
        backoff_max_s=2.0,
        poll_s=0.01,
        clock=clock,
        sleep=clock.sleep,
    )
    defaults.update(kw)
    return ReplicaRouter(replicas, **defaults), clock


class TestRouter:
    def test_all_replicas_unhealthy_sheds_with_distinct_code(self):
        events = []
        router, _ = make_router(
            [FakeReplica("r0", routable=False), FakeReplica("r1", routable=False)],
            on_event=events.append,
        )
        res = router.route({"kind": "episode"})
        assert not res.ok
        assert res.rejection == REJECT_NO_HEALTHY
        assert router.stats.shed_unhealthy == 1
        assert router.stats.completed == 0
        assert [e["event"] for e in events] == ["shed"]
        assert events[0]["rejection"] == REJECT_NO_HEALTHY

    def test_bounded_admission_sheds_queue_full(self):
        router, _ = make_router([FakeReplica("r0")], max_inflight=0)
        res = router.route({"kind": "episode"})
        assert res.rejection == REJECT_QUEUE_FULL
        assert router.stats.shed_queue_full == 1

    def test_least_queue_depth_wins_and_exclusion_falls_back(self):
        deep = FakeReplica("r0", queue_depth=3)
        shallow = FakeReplica("r1", queue_depth=1)
        router, _ = make_router([deep, shallow])
        res = router.route({"kind": "episode"})
        assert res.ok and res.replica == "r1"
        assert not deep.submits
        # Exclusion prefers the untried replica; with everything tried
        # the pick falls back rather than shedding.
        assert router._pick(exclude=["r1"]) is deep
        assert router._pick(exclude=["r0", "r1"]) is shallow

    def test_retry_lands_on_a_different_replica(self):
        failing = FakeReplica(
            "r0",
            queue_depth=0,
            outcomes=[FakePending(error=ReplicaError("r0 died"))],
        )
        backup = FakeReplica("r1", queue_depth=5)
        router, clock = make_router([failing, backup])
        res = router.route({"kind": "episode"})
        assert res.ok
        assert res.replica == "r1"  # excluded the failed replica
        assert res.attempts == 2
        assert router.stats.retries == 1
        assert router.stats.backoff_sleeps == [0.1]

    def test_retry_exhaustion_surfaces_last_error(self):
        only = FakeReplica(
            "r0",
            outcomes=[
                FakePending(error=ReplicaError(f"boom-{k}"))
                for k in (1, 2, 3)
            ],
        )
        events = []
        router, _ = make_router([only], retries=2, on_event=events.append)
        res = router.route({"kind": "episode"})
        assert not res.ok
        assert res.rejection == REJECT_RETRIES_EXHAUSTED
        assert res.attempts == 3
        assert "boom-3" in str(res.error)  # the LAST error, not the first
        assert router.stats.exhausted == 1
        # Capped exponential backoff between attempts.
        assert router.stats.backoff_sleeps == [0.1, 0.2]
        assert events[-1]["event"] == "exhausted"
        assert "boom-3" in events[-1]["error"]

    def test_backoff_curve_doubles_then_caps(self):
        router, _ = make_router(
            [], backoff_base_s=0.5, backoff_max_s=1.7
        )
        assert [router.backoff_delay(k) for k in (1, 2, 3, 4)] == [
            0.5,
            1.0,
            1.7,
            1.7,
        ]

    def test_hedge_win_cancels_the_straggling_primary(self):
        clock = FakeClock()
        straggler_pending = FakePending(done=False)
        straggler = FakeReplica("r0", outcomes=[straggler_pending])
        fast = FakeReplica(
            "r1",
            queue_depth=9,  # primary pick must still be r0
            outcomes=[FakePending(value={"ok": True, "kind": "episode"})],
        )
        events = []
        router, _ = make_router(
            [straggler, fast],
            clock=clock,
            hedge_after_s=0.05,
            on_event=events.append,
        )
        res = router.route({"kind": "episode"})
        assert res.ok and res.hedged and res.hedge_won
        assert res.replica == "r1"
        assert straggler_pending.cancelled  # cancel-on-first-win
        assert router.stats.hedges == 1
        assert router.stats.hedge_wins == 1
        assert [e["event"] for e in events] == ["hedge", "hedge-win"]

    def test_primary_win_cancels_the_hedge(self):
        clock = FakeClock()
        primary = FakeReplica(
            "r0", outcomes=[ClockPending(clock, 0.2, value={"ok": True})]
        )
        hedge_pending = FakePending(done=False)
        backup = FakeReplica(
            "r1", queue_depth=9, outcomes=[hedge_pending]
        )
        router, _ = make_router(
            [primary, backup], clock=clock, hedge_after_s=0.05
        )
        res = router.route({"kind": "episode"})
        assert res.ok and res.replica == "r0"
        assert res.hedged and not res.hedge_won
        assert hedge_pending.cancelled
        assert router.stats.hedges == 1
        assert router.stats.hedge_wins == 0

    def test_timeout_cancels_and_counts(self):
        clock = FakeClock()
        stuck_pending = FakePending(done=False)
        stuck = FakeReplica("r0", outcomes=[stuck_pending])
        router, _ = make_router(
            [stuck], clock=clock, timeout_s=0.1, retries=0
        )
        res = router.route({"kind": "episode"})
        assert not res.ok
        assert res.rejection == REJECT_RETRIES_EXHAUSTED
        assert isinstance(res.error, TimeoutError)
        assert stuck_pending.cancelled
        assert router.stats.timeouts == 1


# --- the shared liveness probe (cli health --probe / fleet admission) ----


def write_health(run_dir, *, time_s, stalled=False, deadline_s=10.0):
    (run_dir / "health.json").write_text(
        json.dumps(
            {
                "time": time_s,
                "pid": 4242,
                "stalled": stalled,
                "watchdog_deadline_s": deadline_s,
            }
        )
    )


class TestProbeRun:
    NOW = 1_000.0

    def test_missing_heartbeat(self, tmp_path):
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_MISSING
        assert out["verdict"] == "missing"

    def test_live(self, tmp_path):
        write_health(tmp_path, time_s=self.NOW - 1.0)
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_LIVE
        assert out["verdict"] == "live"
        assert out["heartbeat_age_s"] == pytest.approx(1.0)
        assert out["pid"] == 4242

    def test_stale_heartbeat(self, tmp_path):
        write_health(tmp_path, time_s=self.NOW - 100.0, deadline_s=10.0)
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_UNHEALTHY
        assert out["verdict"] == "stale"

    def test_fresh_but_stalled(self, tmp_path):
        write_health(tmp_path, time_s=self.NOW - 1.0, stalled=True)
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_UNHEALTHY
        assert out["verdict"] == "stalled"

    def test_unsealed_intent_past_deadline(self, tmp_path):
        write_health(tmp_path, time_s=self.NOW - 1.0)
        (tmp_path / "flight.jsonl").write_text(
            json.dumps(
                {
                    "kind": "flight",
                    "phase": "intent",
                    "seq": 7,
                    "program": "serve/b8",
                    "family": "serve",
                    "time": self.NOW - 50.0,
                    "deadline_s": 5.0,
                }
            )
            + "\n"
        )
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_DISPATCH_OVERDUE
        assert out["verdict"] == "dispatch-overdue"
        assert out["overdue"][0]["program"] == "serve/b8"
        assert "serve/b8" in out["reason"]

    def test_sealed_intent_is_not_overdue(self, tmp_path):
        write_health(tmp_path, time_s=self.NOW - 1.0)
        intent = {
            "kind": "flight",
            "phase": "intent",
            "seq": 7,
            "program": "serve/b8",
            "family": "serve",
            "time": self.NOW - 50.0,
            "deadline_s": 5.0,
        }
        seal = {
            "kind": "flight",
            "phase": "seal",
            "seq": 7,
            "ok": True,
            "program": "serve/b8",
            "family": "serve",
            "time": self.NOW - 49.0,
        }
        (tmp_path / "flight.jsonl").write_text(
            json.dumps(intent) + "\n" + json.dumps(seal) + "\n"
        )
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_LIVE

    def test_previous_incarnation_wedge_does_not_gate_respawn(
        self, tmp_path
    ):
        # The predecessor died wedged (unsealed intent, its pid); the
        # respawned process heartbeats under a NEW pid. Its probe must
        # come up live — the old confession is doctor evidence for the
        # death, not a permanent eviction of the replacement.
        write_health(tmp_path, time_s=self.NOW - 1.0)  # pid 4242
        (tmp_path / "flight.jsonl").write_text(
            json.dumps(
                {
                    "kind": "flight",
                    "phase": "intent",
                    "seq": 7,
                    "program": "serve/b8",
                    "family": "serve",
                    "time": self.NOW - 50.0,
                    "deadline_s": 5.0,
                    "pid": 1111,
                }
            )
            + "\n"
        )
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_LIVE
        assert out["overdue"] == []
        # Same pid -> still overdue (the CURRENT process is wedged).
        (tmp_path / "flight.jsonl").write_text(
            json.dumps(
                {
                    "kind": "flight",
                    "phase": "intent",
                    "seq": 8,
                    "program": "serve/b8",
                    "family": "serve",
                    "time": self.NOW - 50.0,
                    "deadline_s": 5.0,
                    "pid": 4242,
                }
            )
            + "\n"
        )
        out = probe_run(tmp_path, now=self.NOW)
        assert out["code"] == PROBE_DISPATCH_OVERDUE


# --- serve quarantine arm + serve-dispatch fault site --------------------


def test_serve_wedge_quarantines_onto_smaller_bucket():
    policy = RecoveryPolicy(
        max_restarts=8,
        circuit_breaker_deaths=99,
        backoff_base_s=1.0,
        quarantine_after=1,
        clock=lambda: 1000.0,
    )
    a = policy.decide(
        verdict="dispatch-hung",
        exit_code=WEDGE_EXIT_CODE,
        family="serve",
        progress_step=5,
    )
    assert a.kind == "restart"
    assert a.overrides == {
        "SERVE_SLOTS__scale": 0.5,
        "TELEMETRY__BEACONS": True,
    }


class TestServeDispatchFaultSite:
    def test_site_registered(self):
        assert SITE_FAULTS["serve-dispatch"] == ("hang-serve", "crash-serve")

    def test_crash_serve_fires_once_per_state_dir(self, tmp_path, monkeypatch):
        monkeypatch.setenv(FAULTS_ENV, "crash-serve@after=2")
        monkeypatch.setenv(FAULT_STATE_DIR_ENV, str(tmp_path))
        fault_point("serve-dispatch", 1)  # below threshold: no-op
        with pytest.raises(RuntimeError, match="injected serve-dispatch"):
            fault_point("serve-dispatch", 2)
        fault_point("serve-dispatch", 3)  # sentinel claimed: fires once
        assert (tmp_path / "crash-serve.fired").exists()

    def test_unarmed_site_is_a_cheap_no_op(self, monkeypatch):
        monkeypatch.delenv(FAULTS_ENV, raising=False)
        fault_point("serve-dispatch", 10**6)


# --- FleetSupervisor lifecycle with scripted children --------------------


class FakeProc:
    """Subprocess stand-in: stdout lines are pre-scripted (a list is a
    valid line iterable for the handle's reader thread)."""

    _pids = iter(range(50_000, 60_000))

    def __init__(self, stdout_lines):
        self.stdout = list(stdout_lines)
        self.stdin = self
        self.pid = next(FakeProc._pids)
        self.returncode = None

    # stdin protocol (unused unless the test submits requests)
    def write(self, line):
        pass

    def flush(self):
        pass

    def close(self):
        pass

    def poll(self):
        return self.returncode

    def wait(self, timeout=None):
        return self.returncode


def fleet_popen(calls):
    def popen(argv, **kw):
        calls.append(list(argv))
        name = argv[argv.index("--name") + 1]
        return FakeProc(
            [json.dumps({"kind": "ready", "name": name, "pid": 1}) + "\n"]
        )

    return popen


def write_wedge_evidence(run_dir, family="serve", program="serve/b8"):
    """The artifacts a replica's watchdog 113 leaves behind: a wedge
    report plus a ring where the program sealed once before hanging."""
    now = time.time()
    records = [
        {"kind": "flight", "phase": "intent", "seq": 1, "program": program,
         "family": family, "time": now},
        {"kind": "flight", "phase": "seal", "seq": 1, "ok": True,
         "program": program, "family": family, "wall_s": 1.0, "time": now},
        {"kind": "flight", "phase": "intent", "seq": 2, "program": program,
         "family": family, "time": now},
    ]
    (run_dir / "flight.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in records)
    )
    (run_dir / "wedge_report.json").write_text(
        json.dumps(
            {"kind": "wedge", "time": now, "program": program,
             "family": family, "seq": 2, "elapsed_s": 99.0,
             "deadline_s": 5.0}
        )
    )


def fleet_events(run_dir):
    out = []
    for line in (run_dir / FLEET_FILENAME).read_text().splitlines():
        rec = json.loads(line)
        if rec.get("kind") == "fleet":
            out.append(rec)
    return out


class TestFleetSupervisor:
    def make_fleet(self, tmp_path, calls, clock):
        return FleetSupervisor(
            tmp_path / "fleet",
            replicas=1,
            slots=8,
            sims=2,
            popen=fleet_popen(calls),
            now=clock,
            sleep=lambda s: None,
            probe_deadline_s=10.0,
            policy_factory=lambda: RecoveryPolicy(
                max_restarts=8,
                circuit_breaker_deaths=99,
                backoff_base_s=3.0,
                backoff_max_s=30.0,
                quarantine_after=1,
                clock=clock,
            ),
        )

    def test_death_verdict_respawn_readmission_chain(self, tmp_path):
        clock = FakeClock(t=1_000.0)
        calls: list = []
        fleet = self.make_fleet(tmp_path, calls, clock)
        h = fleet.handles[0]

        # Spawn (driving the internals directly keeps the monitor
        # thread out of the test), then the probe admits the replica.
        fleet._spawn(h, "spawn")
        assert h.ready.wait(2.0)
        assert calls[0][calls[0].index("--slots") + 1] == "8"
        write_health(h.run_dir, time_s=clock.t - 0.5)
        fleet._probe(h)
        assert h.routable
        assert fleet.readmissions == 1

        # The replica wedges in serve dispatch and dies by watchdog 113.
        write_wedge_evidence(h.run_dir, family="serve", program="serve/b8")
        h.served_moves = 24  # progress since spawn: streak stays 1
        h.proc.returncode = 113
        fleet.poll_once()
        assert fleet.deaths == 1
        assert not h.routable
        death = [e for e in fleet_events(fleet.run_dir) if e["event"] == "death"][0]
        assert death["rc"] == 113
        assert death["verdict"] == "dispatch-hung"
        assert death["family"] == "serve"
        assert death["program"] == "serve/b8"
        assert death["action"] == "restart"
        assert death["overrides"] == {
            "SERVE_SLOTS__scale": 0.5,
            "TELEMETRY__BEACONS": True,
        }
        assert death["progress_moves"] == 24

        # Before the backoff expires: no respawn yet.
        clock.t += 1.0
        fleet.poll_once()
        assert fleet.respawns == 0

        # Past the backoff: respawn onto the DEGRADED (halved) bucket.
        clock.t += 3.0
        fleet.poll_once()
        assert fleet.respawns == 1
        assert h.ready.wait(2.0)
        assert calls[1][calls[1].index("--slots") + 1] == "4"
        assert h.bucket == 4

        # Fresh heartbeat from the new incarnation -> re-admission.
        write_health(h.run_dir, time_s=clock.t - 0.5)
        fleet.poll_once()
        assert h.routable
        assert fleet.readmissions == 2

        chain = [e["event"] for e in fleet_events(fleet.run_dir)]
        # replica-ready is ledgered by the reader THREAD the moment the
        # child prints its ready line, so its position among the
        # main-thread lifecycle events is timing-dependent: assert one
        # per incarnation, then pin the lifecycle order without them.
        assert chain.count("replica-ready") == 2
        lifecycle = [e for e in chain if e != "replica-ready"]
        assert lifecycle == ["spawn", "readmit", "death", "respawn", "readmit"]
        assert fleet.summary()["buckets"] == {"r0": 4}

    def test_stale_heartbeat_evicts_until_it_recovers(self, tmp_path):
        clock = FakeClock(t=1_000.0)
        calls: list = []
        fleet = self.make_fleet(tmp_path, calls, clock)
        h = fleet.handles[0]
        fleet._spawn(h, "spawn")
        assert h.ready.wait(2.0)
        write_health(h.run_dir, time_s=clock.t - 0.5)
        fleet._probe(h)
        assert h.routable

        clock.t += 100.0  # heartbeat goes stale: evict from admission
        fleet.poll_once()
        assert not h.routable
        assert fleet.evictions == 1
        evict = [e for e in fleet_events(fleet.run_dir) if e["event"] == "evict"][0]
        assert evict["code"] == PROBE_UNHEALTHY

        write_health(h.run_dir, time_s=clock.t - 0.5)  # recovered
        fleet.poll_once()
        assert h.routable
        assert fleet.readmissions == 2

    def test_quarantine_and_ladder_cannot_drift(self, tmp_path):
        """Drift regression (fleet.py `_effective_slots`): quarantine's
        `SERVE_SLOTS__scale` multiplier and the micro-batcher both
        derive their rungs from serving/buckets.py, so one quarantine
        strike must land EXACTLY one `walk_down` on the shared ladder —
        a shape `cli warm`/PolicyService precompiled — for any base
        slot count and any explicit `--buckets` spec."""
        from alphatriangle_tpu.serving.buckets import (
            BucketLadder,
            default_rungs,
        )

        clock = FakeClock(t=1_000.0)
        for slots in (1, 3, 5, 8, 16, 64):
            fleet = FleetSupervisor(
                tmp_path / f"fleet_b{slots}",
                replicas=1,
                slots=slots,
                popen=fleet_popen([]),
                now=clock,
                sleep=lambda s: None,
            )
            name = fleet.handles[0].name
            # The implicit ladder under a bare --slots knob is the
            # halving ladder — the legacy 0.5-multiplier bucket set.
            assert fleet.ladder.rungs == default_rungs(slots)
            # Healthy replica: the base rung itself.
            assert fleet._effective_slots(name) == slots
            # One strike (scale 0.5) == one forced walk-down, exactly.
            fleet._overrides[name] = {"SERVE_SLOTS__scale": 0.5}
            assert fleet._effective_slots(name) == fleet.ladder.walk_down(
                slots
            )
            # Two strikes (0.25) keep agreeing, and the degraded bucket
            # is always a rung the ladder owns (a warmable shape).
            fleet._overrides[name] = {"SERVE_SLOTS__scale": 0.25}
            two = fleet._effective_slots(name)
            assert two == fleet.ladder.walk_down(slots, strikes=2)
            assert two in fleet.ladder
            # summary() advertises the shared rung set (cli watch's
            # fleet line reads it).
            assert fleet.summary()["rungs"] == list(fleet.ladder.rungs)

        # An explicit --buckets spec flows into quarantine too: the
        # strike snaps DOWN onto the CUSTOM rungs, not powers of two.
        fleet = FleetSupervisor(
            tmp_path / "fleet_custom",
            replicas=1,
            slots=48,
            ladder="12,48,96",
            popen=fleet_popen([]),
            now=clock,
            sleep=lambda s: None,
        )
        name = fleet.handles[0].name
        assert fleet.ladder == BucketLadder((12, 48, 96))
        fleet._overrides[name] = {"SERVE_SLOTS__scale": 0.5}
        # 48 * 0.5 = 24 is NOT a rung: rung_at_or_below snaps to 12 —
        # the same answer as one walk_down from the base rung.
        assert fleet._effective_slots(name) == 12
        assert fleet._effective_slots(name) == fleet.ladder.walk_down(48)


# --- perf fold (cli perf / cli compare fleet rows) -----------------------


def test_summarize_fleet_folds_lifecycle_and_storm():
    events = [
        {"kind": "fleet", "event": "fleet-start", "replicas": 2},
        {"kind": "fleet", "event": "death", "replica": "r0"},
        {"kind": "fleet", "event": "respawn", "replica": "r0"},
        {"kind": "fleet", "event": "readmit", "replica": "r0"},
        {"kind": "fleet", "event": "retry", "replica": "r1"},
        {"kind": "fleet", "event": "shed", "rejection": "queue-full"},
        {"kind": "fleet", "event": "replica-reloaded", "recompiles": 0},
        {"kind": "fleet", "event": "replica-reloaded", "recompiles": 0},
        {"kind": "util", "moves_per_sec": 10.0},  # ignored: not fleet
        {
            "kind": "fleet",
            "event": "storm-summary",
            "requests": 32,
            "completed": 30,
            "shed": 2,
            "lost": 0,
            "requests_per_sec": 4.5,
            "move_latency_ms_p50": 12.0,
            "move_latency_ms_p95": 80.0,
        },
        {"kind": "fleet", "event": "fleet-stop", "gaveup": []},
    ]
    out = summarize_fleet(events)
    assert out["fleet_deaths"] == 1
    assert out["fleet_respawns"] == 1
    assert out["fleet_readmissions"] == 1
    assert out["fleet_retries"] == 1
    assert out["fleet_sheds"] == 1
    assert out["fleet_reload_recompiles"] == 0
    assert out["fleet_requests"] == 32
    assert out["fleet_lost"] == 0
    assert out["fleet_move_latency_ms_p95"] == 80.0
    assert out["fleet_requests_per_sec"] == 4.5
    assert out["fleet_gaveup"] == []
    assert summarize_fleet([{"kind": "util"}]) is None
    # The compare rows exist and latency gates in the right direction.
    assert "fleet_move_latency_ms_p95" in COMPARE_METRICS
    assert "fleet_requests_per_sec" in COMPARE_METRICS
    assert "fleet_move_latency_ms_p95" in LOWER_IS_BETTER


def test_router_events_keep_the_fleet_ledger_kind(tmp_path):
    """Router shed payloads carry the REQUEST's kind ("episode"); the
    sink must rename it so the record keeps kind="fleet" and stays
    visible to summarize_fleet (regression: sheds vanished from perf)."""
    fleet = FleetSupervisor(tmp_path / "fleet", replicas=0)
    fleet.router_event(
        {"event": "shed", "kind": "episode", "rejection": "queue-full"}
    )
    events = fleet_events(tmp_path / "fleet")
    assert events[-1]["event"] == "shed"
    assert events[-1]["kind"] == "fleet"
    assert events[-1]["request_kind"] == "episode"
    assert summarize_fleet(events)["fleet_sheds"] == 1


def test_fleet_control_plane_is_jax_free():
    """router/fleet must be importable without JAX (the smoke pins this
    in a blocked subprocess; here we pin the imported module set)."""
    for name in (
        "alphatriangle_tpu.serving.router",
        "alphatriangle_tpu.serving.fleet",
        "alphatriangle_tpu.serving",
    ):
        mod = sys.modules.get(name)
        assert mod is not None, f"{name} should be imported by this test"
        assert not getattr(mod, "jax", None), name


class TestOneChipPerReplica:
    """A chip belongs to one process: on an accelerator host the
    JAX-free parent assigns chips through each child's environment."""

    def spawn_all(self, tmp_path, replicas, chips):
        envs: dict = {}

        def popen(argv, env=None, **kw):
            name = argv[argv.index("--name") + 1]
            envs.setdefault(name, []).append(env)
            return FakeProc(
                [json.dumps({"kind": "ready", "name": name, "pid": 1}) + "\n"]
            )

        fleet = FleetSupervisor(
            tmp_path / "fleet", replicas=replicas, chips=chips, popen=popen
        )
        for h in fleet.handles:
            fleet._spawn(h, "spawn")
        return fleet, envs

    def test_each_replica_child_gets_its_own_chip(self, tmp_path):
        fleet, envs = self.spawn_all(tmp_path, replicas=4, chips=4)
        assert [envs[f"r{i}"][0]["TPU_VISIBLE_CHIPS"] for i in range(4)] == [
            "0", "1", "2", "3",
        ]
        for env in (e[0] for e in envs.values()):
            assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
            assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
        # A respawn lands on the chip the dead incarnation held.
        fleet._spawn(fleet.handles[2], "respawn")
        assert envs["r2"][1]["TPU_VISIBLE_CHIPS"] == "2"

    def test_more_replicas_than_chips_is_refused(self, tmp_path):
        with pytest.raises(ValueError, match="2 replicas need one chip each"):
            FleetSupervisor(
                tmp_path / "fleet", replicas=2, chips=1, popen=fleet_popen([])
            )
        assert not (tmp_path / "fleet").exists()  # refused before any spawn

    def test_cpu_run_assigns_nothing(self, tmp_path, monkeypatch):
        monkeypatch.delenv("TPU_VISIBLE_CHIPS", raising=False)
        _, envs = self.spawn_all(tmp_path, replicas=3, chips=None)
        for env in (e[0] for e in envs.values()):
            assert "TPU_VISIBLE_CHIPS" not in env

    @pytest.mark.parametrize(
        "platforms,stdout,want",
        [
            ("cpu", None, None),  # asked for by name: no child at all
            ("", "tpu 4\n", 4),
            ("", "warning noise\ntpu 1\n", 1),
            ("", "cpu 1\n", None),  # unpinned, and JAX found no chip
        ],
    )
    def test_chip_count_comes_from_a_child_that_exits(
        self, monkeypatch, platforms, stdout, want
    ):
        import types

        from alphatriangle_tpu.serving.fleet import local_chip_count

        monkeypatch.setenv("JAX_PLATFORMS", platforms)
        ran = []

        def run(argv, **kw):
            ran.append(argv)
            return types.SimpleNamespace(stdout=stdout)

        assert local_chip_count(run=run) == want
        assert len(ran) == (0 if stdout is None else 1)
