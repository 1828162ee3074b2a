"""Run telemetry: span tracing, health/watchdog monitoring, anomaly
detection (docs/OBSERVABILITY.md).

Three cooperating pieces, all host-side and off the device dispatch
path, bundled behind the `RunTelemetry` facade the training loop talks
to:

- `tracer.SpanTracer` — thread-aware begin/end spans (rollout chunk,
  sample, learner dispatch/train, weight sync, checkpoint, fold),
  ring-buffered and exported as Chrome/Perfetto `trace.json`.
- `health.HealthMonitor` + `health.Watchdog` — a `health.json`
  heartbeat updated each loop tick, and a stall watchdog that dumps all
  thread stacks and flushes the span buffer when nothing progresses for
  a deadline.
- `anomaly.AnomalyDetector` — streaming EWMA/z-score checks over
  per-step training metrics (loss spikes, grad-norm explosions,
  non-finite values, policy-entropy collapse) escalated to `Anomaly/*`
  metrics and warnings with recent-window context; plus a
  monotonic-growth memory leak detector (`Anomaly/memory_growth`) fed
  per utilization tick.
- `memory` — per-program HBM attribution (AOT `memory_analysis()`
  capture via compile_cache), train-state/replay-ring byte accounting,
  and the static pre-flight budget behind `cli fit`/`cli mem`
  (docs/OBSERVABILITY.md "Memory").
- `roofline` — per-program `cost_analysis()` capture (FLOPs, bytes
  accessed), the arithmetic-intensity roofline model behind
  `cli roofline`, and chip-idle gap forensics over the flight ring
  (docs/OBSERVABILITY.md "Roofline & gap attribution").

Podracer-style stacks (arXiv:2104.06272) treat this visibility as a
prerequisite for scaling an async producer/learner loop; the repo's own
pre-chip "10.3h with zero healthy windows" is the local proof.
"""

import logging
import time
from pathlib import Path

from ..config.telemetry_config import TelemetryConfig
from .anomaly import Anomaly, AnomalyDetector
from .health import (
    HealthMonitor,
    Watchdog,
    dump_thread_stacks,
    health_verdict,
    read_health,
)
from .flight import (
    FLIGHT_FILENAME,
    WEDGE_EXIT_CODE,
    WEDGE_REPORT_FILENAME,
    DispatchWatchdog,
    FlightRecorder,
    classify_run,
    flight_span,
    read_flight,
    summarize_flight,
)
from .ledger import (
    METRICS_FILENAME,
    PROM_FILENAME,
    MetricsLedger,
    read_ledger,
    tick_record,
    write_prometheus_textfile,
)
from .memory import (
    attribution_rows,
    compose_budget,
    estimate_fit,
    fit_verdict,
    program_memory_record,
    replay_ring_bytes,
    replay_ring_record,
    summarize_device_memory,
    train_state_record,
    tree_bytes,
)
from .merge import MERGED_TRACE_FILENAME, merge_fleet_trace
from .perf import UtilizationMeter, summarize_utilization
from .roofline import (
    attribute_gaps,
    cost_flops_by_family,
    peak_hbm_gbps_info,
    program_cost_record,
    roofline_rows,
    summarize_roofline,
)
from .slo import (
    FLEET_PROM_FILENAME,
    SLO_EXIT_CODES,
    evaluate_slos,
    slo_status_line,
    write_fleet_prometheus,
)
from .tracectx import TraceContext, TRACEPARENT_ENV
from .tracer import (
    SpanTracer,
    default_tracer,
    set_default_tracer,
    summarize_trace_file,
)
from . import tracectx

logger = logging.getLogger(__name__)

__all__ = [
    "Anomaly",
    "AnomalyDetector",
    "DispatchWatchdog",
    "FlightRecorder",
    "FLEET_PROM_FILENAME",
    "HealthMonitor",
    "MERGED_TRACE_FILENAME",
    "MetricsLedger",
    "SLO_EXIT_CODES",
    "RunTelemetry",
    "SpanTracer",
    "default_tracer",
    "set_default_tracer",
    "TelemetryConfig",
    "TraceContext",
    "TRACEPARENT_ENV",
    "tracectx",
    "UtilizationMeter",
    "Watchdog",
    "attribute_gaps",
    "attribution_rows",
    "classify_run",
    "cost_flops_by_family",
    "peak_hbm_gbps_info",
    "program_cost_record",
    "roofline_rows",
    "summarize_roofline",
    "flight_span",
    "read_flight",
    "summarize_flight",
    "compose_budget",
    "dump_thread_stacks",
    "estimate_fit",
    "evaluate_slos",
    "fit_verdict",
    "merge_fleet_trace",
    "slo_status_line",
    "write_fleet_prometheus",
    "health_verdict",
    "program_memory_record",
    "read_health",
    "read_ledger",
    "replay_ring_bytes",
    "replay_ring_record",
    "summarize_device_memory",
    "summarize_trace_file",
    "summarize_utilization",
    "train_state_record",
    "tree_bytes",
]

TRACE_FILENAME = "trace.json"
HEALTH_FILENAME = "health.json"
STACKS_FILENAME = "stall_stacks.txt"


class RunTelemetry:
    """One run's telemetry: tracer + heartbeat + watchdog + anomalies.

    Constructed by `setup_training_components`, driven by the training
    loop: `start()` when the loop begins, `on_rollout`/`on_learner_step`
    as work lands (O(1), any thread), `on_tick` once per loop iteration
    (the only place heartbeat IO happens), `close()` in the loop's
    finally block. With `config.ENABLED` false every hook is a cheap
    no-op and no files are written.
    """

    def __init__(
        self,
        config: TelemetryConfig | None = None,
        run_dir: Path | str = ".",
        stats=None,
        run_name: str = "",
        clock=time.monotonic,
        perf: UtilizationMeter | None = None,
    ) -> None:
        self.config = config or TelemetryConfig()
        self.run_dir = Path(run_dir)
        self.stats = stats
        self.run_name = run_name
        enabled = self.config.ENABLED
        # The run's tracer is the process's default: the spans drawn
        # inside Trainer / DeviceReplayBuffer / SelfPlayEngine land in
        # it, as children of the loop phase that is open (a disabled
        # one where telemetry is off, so nothing records anywhere).
        self.tracer = set_default_tracer(
            SpanTracer(capacity=self.config.SPAN_BUFFER_SIZE, enabled=enabled)
        )
        self.health = HealthMonitor(
            self.run_dir / HEALTH_FILENAME,
            deadline_s=self.config.WATCHDOG_DEADLINE_S,
            run_name=run_name,
            clock=clock,
        )
        self.anomaly = AnomalyDetector(
            alpha=self.config.ANOMALY_EWMA_ALPHA,
            z_threshold=self.config.ANOMALY_Z_THRESHOLD,
            warmup=self.config.ANOMALY_WARMUP_STEPS,
            window=self.config.ANOMALY_WINDOW,
            entropy_floor=self.config.ENTROPY_COLLAPSE_THRESHOLD,
            memory_growth_ticks=self.config.MEMORY_GROWTH_TICKS,
            memory_growth_fraction=self.config.MEMORY_GROWTH_MIN_FRACTION,
        )
        # Durable metrics ledger + live utilization accounting (the
        # persistence-and-analysis tier under the span/heartbeat
        # surfaces; docs/OBSERVABILITY.md "Ledger").
        self.perf = perf
        self.ledger: MetricsLedger | None = None
        if enabled and self.config.LEDGER_ENABLED:
            self.ledger = MetricsLedger(
                self.run_dir / METRICS_FILENAME,
                max_bytes=self.config.LEDGER_MAX_BYTES,
                keep=self.config.LEDGER_KEEP_ROTATIONS,
                fsync=self.config.LEDGER_FSYNC,
            )
        if perf is not None:
            self.health.set_device_info(
                perf.device_kind, perf.peak_tflops, perf.peak_source
            )
        self.watchdog: Watchdog | None = None
        if enabled and self.config.WATCHDOG_ENABLED:
            self.watchdog = Watchdog(
                self.health,
                deadline_s=self.config.WATCHDOG_DEADLINE_S,
                poll_s=self.config.WATCHDOG_POLL_S,
                on_stall=self._on_stall,
                clock=clock,
            )
        # Dispatch flight recorder + per-dispatch deadline watchdog
        # (telemetry/flight.py): the black box that survives a dead
        # process. Components pick the recorder up as a `flight`
        # attribute (training/setup.py, serving/service.py).
        self.flight: FlightRecorder | None = None
        self.dispatch_watchdog: DispatchWatchdog | None = None
        if enabled and self.config.FLIGHT_ENABLED:
            if self.config.DISPATCH_WATCHDOG_ENABLED:
                self.dispatch_watchdog = DispatchWatchdog(
                    self.run_dir,
                    poll_s=self.config.DISPATCH_WATCHDOG_POLL_S,
                    on_wedge=self._on_wedge,
                    exit_on_wedge=self.config.DISPATCH_EXIT_ON_WEDGE,
                    clock=clock,
                    warn_fraction=self.config.DISPATCH_WARN_FRACTION,
                    on_warn=self._on_dispatch_warn,
                )
            # A parent (supervisor attempt / fleet spawn) may have
            # handed this process a trace context via the traceparent
            # env seam; adopting it as the ring's base trace links
            # every dispatch here back to the spawning attempt.
            parent_ctx = tracectx.from_env()
            self.flight = FlightRecorder(
                self.run_dir / FLIGHT_FILENAME,
                max_bytes=self.config.FLIGHT_MAX_BYTES,
                keep=self.config.FLIGHT_KEEP_ROTATIONS,
                deadline_factor=self.config.DISPATCH_DEADLINE_FACTOR,
                min_deadline_s=self.config.DISPATCH_MIN_DEADLINE_S,
                first_deadline_s=self.config.DISPATCH_FIRST_DEADLINE_S,
                watchdog=self.dispatch_watchdog,
                base_trace=(
                    parent_ctx.fields() if parent_ctx is not None else None
                ),
            )
        # Device-telemetry plane (telemetry/device_stats.py): point the
        # beacon writer at this run's beacons.jsonl. No file is created
        # until an armed program's callback actually fires.
        if enabled:
            try:
                from .device_stats import attach_beacon_run_dir

                attach_beacon_run_dir(self.run_dir)
            except Exception:
                logger.debug("beacon run-dir attach failed", exc_info=True)
        self._step = 0
        self._memory_seen: set = set()
        self._cost_seen: set = set()
        self._last_write_mono = None
        self._last_written_step: int | None = None
        self._clock = clock
        self._closed = False

    @property
    def enabled(self) -> bool:
        return self.config.ENABLED

    # --- loop lifecycle ----------------------------------------------

    def start(self) -> None:
        if self.watchdog is not None:
            self.watchdog.start()
        if self.dispatch_watchdog is not None:
            self.dispatch_watchdog.start()

    def close(self, step: int | None = None) -> None:
        """Stop the watchdog, write the final heartbeat + trace export."""
        if self._closed:
            return
        self._closed = True
        if self.watchdog is not None:
            self.watchdog.stop()
        if self.dispatch_watchdog is not None:
            self.dispatch_watchdog.stop()
        if self.flight is not None:
            self.flight.close()
        if not self.enabled:
            return
        if step is not None:
            self._step = step
        # Programs that compiled after the last util tick still land in
        # the ledger's attribution record.
        self._ledger_compile_memory()
        self.health.write()
        n = self.tracer.export(self.run_dir / TRACE_FILENAME)
        logger.info(
            "Telemetry: %d span(s) -> %s, heartbeat -> %s",
            n,
            self.run_dir / TRACE_FILENAME,
            self.health.path,
        )

    # --- beats (any thread, O(1) — no IO) ----------------------------

    def on_rollout(self, experiences: int = 0, episodes: int = 0) -> None:
        if self.enabled:
            self.health.note_rollout(experiences, episodes)

    def on_learner_step(self, step: int, metrics: dict) -> list[Anomaly]:
        """Record learner progress and screen this step's metrics.

        `metrics` uses the stats-pipeline names (`Loss/total_loss`,
        `Loss/Grad_Norm`, `Loss/Entropy`, ...). Returns the anomalies
        (already escalated to `Anomaly/*` metrics + warnings).
        """
        self._step = step
        if not self.enabled:
            return []
        self.health.note_learner_step(step)
        if not self.config.ANOMALY_ENABLED:
            return []
        anomalies = []
        for name, value in metrics.items():
            try:
                value = float(value)
            except (TypeError, ValueError):
                continue
            anomalies.extend(self.anomaly.observe(name, value, step))
        for a in anomalies:
            logger.warning("Training anomaly: %s", a.describe())
            if self.stats is not None:
                self.stats.log_scalar(f"Anomaly/{a.kind}", 1.0, step)
        return anomalies

    # --- metrics ledger (durable per-run timeseries) -------------------

    def record_metrics(self, step: int, means: dict) -> None:
        """Ledger one processed metric batch (the StatsCollector's tick
        sink — wired in setup so EVERY flush lands, including the final
        force flush and the collector's own close-time flush)."""
        if self.ledger is not None and means:
            self.ledger.append(tick_record(step, means))

    def record_device_stats(
        self, step: int, program: "str | None" = None, **legs
    ) -> "dict | None":
        """Ledger one ``kind:"device_stats"`` record from the legs the
        host just folded out of the one per-iteration fetch (search /
        rollout / per / learner / serve — telemetry/device_stats.py),
        and screen the search leg for device-side anomalies (value
        explosion, root-entropy collapse, occupancy saturation).
        Returns the record, or None when every leg was empty."""
        if not self.enabled:
            return None
        from .device_stats import device_stats_record

        record = device_stats_record(step, program=program, **legs)
        if record is None:
            return None
        if self.ledger is not None:
            self.ledger.append(record)
        search_leg = record.get("search") or record.get("serve")
        if self.config.ANOMALY_ENABLED and search_leg:
            for a in self.anomaly.observe_search(search_leg, step):
                logger.warning("Training anomaly: %s", a.describe())
                if self.stats is not None:
                    self.stats.log_scalar(f"Anomaly/{a.kind}", 1.0, step)
        return record

    def record_memory(self, record: "dict | None") -> None:
        """Ledger one static memory-attribution record (train-state
        tree bytes, replay-ring bytes, program memory_analysis —
        telemetry/memory.py; `cli mem` renders these)."""
        if self.ledger is not None and record:
            self.ledger.append(record)

    def _ledger_compile_memory(self) -> None:
        """Append program memory records the compile cache has captured
        but this run's ledger hasn't seen yet (programs compile lazily
        on first dispatch, so this runs every util tick and at close;
        the seen-set is per run — several runs in one process each get
        the full attribution)."""
        if self.ledger is None:
            return
        try:
            from ..compile_cache import get_compile_cache

            cache = get_compile_cache()
            for record in cache.memory_summary():
                rid = (record.get("program"), record.get("key"))
                if rid in self._memory_seen:
                    continue
                self._memory_seen.add(rid)
                self.ledger.append(record)
            # Same drain for compiler cost records (`kind:"cost"`,
            # telemetry/roofline.py): `cli roofline` joins these against
            # flight-seal walls without re-touching the compile cache.
            for record in cache.cost_summary():
                rid = (record.get("program"), record.get("key"))
                if rid in self._cost_seen:
                    continue
                self._cost_seen.add(rid)
                self.ledger.append(record)
        except Exception:  # accounting must never hurt the loop
            pass

    def on_util_tick(self, step: int, **counters) -> "dict | None":
        """Derive + persist one utilization record from the loop's
        cumulative counters (see UtilizationMeter.tick for the keys).
        Returns the record (tests, callers wanting the live numbers).
        """
        if not self.enabled or self.perf is None:
            return None
        if "compile_hits" not in counters:
            try:
                # Lazy: keeps this package importable without pulling
                # jax into heartbeat/ledger READER processes.
                from ..compile_cache import get_compile_cache

                cc = get_compile_cache().stats()
                counters["compile_hits"] = cc.get("hits", 0)
                counters["compile_misses"] = cc.get("misses", 0)
            except Exception:  # never let accounting hurt the loop
                pass
        if "device_memory" not in counters:
            try:
                # The writer side runs beside JAX by definition; the
                # lazy import keeps reader processes JAX-free.
                from .health import device_memory_stats

                counters["device_memory"] = device_memory_stats()
            except Exception:
                pass
        self._ledger_compile_memory()
        record = self.perf.tick(step, **counters)
        if record is None:
            return None
        if self.ledger is not None:
            self.ledger.append(record)
        self.health.note_utilization(record)
        in_use = record.get("mem_bytes_in_use")
        if self.config.ANOMALY_ENABLED and isinstance(in_use, (int, float)):
            for a in self.anomaly.observe_memory(in_use, step):
                logger.warning("Training anomaly: %s", a.describe())
                if self.stats is not None:
                    self.stats.log_scalar(f"Anomaly/{a.kind}", 1.0, step)
        if self.config.PROMETHEUS_TEXTFILE:
            write_prometheus_textfile(
                self.run_dir / PROM_FILENAME, record, self.run_name
            )
        return record

    # --- per-iteration tick (the only heartbeat IO site) --------------

    def on_tick(self, step: int, buffer_size: int = 0) -> None:
        if not self.enabled:
            return
        self._step = step
        self.health.note_buffer(buffer_size)
        now = self._clock()
        due = (
            self._last_write_mono is None
            or step != self._last_written_step
            or now - self._last_write_mono
            >= self.config.HEALTH_WRITE_INTERVAL_S
        )
        if due:
            self._last_write_mono = now
            self._last_written_step = step
            self.health.write()

    # --- stall reaction ----------------------------------------------

    def _on_stall(self, age_s: float) -> None:
        """Watchdog hook: make the stall a diagnosable artifact."""
        dump_thread_stacks(self.run_dir / STACKS_FILENAME)
        self.tracer.instant("watchdog_stall", age_s=round(age_s, 1))
        if self.stats is not None:
            # Lands in TensorBoard on the next tick IF the loop ever
            # ticks again; health.json carries the flag regardless.
            self.stats.log_scalar("Health/stall", age_s, self._step)
        if self.config.FLUSH_TRACE_ON_STALL:
            self.tracer.export(self.run_dir / TRACE_FILENAME)
        self.health.write()
        logger.warning(
            "Watchdog: thread stacks -> %s, span trace -> %s",
            self.run_dir / STACKS_FILENAME,
            self.run_dir / TRACE_FILENAME,
        )

    def _on_dispatch_warn(self, info: dict) -> None:
        """Near-deadline hook (DispatchWatchdog.warn_fraction): a
        dispatch is running long — arm progress beacons NOW, so every
        program built from here on (a supervised respawn rebuilds them
        all) phases itself into beacons.jsonl. If this dispatch
        recovers, the arming cost is a cache re-key; if it wedges, the
        respawn's programs carry the forensics the first one lacked."""
        self.tracer.instant(
            "dispatch_warn",
            program=info.get("program"),
            elapsed_s=info.get("elapsed_s"),
        )
        try:
            from .device_stats import arm_beacons, beacons_armed

            if not beacons_armed():
                arm_beacons(self.config.BEACON_EVERY_N_WAVES)
        except Exception:
            logger.exception("beacon arming on dispatch warn failed")

    def _on_wedge(self, info: dict) -> None:
        """Dispatch-watchdog hook (runs BEFORE wedge_report.json lands
        and any exit): the timeline INTO the wedge must be on disk."""
        self.tracer.instant(
            "dispatch_wedge",
            program=info.get("program"),
            elapsed_s=info.get("elapsed_s"),
        )
        if self.config.FLUSH_TRACE_ON_STALL:
            self.tracer.export(self.run_dir / TRACE_FILENAME)
        # No heartbeat write here: `health.write` snapshots device
        # memory, and touching a wedged device could hang the watchdog
        # thread before the wedge report lands.
        self.health.set_stalled(True)
