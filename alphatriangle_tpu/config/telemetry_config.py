"""Run-telemetry configuration (span tracing, health/watchdog, anomaly
detection — see `alphatriangle_tpu/telemetry/` and docs/OBSERVABILITY.md).

Telemetry is on by default: every knob here bounds host-side memory or
IO cadence, and nothing in the package touches the device dispatch path
(span/beat ingestion is an O(1) append or field write under a lock; IO
happens on loop ticks and watchdog polls only).
"""

from pydantic import BaseModel, Field


class TelemetryConfig(BaseModel):
    """Knobs for the telemetry subsystem."""

    ENABLED: bool = Field(default=True)

    # --- span tracer ---
    # Ring capacity for in-memory spans; the newest SPAN_BUFFER_SIZE
    # spans are exported to runs/<run>/trace.json at exit and on stall.
    SPAN_BUFFER_SIZE: int = Field(default=65536, ge=1)

    # --- health heartbeat + watchdog ---
    # health.json is rewritten when the learner step advances, and at
    # least this often while the loop ticks (so a stalled-but-alive run
    # keeps a fresh heartbeat carrying its stall flag).
    HEALTH_WRITE_INTERVAL_S: float = Field(default=5.0, gt=0)
    WATCHDOG_ENABLED: bool = Field(default=True)
    # No learner step AND no rollout harvest for this long => stall.
    # Generous default: a flagship compile is ~70s and a rollout chunk
    # is multi-second; 300s of neither is a wedged run, not a slow one.
    WATCHDOG_DEADLINE_S: float = Field(default=300.0, gt=0)
    WATCHDOG_POLL_S: float = Field(default=10.0, gt=0)
    # On stall, also export the span ring to trace.json so the timeline
    # leading INTO the stall is on disk before anyone kills the process.
    FLUSH_TRACE_ON_STALL: bool = Field(default=True)

    # --- metrics ledger (telemetry/ledger.py) ---
    # Durable per-run timeseries: every processed metric batch and one
    # derived utilization record per tick appended crash-safely to
    # runs/<run>/metrics.jsonl (`cli perf` / `cli compare` read it).
    LEDGER_ENABLED: bool = Field(default=True)
    # Rotation: metrics.jsonl -> .1 -> .2 when a file crosses this size
    # (0 disables rotation; the file then grows unbounded).
    LEDGER_MAX_BYTES: int = Field(default=16 * 1024 * 1024, ge=0)
    LEDGER_KEEP_ROTATIONS: int = Field(default=2, ge=0)
    # fsync every append: maximally crash-durable, but a per-tick disk
    # sync is unnecessary for observability — flush-on-close already
    # survives process death; only a kernel crash loses the tail.
    LEDGER_FSYNC: bool = Field(default=False)
    # Opt-in Prometheus textfile exporter: the newest utilization
    # record rendered as gauges into runs/<run>/metrics.prom (point a
    # node_exporter textfile collector or any scraper at it).
    PROMETHEUS_TEXTFILE: bool = Field(default=False)

    # --- dispatch flight recorder (telemetry/flight.py) ---
    # Intent-before / seal-after records for every hot-family device
    # dispatch, appended crash-safely to runs/<run>/flight.jsonl so a
    # SIGKILLed or wedged run still names the program it died inside
    # (`cli doctor`). Two tiny appends per dispatch; perf-smoke pins
    # the overhead under ~1% of iteration time.
    FLIGHT_ENABLED: bool = Field(default=True)
    FLIGHT_MAX_BYTES: int = Field(default=8 * 1024 * 1024, ge=0)
    FLIGHT_KEEP_ROTATIONS: int = Field(default=1, ge=0)
    # Per-dispatch deadline watchdog: a dispatch in flight past
    # FACTOR x its expected duration (EWMA of this run's own sealed
    # walls; MIN floors noisy fast programs) dumps stacks + trace,
    # writes wedge_report.json, and exits WEDGE_EXIT_CODE (113) so the
    # supervisor reclassifies the window in minutes. A program's FIRST
    # dispatch includes its compile, hence the generous allowance.
    DISPATCH_WATCHDOG_ENABLED: bool = Field(default=True)
    DISPATCH_DEADLINE_FACTOR: float = Field(default=10.0, gt=1.0)
    DISPATCH_MIN_DEADLINE_S: float = Field(default=60.0, gt=0)
    DISPATCH_FIRST_DEADLINE_S: float = Field(default=900.0, gt=0)
    DISPATCH_WATCHDOG_POLL_S: float = Field(default=5.0, gt=0)
    # Exit-on-wedge is what turns a 10h silent window into a minutes-
    # scale reclassification; tests and doctor-smoke disable it to
    # observe the report without dying.
    DISPATCH_EXIT_ON_WEDGE: bool = Field(default=True)

    # --- device telemetry plane (telemetry/device_stats.py) ---
    # Fixed-shape in-program stat-packs (KataGo-style search health:
    # root-visit entropy/concentration, value bounds, tree occupancy;
    # PER skew; per-fused-step grad/update norms) computed inside the
    # hot programs and returned through the EXISTING single
    # per-iteration fetch — no extra dispatch, no host sync. Ledgered
    # as kind:"device_stats" records (`cli perf`, `cli watch`) and fed
    # to AnomalyDetector.observe_search.
    DEVICE_STATS: bool = Field(default=True)
    # Progress beacons (`jax.debug.callback` phase markers appended to
    # runs/<run>/beacons.jsonl) are OFF on hot paths by default; they
    # arm via ALPHATRIANGLE_BEACONS=1, the dispatch watchdog's
    # near-deadline warning, or a supervised dispatch-hung respawn.
    # When armed, search-wave beacons subsample to every Nth wave.
    BEACON_EVERY_N_WAVES: int = Field(default=8, ge=1)
    # Fraction of the dispatch deadline after which the watchdog warns
    # and arms beacons for programs built from then on (the wedge's
    # SECOND occurrence then names its phase).
    DISPATCH_WARN_FRACTION: float = Field(default=0.5, gt=0, lt=1.0)

    # --- anomaly detection ---
    ANOMALY_ENABLED: bool = Field(default=True)
    ANOMALY_EWMA_ALPHA: float = Field(default=0.02, gt=0, le=1.0)
    ANOMALY_Z_THRESHOLD: float = Field(default=6.0, gt=0)
    ANOMALY_WARMUP_STEPS: int = Field(default=20, ge=1)
    ANOMALY_WINDOW: int = Field(default=32, ge=1)
    # Policy entropy at/below this after warmup counts as a collapse.
    ENTROPY_COLLAPSE_THRESHOLD: float = Field(default=0.01, ge=0)

    # --- memory observability (telemetry/memory.py) ---
    # Leak detector (`Anomaly/memory_growth`): device bytes_in_use
    # rising MONOTONICALLY for this many utilization ticks, with total
    # growth over the run of at least this fraction, fires once per
    # excursion (a healthy allocator sawtooths; a leak only climbs).
    MEMORY_GROWTH_TICKS: int = Field(default=12, ge=2)
    MEMORY_GROWTH_MIN_FRACTION: float = Field(default=0.05, ge=0)


TelemetryConfig.model_rebuild(force=True)
