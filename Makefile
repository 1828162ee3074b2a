# Quality gate (reference: .github/workflows/ci_cd.yml:18-100 runs
# ruff + mypy + pytest + coverage fail_under=40).
#
# `make check` is the one command that fails the build on a lint, type,
# syntax or test regression. Tools missing from the current image
# (ruff/mypy/pytest-cov are not baked into the TPU image and installs
# are disallowed there) degrade to the strongest available check and
# SAY SO; the test suite itself is mandatory and never skipped.

PY ?= python

.PHONY: check lint type test perf-smoke serve-smoke tune-smoke doctor-smoke league-smoke chaos-smoke fleet-smoke

check: lint type test

lint:
	@if $(PY) -c "import ruff" 2>/dev/null; then \
		echo "== ruff check =="; \
		$(PY) -m ruff check alphatriangle_tpu tests; \
	else \
		echo "== ruff unavailable; syntax gate via compileall =="; \
		$(PY) -m compileall -q alphatriangle_tpu tests __graft_entry__.py; \
	fi
	@echo "== graftlint (docs/ANALYSIS.md) =="
	@$(PY) -m alphatriangle_tpu.cli lint

type:
	@if $(PY) -c "import mypy" 2>/dev/null; then \
		echo "== mypy =="; \
		$(PY) -m mypy alphatriangle_tpu; \
	else \
		echo "== mypy unavailable in this image; skipping type gate =="; \
	fi

test:
	@if $(PY) -c "import pytest_cov" 2>/dev/null; then \
		echo "== pytest + coverage (fail_under from pyproject) =="; \
		$(PY) -m pytest tests/ -q --cov --cov-fail-under=40; \
	else \
		echo "== pytest (coverage plugin unavailable) =="; \
		$(PY) -m pytest tests/ -q; \
	fi

# Metrics-ledger pipeline gate: a short CPU training run must produce a
# parseable metrics.jsonl carrying memory-attribution + live-memory
# records, `cli perf` must summarize it (exit 2 = the ledger schema
# broke), `cli fit 1` must compose the static memory budget and exit
# 0 (the OOM pre-flight gate), and `cli compare` must hold against the
# checked-in reference summary (generous threshold — CI hosts vary in
# speed; the hard signal is schema alignment + "not catastrophically
# slower"). Regenerate the reference after intentional schema changes:
#   $(PY) benchmarks/perf_smoke.py --write-reference
perf-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/perf_smoke.py

# Policy-serving pipeline gate (docs/SERVING.md): `cli serve --smoke`
# must storm 96 simulated sessions on CPU over the {16,32,64}
# serve-shape ladder with int8 inference ON — the micro-batcher walks
# up >= 1 rung (64 concurrent at the top) and back down on the drain,
# zero recompiles after the all-rung warm, zero lost requests,
# admit/retire churn mid-run — land per-request p50/p95 move-latency
# records plus the serve_bucket/serve_fill gauges in the serve run's
# metrics ledger, and summarize them via `cli perf --json`.
serve-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/serve_smoke.py

# Experience-flywheel gate (docs/LEAGUE.md): seed a league pool from a
# tiny CPU run's checkpoints, then `cli league` must train the learner
# while a PolicyService plays matchmade pool games whose trajectories
# verifiably reach the replay ring (ledger `kind:"league"` records with
# ingest counts + staleness tags), promote the live net at least once
# under a permissive gate, keep league.jsonl's rating events consistent
# with its result events, surface the league fields through `cli perf
# --json` / `cli compare`, and leave a checkpoint that resumes under
# plain training.
league-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/league_smoke.py

# Window-forensics gate (docs/OBSERVABILITY.md "Flight recorder"):
# a synthetic torn flight ring must classify as dispatch-hung naming
# the exact program, a simulated over-deadline dispatch (frozen clock,
# exit-on-wedge off) must land wedge_report.json + stacks and doctor
# the same way, and sealed flight records must surface as per-program
# device-time rows in `cli perf --json`. Runs the doctor CLI in
# subprocesses — JAX is never imported on that path.
doctor-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/doctor_smoke.py

# Self-healing gate (docs/ROBUSTNESS.md): injected faults against real
# training children — a mid-run dispatch hang must die by the watchdog's
# exit 113 and be restarted by the supervisor from the latest committed
# checkpoint (completing with step loss <= one checkpoint cadence, the
# death->verdict->restart chain in supervisor.jsonl); SIGTERM must be
# absorbed as an emergency checkpoint + exit 114 that doctor reads as
# `preempted` and a rerun resumes; SIGKILL mid-checkpoint-save must
# leave a torn step dir that restore skips for the prior committed one.
# The supervisor parent runs with jax imports hard-blocked.
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/chaos_smoke.py

# Serve-fleet gate (docs/SERVING.md "Fleet"): a loadgen storm through
# `cli fleet --smoke` (2 replica subprocesses behind the least-queue-
# depth router, jax-free parent) must survive a mid-storm SIGKILL, an
# injected hang-serve wedge (watchdog 113 -> dispatch-hung -> respawn
# on a halved bucket -> re-admission, the chain in fleet.jsonl), and a
# rolling weight reload with zero recompiles — with ZERO lost requests
# (completed + shed == requests) and p95 move latency inside the SLO.
fleet-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/fleet_smoke.py

# Fit-driven autotuner gate (docs/AUTOTUNE.md): the search under
# `cli tune`, over the script's own tiny configs and lattice under a
# host-RAM byte limit, must emit a tuned_preset.json that
# `cli fit` independently confirms fits, whose winner out-predicts every
# feasible rejected candidate, that `cli train --preset <artifact>
# --dry-setup` can construct components from, and whose short real run
# ledgers the predicted-vs-observed tune_outcome record the next
# search's --calibrate reads.
tune-smoke:
	JAX_PLATFORMS=cpu $(PY) benchmarks/tune_smoke.py
