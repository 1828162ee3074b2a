"""Roofline attribution plane: compiler cost ground truth + chip-idle
gap forensics.

The ledger says how fast a run went and the flight ring says how long
each dispatch took, but neither can say how far a program sits from
what the hardware allows — MFU is computed from hand-derived analytic
FLOPs (utils/flops.py) and the wall-clock BETWEEN dispatches is
invisible. This module closes both gaps (Podracer's
hardware-utilization discipline, arXiv:2104.06272):

- **Cost capture.** Every program through the AOT compile cache
  records `compiled.cost_analysis()` — FLOPs, bytes accessed,
  transcendentals — as a `kind: "cost"` record (`program_cost_record`),
  persisted as a `.cost.json` sidecar beside the executable exactly
  like the `.mem.json` flow and drained into the run's
  `metrics.jsonl`.
- **Roofline model.** Arithmetic intensity (FLOPs / bytes accessed)
  against the device's machine balance (peak FLOP/s over peak HBM
  bandwidth, `peak_hbm_gbps_info` below) classifies each hot program
  compute- vs memory-bound; joining cost records against the flight
  ring's measured p50 dispatch walls yields achieved-vs-roofline
  fractions (`roofline_rows`).
- **Gap forensics.** A timeline pass over the flight ring
  (`attribute_gaps`) unions the intent→seal dispatch intervals into
  chip-busy time and attributes every idle gap to a named host
  category (fetch / ingest / ledger / checkpoint / other) via span
  overlap from `trace.json` — producing the `chip_idle_fraction`
  that rides util records, `cli perf`, `cli watch`, `cli compare`
  and the Prometheus textfile.

Nothing here imports JAX: `cli roofline` must render beside a wedged
chip, same contract as `cli mem` / `cli doctor`.
"""

import json
import logging
import os
import time
from pathlib import Path

from ..utils.flops import peak_info

logger = logging.getLogger(__name__)

COST_KIND = "cost"

# Operator-supplied peak HBM bandwidth override (GB/s): lets CPU/smoke
# runs and unlisted chips still produce a machine balance (parallel to
# utils/flops.py's ALPHATRIANGLE_PEAK_TFLOPS).
PEAK_HBM_GBPS_ENV = "ALPHATRIANGLE_PEAK_HBM_GBPS"

# "0" skips the setup-time cost pre-capture for AOT-bypassed programs
# (training/setup.py; CPU backend only — on an accelerator every
# program is on the AOT dispatch path and captures its cost when it
# compiles). The pre-capture is a fresh lower+compile purely for
# `cost_analysis()`: seconds of pure overhead per process, so the test
# suite turns it off (tests/conftest.py; subprocess children inherit
# it).
COST_PRECAPTURE_ENV = "ALPHATRIANGLE_COST_PRECAPTURE"


def cost_precapture_enabled() -> bool:
    return os.environ.get(COST_PRECAPTURE_ENV, "1").strip() != "0"

# Peak HBM bandwidth per chip, GB/s, keyed like the bf16 table in
# utils/flops.py and from the same source (Google Cloud TPU
# documentation, system-architecture page of each generation).
_PEAK_HBM_GBPS = {
    "TPU v4": 1228.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5": 2765.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1638.0,
    "TPU v6e": 1638.0,
}

#: Named host-gap categories, attribution order. "other" absorbs every
#: idle second no span claims, so dispatch + gaps always cover the
#: whole flight timeline.
GAP_CATEGORIES = ("fetch", "ingest", "ledger", "checkpoint", "other")

# Span-name keywords -> gap category. The loop's host phases
# (docs/OBSERVABILITY.md "Spans"): result fetch/harvest lands in
# "fetch", replay fold/sampling in "ingest", telemetry/stats ticks in
# "ledger", checkpoint + weight sync in "checkpoint".
_SPAN_CATEGORY_KEYWORDS = (
    ("fetch", ("fetch", "harvest", "rollout", "d2h")),
    ("ingest", ("fold", "sample", "ingest", "enqueue", "stream", "h2d")),
    ("ledger", ("ledger", "tick", "stats", "telemetry", "health", "prom")),
    ("checkpoint", ("checkpoint", "weight_sync", "save")),
)


def peak_hbm_gbps_info(device_kind: str) -> "tuple[float | None, str]":
    """(peak HBM GB/s, source) for a `jax.Device.device_kind`: the
    bandwidth twin of `utils.flops.peak_bf16_tflops_info`, same rules
    (`utils.flops.peak_info`), override ALPHATRIANGLE_PEAK_HBM_GBPS."""
    return peak_info(_PEAK_HBM_GBPS, PEAK_HBM_GBPS_ENV, device_kind)


def machine_balance_flops_per_byte(
    peak_tflops, peak_hbm_gbps
) -> "float | None":
    """Machine balance (FLOPs per byte): programs whose arithmetic
    intensity exceeds it are compute-bound on this chip, the rest are
    bandwidth-bound. None when either peak is unknown."""
    if not _num(peak_tflops) or not _num(peak_hbm_gbps):
        return None
    if peak_tflops <= 0 or peak_hbm_gbps <= 0:
        return None
    return (peak_tflops * 1e12) / (peak_hbm_gbps * 1e9)


def _num(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


# --- cost records (writer side; called via compile_cache) ----------------


def program_cost_record(
    name: str,
    compiled,
    backend: str = "",
    key: str = "",
    origin: str = "compile",
) -> "dict | None":
    """One `kind: "cost"` record from a compiled program's
    `cost_analysis()` (FLOPs / bytes accessed / transcendentals).
    Handles both the dict and the legacy list-of-dicts return shape.
    None when the executable doesn't support the analysis — cost
    attribution degrades, nothing raises (same contract as
    `memory.program_memory_record`)."""
    analysis = getattr(compiled, "cost_analysis", None)
    if analysis is None:
        return None
    try:
        stats = analysis()
    except Exception:
        return None
    if isinstance(stats, (list, tuple)):
        stats = next((s for s in stats if isinstance(s, dict)), None)
    if not isinstance(stats, dict):
        return None

    def grab(field: str) -> "float | None":
        v = stats.get(field)
        return float(v) if _num(v) else None

    flops = grab("flops")
    bytes_accessed = grab("bytes accessed")
    transcendentals = grab("transcendentals")
    if all(v is None for v in (flops, bytes_accessed, transcendentals)):
        return None
    return {
        "kind": COST_KIND,
        "category": "program",
        "component": f"program/{name}",
        "program": name,
        "key": key,
        "backend": backend,
        "origin": origin,
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "transcendentals": transcendentals,
        "time": time.time(),
    }


# --- readers (no JAX on this path) ---------------------------------------


def latest_cost_by_program(records) -> dict:
    """Newest usable cost record per program name (re-compiles re-emit;
    the roofline wants the latest of each). Non-dict and non-cost rows
    are skipped — torn/legacy ledgers degrade, never raise."""
    out: dict = {}
    for rec in records:
        if (
            isinstance(rec, dict)
            and rec.get("kind") == COST_KIND
            and rec.get("program")
        ):
            out[str(rec["program"])] = rec
    return out


def cost_flops_by_family(records) -> dict:
    """Per-family compiler-reported FLOPs per dispatch: the HOTTEST
    (max-FLOP) program of each family wins — the autotuner's
    `cost_flops` calibration source (autotune/model.py)."""
    from .flight import program_family

    out: dict = {}
    for program, rec in latest_cost_by_program(records).items():
        flops = rec.get("flops")
        if not _num(flops) or flops <= 0:
            continue
        fam = program_family(program)
        if fam not in out or flops > out[fam]:
            out[fam] = float(flops)
    return out


def roofline_rows(
    cost_records,
    flight_rows,
    peak_tflops=None,
    peak_hbm_gbps=None,
) -> list:
    """Per-program roofline rows: `summarize_flight` rows joined with
    the newest cost record per program. Every flight row yields a row;
    programs with no cost record (legacy runs, torn sidecars) come out
    with None cost fields — "n/a" in the tables, never an error.

    Row fields: program, family, count, wall_s_p50, wall_s_total,
    flops, bytes_accessed, intensity (FLOPs/byte), bound ("compute" /
    "memory" / None), achieved_tflops (compiler FLOPs over measured
    p50 wall), roofline_tflops (the ceiling at this intensity), and
    roofline_fraction (achieved / ceiling).
    """
    balance = machine_balance_flops_per_byte(peak_tflops, peak_hbm_gbps)
    by_program = latest_cost_by_program(cost_records)
    rows = []
    for fr in flight_rows or []:
        if not isinstance(fr, dict):
            continue
        program = str(fr.get("program"))
        cost = by_program.get(program)
        flops = cost.get("flops") if cost else None
        bytes_accessed = cost.get("bytes_accessed") if cost else None
        intensity = None
        if _num(flops) and _num(bytes_accessed) and bytes_accessed > 0:
            intensity = flops / bytes_accessed
        bound = None
        if intensity is not None and balance is not None:
            bound = "compute" if intensity > balance else "memory"
        wall_p50 = fr.get("wall_s_p50")
        achieved = None
        if _num(flops) and _num(wall_p50) and wall_p50 > 0:
            achieved = flops / wall_p50
        ceiling = None
        if _num(peak_tflops) and peak_tflops > 0:
            ceiling = peak_tflops * 1e12
            if intensity is not None and _num(peak_hbm_gbps):
                ceiling = min(ceiling, intensity * peak_hbm_gbps * 1e9)
        fraction = None
        if achieved is not None and ceiling is not None and ceiling > 0:
            fraction = achieved / ceiling
        rows.append(
            {
                "program": program,
                "family": fr.get("family"),
                "count": fr.get("count"),
                "wall_s_p50": wall_p50,
                "wall_s_total": fr.get("wall_s_total"),
                "flops": flops if _num(flops) else None,
                "bytes_accessed": (
                    bytes_accessed if _num(bytes_accessed) else None
                ),
                "transcendentals": (
                    cost.get("transcendentals") if cost else None
                ),
                "intensity": (
                    round(intensity, 4) if intensity is not None else None
                ),
                "bound": bound,
                "achieved_tflops": (
                    round(achieved / 1e12, 6) if achieved is not None else None
                ),
                "roofline_tflops": (
                    round(ceiling / 1e12, 6) if ceiling is not None else None
                ),
                "roofline_fraction": (
                    round(fraction, 6) if fraction is not None else None
                ),
            }
        )
    return rows


# --- gap forensics -------------------------------------------------------


def load_trace_spans(trace_path) -> list:
    """(category, begin_s, end_s) wall-clock span intervals from a
    run's `trace.json` (telemetry/tracer.py), keyword-mapped to gap
    categories; uncategorized spans are dropped (the residual lands in
    "other" anyway). Missing/corrupt traces return [] — gap
    attribution degrades to all-"other", never raises."""
    try:
        data = json.loads(Path(trace_path).read_text())
    except (OSError, ValueError):
        return []
    events = data.get("traceEvents", []) if isinstance(data, dict) else data
    if not isinstance(events, list):
        return []
    spans = []
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        ts, dur = ev.get("ts"), ev.get("dur")
        if not _num(ts) or not _num(dur) or dur <= 0:
            continue
        category = _span_category(str(ev.get("name", "")))
        if category is None:
            continue
        begin = ts / 1e6  # Chrome traces use microseconds
        spans.append((category, begin, begin + dur / 1e6))
    spans.sort(key=lambda s: s[1])
    return spans


def _span_category(name: str) -> "str | None":
    low = name.lower()
    for category, keywords in _SPAN_CATEGORY_KEYWORDS:
        if any(k in low for k in keywords):
            return category
    return None


def _merge_intervals(intervals: list) -> list:
    """Sorted (begin, end) intervals -> merged disjoint intervals."""
    merged: list = []
    for begin, end in sorted(intervals):
        if end <= begin:
            continue
        if merged and begin <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([begin, end])
    return merged


def _overlap_seconds(merged: list, begin: float, end: float) -> float:
    """Seconds of a merged interval list that fall inside [begin, end]."""
    total = 0.0
    for b, e in merged:
        if e <= begin:
            continue
        if b >= end:
            break
        total += min(e, end) - max(b, begin)
    return total


def attribute_gaps(flight_records, spans=None) -> "dict | None":
    """Timeline attribution over a run's flight ring.

    Unions the sealed intent→seal intervals (t_mono) into chip-busy
    time; the complement within [first record, last record] is chip
    idle, attributed per gap to the named host categories via
    wall-clock span overlap (`spans` from `load_trace_spans`; the
    mono→wall offset is the median over the records that carry both
    stamps). Overclaimed gaps scale proportionally; unclaimed seconds
    land in "other" — dispatch + gaps therefore always cover the whole
    timeline (`attributed_fraction` 1.0 by construction, <1.0 only
    when intervals are unusable).

    Returns None when fewer than two timestamped records exist (a
    legacy or empty ring), else {wall_s, dispatch_s, gap_s, gaps:
    {category: s}, chip_idle_fraction, attributed_fraction,
    dispatches, unsealed}.
    """
    stamped = [
        r
        for r in flight_records or []
        if isinstance(r, dict) and _num(r.get("t_mono"))
    ]
    if len(stamped) < 2:
        return None
    t0 = min(r["t_mono"] for r in stamped)
    t1 = max(r["t_mono"] for r in stamped)
    wall = t1 - t0
    if wall <= 0:
        return None
    intents = {
        r.get("seq"): r for r in stamped if r.get("phase") == "intent"
    }
    dispatch_intervals = []
    dispatches = 0
    for r in stamped:
        if r.get("phase") != "seal":
            continue
        intent = intents.pop(r.get("seq"), None)
        if intent is None:
            continue
        dispatches += 1
        dispatch_intervals.append((intent["t_mono"], r["t_mono"]))
    busy = _merge_intervals(dispatch_intervals)
    dispatch_s = sum(e - b for b, e in busy)
    # Idle gaps: the complement of chip-busy within the timeline.
    gaps = []
    cursor = t0
    for b, e in busy:
        if b > cursor:
            gaps.append((cursor, b))
        cursor = max(cursor, e)
    if t1 > cursor:
        gaps.append((cursor, t1))
    # mono -> wall offset for span overlap (spans are wall-clock).
    offsets = sorted(
        r["time"] - r["t_mono"] for r in stamped if _num(r.get("time"))
    )
    offset = offsets[len(offsets) // 2] if offsets else None
    by_category = {}
    if spans and offset is not None:
        for category, begin, end in spans:
            by_category.setdefault(category, []).append((begin, end))
        by_category = {
            c: _merge_intervals(ivals) for c, ivals in by_category.items()
        }
    totals = {c: 0.0 for c in GAP_CATEGORIES}
    for begin, end in gaps:
        length = end - begin
        claimed = {}
        if by_category:
            wb, we = begin + offset, end + offset
            for category, merged in by_category.items():
                sec = _overlap_seconds(merged, wb, we)
                if sec > 0:
                    claimed[category] = sec
        claimed_total = sum(claimed.values())
        if claimed_total > length > 0:
            scale = length / claimed_total
            claimed = {c: s * scale for c, s in claimed.items()}
            claimed_total = length
        for category, sec in claimed.items():
            totals[category] += sec
        totals["other"] += max(0.0, length - claimed_total)
    gap_s = sum(e - b for b, e in gaps)
    return {
        "wall_s": round(wall, 6),
        "dispatch_s": round(dispatch_s, 6),
        "gap_s": round(gap_s, 6),
        "gaps": {c: round(s, 6) for c, s in totals.items()},
        "chip_idle_fraction": round(gap_s / wall, 6),
        "attributed_fraction": round((dispatch_s + gap_s) / wall, 6),
        "dispatches": dispatches,
        "unsealed": len(intents),
    }


# --- run-level summary (cli roofline / cli perf fold) --------------------


def summarize_roofline(
    cost_records,
    flight_records,
    device_kind: str = "",
    peak_tflops=None,
    trace_path=None,
) -> "dict | None":
    """The `cli roofline` payload: machine balance + per-program rows +
    gap attribution for one run. `peak_tflops` should come from the
    run's own util records (already env-resolved at run time); the HBM
    peak resolves here so `ALPHATRIANGLE_PEAK_HBM_GBPS` works at read
    time. None when the run has neither cost records nor a usable
    flight timeline (exit-2 territory for the CLI)."""
    from .flight import summarize_flight

    flight_rows = summarize_flight(flight_records or [])
    peak_gbps, hbm_source = peak_hbm_gbps_info(device_kind)
    rows = roofline_rows(
        cost_records or [],
        flight_rows,
        peak_tflops=peak_tflops,
        peak_hbm_gbps=peak_gbps,
    )
    spans = load_trace_spans(trace_path) if trace_path else []
    attribution = attribute_gaps(flight_records or [], spans=spans)
    if not rows and attribution is None:
        return None
    balance = machine_balance_flops_per_byte(peak_tflops, peak_gbps)
    return {
        "schema": "alphatriangle.roofline.v1",
        "device_kind": device_kind,
        "peak_bf16_tflops": peak_tflops if _num(peak_tflops) else None,
        "peak_hbm_gbps": peak_gbps,
        "peak_hbm_source": hbm_source,
        "machine_balance_flops_per_byte": (
            round(balance, 4) if balance is not None else None
        ),
        "programs": rows,
        "attribution": attribution,
    }
