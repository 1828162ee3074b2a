"""Device telemetry plane: in-program stat-packs + progress beacons.

The fused megastep (rl/megastep.py, Podracer arXiv:2104.06272) bought a
1-dispatch iteration at the price of opacity: rollout, search, ingest,
PER sampling and K learner steps execute inside single XLA programs
that every host-side surface (tracer spans, flight recorder, anomaly
detector) can only see from outside, as one wall-clock number between
intent and seal. This module makes the fused black boxes observable
WITHOUT adding a dispatch or a host sync, with two legs:

**Stat-packs** (``TelemetryConfig.DEVICE_STATS``). Fixed-shape bundles
of KataGo-style search-health statistics (arXiv:1902.10565: root-visit
concentration/entropy, value bounds, tree occupancy) computed where the
data already lives — inside the search waves, the rollout chunk, the
PER sample and the fused learner steps — and returned through the
EXISTING single per-iteration fetch as one more leaf of the output
pytree. The host folds them into ``kind:"device_stats"`` ledger records
(`cli perf`, `cli watch`) and feeds them
to `AnomalyDetector.observe_search` so a value explosion or an entropy
collapse is attributed to the exact fused step, not the iteration
aggregate.

**Progress beacons** (off by default on hot paths). `jax.debug.callback`
markers at phase boundaries — every Nth search wave, each fused learner
step, the ring scatter — appending ``(program, phase, index, monotonic)``
rows to a crash-safe per-run ``beacons.jsonl`` via the ledger writer.
Armed by env (``ALPHATRIANGLE_BEACONS=1``), by the dispatch watchdog's
near-deadline warning, or by `cli supervise` on a dispatch-hung respawn
(the ``TELEMETRY__BEACONS`` override), so the SECOND occurrence of a
wedge names its phase: `wedge_report.json` and `cli doctor`'s
dispatch-hung verdict carry a ``last_beacon`` field ("hung at
megastep/t16_k8, phase=search_wave, wave=37"). Beacon-armed programs
key differently in the AOT compile cache (`beacon_signature` joins the
extra digest) and skip executable serialization — a callback closure
does not survive `serialize_executable`.

Module-top is JAX-free on purpose: `cli doctor` / `cli perf` import the
readers here beside a wedged chip. Only `emit_beacon` (called from
traced code) imports jax, lazily.
"""

import logging
import os
import threading
import time
from pathlib import Path

logger = logging.getLogger(__name__)

DEVICE_STATS_KIND = "device_stats"
BEACON_KIND = "beacon"
BEACONS_FILENAME = "beacons.jsonl"

#: Leaf-depth histogram bins in the per-wave search stat-pack. Depths at
#: or past the last bin clip into it, so the shape is static regardless
#: of max_depth.
DEPTH_BINS = 16

#: Default wave-subsampling for search beacons: every wave still calls
#: the host callback when armed, but only every Nth writes a row.
DEFAULT_BEACON_EVERY = 8

DEVICE_STATS_ENV = "ALPHATRIANGLE_DEVICE_STATS"
BEACONS_ENV = "ALPHATRIANGLE_BEACONS"
BEACON_EVERY_ENV = "ALPHATRIANGLE_BEACON_EVERY"

# --- process-global enable state -------------------------------------------
# Engines consult these at CONSTRUCTION time (the flags shape compiled
# programs, so they join the AOT cache extra digests); setup_training_
# components / the serve bring-up set them from TelemetryConfig before
# any engine is built. Env overrides exist so smokes and a respawned
# supervised child can flip them without threading a config through.

_lock = threading.Lock()
_device_stats: "bool | None" = None
_beacons_armed: "bool | None" = None
_beacon_every: "int | None" = None
_beacon_ledger = None  # telemetry.ledger.MetricsLedger once attached
_current_program: "str | None" = None


def device_stats_enabled() -> bool:
    """Whether engines should compile stat-packs into their programs.

    Defaults OFF until `set_device_stats` runs (training/serve setup
    wires it from ``TelemetryConfig.DEVICE_STATS``); the env override
    ``ALPHATRIANGLE_DEVICE_STATS=1/0`` wins over both."""
    env = os.environ.get(DEVICE_STATS_ENV)
    if env is not None and env != "":
        return env != "0"
    return bool(_device_stats)


def set_device_stats(flag: bool) -> None:
    global _device_stats
    _device_stats = bool(flag)


def beacons_armed() -> bool:
    """Whether programs built NOW should embed progress beacons."""
    global _beacons_armed
    if _beacons_armed is None:
        with _lock:
            if _beacons_armed is None:
                _beacons_armed = os.environ.get(BEACONS_ENV, "") not in (
                    "",
                    "0",
                )
    return _beacons_armed


def arm_beacons(every: "int | None" = None) -> None:
    """Arm beacons for programs built after this call.

    Called by the dispatch watchdog's near-deadline warning and by the
    runner when `cli supervise` delivers a ``TELEMETRY__BEACONS``
    override on a dispatch-hung respawn. Programs already compiled keep
    running beacon-free (re-tracing them mid-flight would risk the very
    wedge this exists to diagnose); a respawn rebuilds everything armed.
    """
    global _beacons_armed, _beacon_every
    with _lock:
        _beacons_armed = True
        if every is not None and every > 0:
            _beacon_every = int(every)
    logger.warning(
        "progress beacons ARMED (every %d search waves): programs built "
        "from now on append phase rows to %s",
        beacon_every(),
        BEACONS_FILENAME,
    )


def disarm_beacons() -> None:
    """Tests/teardown: forget the armed flag AND the env-derived cache."""
    global _beacons_armed, _beacon_ledger
    with _lock:
        _beacons_armed = False
        _beacon_ledger = None


def reset_device_stats_state() -> None:
    """Tests: back to import-time defaults (env re-read on next query)."""
    global _device_stats, _beacons_armed, _beacon_every, _beacon_ledger
    global _current_program
    with _lock:
        _device_stats = None
        _beacons_armed = None
        _beacon_every = None
        _beacon_ledger = None
        _current_program = None


def beacon_every() -> int:
    global _beacon_every
    if _beacon_every is None:
        try:
            _beacon_every = max(
                1, int(os.environ.get(BEACON_EVERY_ENV, DEFAULT_BEACON_EVERY))
            )
        except ValueError:
            _beacon_every = DEFAULT_BEACON_EVERY
    return _beacon_every


def beacon_signature() -> str:
    """AOT cache `extra` fragment for programs built under the current
    beacon state: a beacon-armed executable embeds host callbacks, so it
    must never be confused with (or deserialized as) the clean one."""
    return f"|beacons{beacon_every()}" if beacons_armed() else ""


def device_stats_signature() -> str:
    """AOT cache `extra` fragment for the stat-pack flag (it changes the
    program's output pytree)."""
    return "|devstats1" if device_stats_enabled() else ""


def attach_beacon_run_dir(run_dir) -> None:
    """Point beacon rows at ``<run_dir>/beacons.jsonl`` (RunTelemetry
    ctor). Harmless when beacons never arm — the ledger writer is only
    touched from inside an armed program's callback."""
    global _beacon_ledger
    if run_dir is None:
        return
    from .ledger import MetricsLedger

    with _lock:
        _beacon_ledger = MetricsLedger(Path(run_dir) / BEACONS_FILENAME)


def note_dispatch(program: str) -> None:
    """Best-effort program attribution for beacon rows: the dispatching
    host site names the program about to launch; the (async) callbacks
    it triggers stamp that name on their rows. Single-writer training
    loops dispatch one program at a time, so the attribution is exact
    there; overlapped streams may mis-attribute a row to the newest
    dispatch — the phase/index remain authoritative."""
    global _current_program
    _current_program = program


def _write_beacon_row(phase: str, index: int) -> None:
    ledger = _beacon_ledger
    if ledger is None:
        return
    ledger.append(
        {
            "kind": BEACON_KIND,
            "program": _current_program,
            "phase": phase,
            "index": index,
            "t_mono": time.monotonic(),
            "time": time.time(),
            "pid": os.getpid(),
        }
    )


def emit_beacon(phase: str, index, every: int = 1) -> None:
    """Trace-time beacon site. A Python-level no-op unless beacons are
    armed when the program is TRACED — the unarmed hot path compiles to
    exactly the program it compiled to before this module existed.

    When armed, inserts a `jax.debug.callback` that appends one row per
    firing (host-side subsampled to every `every`-th index — inside a
    fori_loop/scan the callback runs unordered, so the traced index is
    the authoritative sequencing, not arrival order)."""
    if not beacons_armed():
        return
    import jax

    step = max(1, int(every))

    def _cb(idx) -> None:
        try:
            i = int(idx)
            if i % step:
                return
            _write_beacon_row(phase, i)
        except Exception:  # a beacon must never kill a dispatch
            logger.debug("beacon write failed (%s)", phase, exc_info=True)

    jax.debug.callback(_cb, index, ordered=False)


# --- JAX-free readers (doctor / perf path) ---------------------------------


def read_beacons(path) -> list[dict]:
    """All parseable beacon rows from a ``beacons.jsonl`` (torn-tail
    tolerant via the ledger reader; missing file -> empty list, the
    legacy-run contract)."""
    from .ledger import iter_jsonl_records

    return list(iter_jsonl_records(path, kinds={BEACON_KIND}))


def last_beacon(run_dir_or_path) -> "dict | None":
    """The newest beacon row of a run, or None (no file / never armed).

    This is what `wedge_report.json` and the dispatch-hung doctor
    verdict carry: at wedge time the file ends with the last phase the
    hung program (or its predecessor iteration) announced."""
    if run_dir_or_path is None:
        return None
    path = Path(run_dir_or_path)
    if path.is_dir():
        path = path / BEACONS_FILENAME
    rows = read_beacons(path)
    return rows[-1] if rows else None


def describe_beacon(row: "dict | None") -> "str | None":
    """One-line rendering for doctor/wedge output: ``megastep/t16_k8
    phase=search_wave index=37 (2.1s before report)``-style."""
    if not isinstance(row, dict):
        return None
    program = row.get("program") or "?"
    return (
        f"{program} phase={row.get('phase')} index={row.get('index')}"
    )


# --- host-side folds --------------------------------------------------------


def _finite(value) -> "float | None":
    if isinstance(value, bool) or not isinstance(value, (int, float)):
        return None
    if value != value or value in (float("inf"), float("-inf")):
        return None
    return float(value)


def fold_search_stats(stats) -> "dict | None":
    """Fold a fetched search stat-pack (possibly (T,)-stacked by the
    rollout chunk's scan) into plain floats for the ledger record.

    Accepts host numpy arrays / scalars (post-`device_get`); never
    imports jax. Scalars fold as mean over the stacking axis except the
    excursion stats (`value_abs_max` folds as max); the depth histogram
    sums."""
    if not isinstance(stats, dict) or not stats:
        return None
    import numpy as np

    out: dict = {}
    for key, reduce_fn in (
        ("root_entropy", np.mean),
        ("root_concentration", np.mean),
        ("occupancy", np.mean),
        ("reuse_frac", np.mean),
        ("value_abs_max", np.max),
    ):
        if key in stats:
            try:
                out[key] = round(float(reduce_fn(np.asarray(stats[key]))), 6)
            except (TypeError, ValueError):
                continue
    if "depth_hist" in stats:
        try:
            hist = np.asarray(stats["depth_hist"], dtype=np.float64)
            if hist.ndim > 1:  # (T, BINS) stacked by the chunk scan
                hist = hist.sum(axis=tuple(range(hist.ndim - 1)))
            out["depth_hist"] = [round(float(v), 1) for v in hist.tolist()]
        except (TypeError, ValueError):
            pass
    return out or None


def merge_search_folds(folds: list) -> "dict | None":
    """Merge several already-folded search stat-packs (the serve loop
    accumulates one per wave between `tick()` windows) into one leg:
    scalars average, `value_abs_max` maxes, depth histograms sum."""
    rows = [f for f in folds if isinstance(f, dict) and f]
    if not rows:
        return None
    out: dict = {}
    for key in ("root_entropy", "root_concentration", "occupancy", "reuse_frac"):
        vals = [v for v in (_finite(r.get(key)) for r in rows) if v is not None]
        if vals:
            out[key] = round(sum(vals) / len(vals), 6)
    vmax = [
        v for v in (_finite(r.get("value_abs_max")) for r in rows) if v is not None
    ]
    if vmax:
        out["value_abs_max"] = round(max(vmax), 6)
    hists = [r["depth_hist"] for r in rows if isinstance(r.get("depth_hist"), list)]
    if hists:
        width = max(len(h) for h in hists)
        summed = [0.0] * width
        for h in hists:
            for i, v in enumerate(h):
                f = _finite(v)
                if f is not None:
                    summed[i] += f
        out["depth_hist"] = [round(v, 1) for v in summed]
    return out or None


def rollout_chunk_stats(endings, rewards) -> "dict | None":
    """Rollout-chunk stat leg from arrays the host ALREADY fetched
    (`play_chunk`'s one device_get): per-step-of-T episode terminations
    and reward extremes. Zero program change — pure host fold."""
    import numpy as np

    try:
        ends = np.asarray(endings)
        rew = np.asarray(rewards, dtype=np.float64)
    except (TypeError, ValueError):
        return None
    if ends.ndim < 2 or rew.size == 0:
        return None
    terms = (ends != 0).sum(axis=tuple(range(1, ends.ndim)))
    return {
        "terminations_per_step": [int(v) for v in terms.tolist()],
        "reward_min": round(float(rew.min()), 6),
        "reward_max": round(float(rew.max()), 6),
    }


def device_stats_record(
    step: int,
    program: "str | None" = None,
    search: "dict | None" = None,
    rollout: "dict | None" = None,
    per: "dict | None" = None,
    learner: "dict | None" = None,
    serve: "dict | None" = None,
    now: "float | None" = None,
) -> "dict | None":
    """One ``kind:"device_stats"`` ledger line; None when every leg is
    empty (nothing worth a record)."""
    legs = {
        k: v
        for k, v in (
            ("search", search),
            ("rollout", rollout),
            ("per", per),
            ("learner", learner),
            ("serve", serve),
        )
        if v
    }
    if not legs:
        return None
    record = {
        "kind": DEVICE_STATS_KIND,
        "step": step,
        "time": time.time() if now is None else now,
        **legs,
    }
    if program:
        record["program"] = program
    return record


def summarize_device_stats(records: list) -> "dict | None":
    """Fold a run's ``device_stats`` records into `cli perf` summary
    fields (all ``ds_``-prefixed). None for legacy runs (no records),
    so pre-PR ledgers summarize exactly as before."""
    rows = [
        r
        for r in records
        if isinstance(r, dict) and r.get("kind") == DEVICE_STATS_KIND
    ]
    if not rows:
        return None

    def leg(name: str, key: str) -> list:
        out = []
        for r in rows:
            v = _finite((r.get(name) or {}).get(key))
            if v is not None:
                out.append(v)
        return out

    def _mean(vals: list) -> "float | None":
        return round(sum(vals) / len(vals), 6) if vals else None

    def _max(vals: list) -> "float | None":
        return round(max(vals), 6) if vals else None

    def _min(vals: list) -> "float | None":
        return round(min(vals), 6) if vals else None

    return {
        "ds_records": len(rows),
        "ds_root_entropy": _mean(leg("search", "root_entropy")),
        "ds_root_entropy_min": _min(leg("search", "root_entropy")),
        "ds_root_concentration": _mean(leg("search", "root_concentration")),
        "ds_value_abs_max": _max(leg("search", "value_abs_max")),
        "ds_tree_occupancy": _mean(leg("search", "occupancy")),
        "ds_tree_occupancy_max": _max(leg("search", "occupancy")),
        "ds_reuse_frac": _mean(leg("search", "reuse_frac")),
        "ds_reward_min": _min(leg("rollout", "reward_min")),
        "ds_reward_max": _max(leg("rollout", "reward_max")),
        "ds_priority_skew": _max(leg("per", "priority_skew")),
        "ds_is_weight_min": _min(leg("per", "is_weight_min")),
        "ds_grad_norm_max": _max(leg("learner", "grad_norm_max")),
        "ds_update_norm_max": _max(leg("learner", "update_norm_max")),
        "ds_serve_root_entropy": _mean(leg("serve", "root_entropy")),
    }
