"""Memory ledger: per-program HBM attribution + OOM pre-flight math.

The telemetry stack answers "how fast" (perf.py) and "is it alive"
(health.py) but, before this module, not "where did the HBM go" — the
question that decides whether a bigger batch, a deeper net, or a larger
device-resident replay ring fits BEFORE a scarce TPU window is burned
on an OOM. Podracer-style pipelines (arXiv:2104.06272) and MindSpeed RL
(arXiv:2507.19017) both treat per-component memory accounting and
ahead-of-time fit checks as first-class infrastructure; this is that
tier here:

- **Static attribution.** Every program wrapped by
  `compile_cache.CachedProgram` records its AOT
  `compiled.memory_analysis()` — argument / output / temp /
  generated-code bytes — at compile time (`program_memory_record`),
  persisted beside the executable artifact and drained into the run's
  `metrics.jsonl` as `kind: "memory"` records. Model/optimizer/
  train-state bytes come from tree-size accounting (`tree_bytes`,
  `train_state_record`), replay-ring bytes from the device buffers'
  own dtype/shape math (`replay_ring_bytes` — asserted equal to the
  allocated storage in tests).
- **Budget composition.** `compose_budget` folds those records into a
  worst-case per-device budget: persistent train state + device ring +
  rollout-carry residency (chunk-program arguments minus params) +
  the worst single program's transient (temp + output). `cli fit`
  checks it against `bytes_limit`; `cli mem` renders the attribution
  table; `cli compare` gates `memory_budget_bytes` across runs.
- **Live accounting** lives in `perf.UtilizationMeter` (per-tick
  `mem_bytes_in_use`/`mem_peak_bytes_in_use` + high-water tracking)
  and `health.device_memory_stats`; the leak detector is
  `anomaly.AnomalyDetector.observe_memory` (`Anomaly/memory_growth`).

Reader functions here never import JAX — `cli mem` must render a run's
attribution from artifacts alone beside a wedged chip. Anything that
needs JAX (tree accounting, the fit estimator) imports it lazily.
"""

import logging
import math
import time

logger = logging.getLogger(__name__)

MEMORY_KIND = "memory"

# Operator-supplied per-device byte budget override: lets `cli fit`
# assert a denominator for backends that report no allocator limit
# (parallel to utils/flops.py's ALPHATRIANGLE_PEAK_TFLOPS).
BYTES_LIMIT_ENV = "ALPHATRIANGLE_DEVICE_BYTES_LIMIT"

# `cli fit` exit codes.
FIT_OK = 0  # budget fits the per-device limit
FIT_OVER = 1  # budget exceeds the limit
FIT_UNKNOWN = 2  # no device byte limit known (and no override)


def fmt_bytes(n) -> str:
    """Human bytes for tables: '1.50 GiB' / '320.0 KiB' / '—'."""
    if not isinstance(n, (int, float)) or isinstance(n, bool):
        return "—"
    n = float(n)
    for unit, scale in (("GiB", 2**30), ("MiB", 2**20), ("KiB", 2**10)):
        if abs(n) >= scale:
            return f"{n / scale:,.2f} {unit}"
    return f"{n:,.0f} B"


# --- static attribution records -----------------------------------------


def program_memory_record(
    name: str,
    compiled,
    backend: str = "",
    key: str = "",
    origin: str = "compile",
) -> "dict | None":
    """One `kind: "memory"` record from an AOT program's
    `memory_analysis()` (argument/output/temp/generated-code bytes).
    None when the executable doesn't support the analysis (exotic
    backends) — attribution degrades, nothing raises."""
    analysis = getattr(compiled, "memory_analysis", None)
    if analysis is None:
        return None
    try:
        stats = analysis()
    except Exception:
        return None
    if stats is None:
        return None

    def grab(attr: str) -> "int | None":
        v = getattr(stats, attr, None)
        return int(v) if isinstance(v, (int, float)) else None

    b = {
        "argument": grab("argument_size_in_bytes"),
        "output": grab("output_size_in_bytes"),
        "temp": grab("temp_size_in_bytes"),
        "generated_code": grab("generated_code_size_in_bytes"),
        "alias": grab("alias_size_in_bytes"),
    }
    if all(v is None for v in b.values()):
        return None
    # TPU analyses additionally expose a whole-program peak; keep it
    # when present (it subsumes temp+output as the transient bound).
    peak = grab("peak_memory_in_bytes")
    v = {k: x or 0 for k, x in b.items()}
    rec = {
        "kind": MEMORY_KIND,
        "category": "program",
        "component": f"program/{name}",
        "program": name,
        "key": key,
        "backend": backend,
        "origin": origin,
        "bytes": b,
        "total": v["argument"] + v["output"] + v["temp"] + v["generated_code"],
        # Extra bytes one dispatch needs beyond its resident arguments:
        # temps plus the NON-aliased outputs (donated outputs reuse
        # argument buffers — `alias` bytes — and allocate nothing new).
        "transient": v["temp"] + max(0, v["output"] - v["alias"]),
        "time": time.time(),
    }
    if peak is not None:
        rec["peak"] = peak
    return rec


def tree_bytes(tree) -> int:
    """Total bytes of every array leaf in a pytree (shape x dtype
    itemsize — works on concrete arrays and ShapeDtypeStructs alike).
    Lazy JAX import: this is a writer-side helper."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        size = getattr(leaf, "size", None)
        if dtype is None or size is None:
            continue
        try:
            total += int(size) * int(np.dtype(dtype).itemsize)
        except TypeError:
            continue
    return total


def train_state_record(state) -> dict:
    """Tree-size accounting of one TrainState: params vs optimizer
    state vs batch stats (the bytes `training/setup.py` ledgers)."""
    parts = {
        "params": tree_bytes(getattr(state, "params", None)),
        "opt_state": tree_bytes(getattr(state, "opt_state", None)),
        "batch_stats": tree_bytes(getattr(state, "batch_stats", None)),
    }
    total = tree_bytes(state)
    return {
        "kind": MEMORY_KIND,
        "category": "state",
        "component": "train_state",
        "bytes": parts,
        "total": total,
        "time": time.time(),
    }


def replay_ring_bytes(
    capacity: int,
    grid_shape: tuple,
    other_dim: int,
    action_dim: int,
    shards: int = 1,
) -> int:
    """Exact bytes of a device replay ring's storage, from the same
    dtype/shape math the buffers allocate with: one int8 grid cell per
    board cell, float32 everything else, one trash row per shard
    (rl/device_buffer.py / rl/sharded_device_buffer.py — tests assert
    this equals the allocated storage bit for bit)."""
    rows = int(capacity) + int(shards)
    row_bytes = (
        int(math.prod(grid_shape))  # grid, int8
        + 4 * int(other_dim)  # other_features, float32
        + 4 * int(action_dim)  # policy_target, float32
        + 4  # value_target, float32
        + 4  # policy_weight, float32
    )
    return rows * row_bytes


def replay_ring_record(
    total_bytes: int,
    capacity: int,
    shards: int = 1,
    location: str = "device",
) -> dict:
    """The ledger record for one replay ring (location "device" for the
    HBM-resident rings, "host" for the NumPy buffer — host rings are
    listed in the attribution table but excluded from the HBM budget)."""
    return {
        "kind": MEMORY_KIND,
        "category": "ring",
        "component": "replay_ring",
        "bytes": {"storage": int(total_bytes)},
        "total": int(total_bytes),
        "capacity": int(capacity),
        "shards": int(shards),
        "location": location,
        "time": time.time(),
    }


# --- live totals ---------------------------------------------------------


def summarize_device_memory(device_memory) -> "dict | None":
    """Fold `health.device_memory_stats()` rows into run totals:
    summed in-use/peak, summed limit (None when no device reports one).
    """
    if not device_memory:
        return None
    in_use = 0
    peak = 0
    limits = []
    for d in device_memory:
        if not isinstance(d, dict):
            continue
        u = d.get("bytes_in_use")
        if isinstance(u, (int, float)):
            in_use += int(u)
        p = d.get("peak_bytes_in_use")
        peak += int(p) if isinstance(p, (int, float)) else (
            int(u) if isinstance(u, (int, float)) else 0
        )
        lim = d.get("bytes_limit")
        if isinstance(lim, (int, float)) and lim > 0:
            limits.append(int(lim))
    return {
        "bytes_in_use": in_use,
        "peak_bytes_in_use": peak,
        "bytes_limit": sum(limits) if limits else None,
    }


# --- budget composition --------------------------------------------------


def latest_by_component(records) -> dict:
    """Newest record per component name (re-compiles and re-runs
    re-emit records; attribution wants the latest of each)."""
    out: dict = {}
    for rec in records:
        if isinstance(rec, dict) and rec.get("component"):
            out[rec["component"]] = rec
    return out


def compose_budget(records) -> dict:
    """Fold memory records into the static per-device budget.

    total = train-state bytes (params + optimizer + batch stats,
    resident for the whole run) + device replay ring + rollout carry
    residency (the chunk program's argument bytes minus the params it
    shares with the train state — game/tree state that stays resident
    between chunks) + the worst single program's transient (temp +
    output; the program-reported `peak` wins when present). Host rings
    are excluded: they live in host RAM, not HBM.

    The budget is PER DEVICE: a dp-sharded ring's record carries the
    global storage bytes over `shards` devices (each device holds only
    its cap_local = capacity/dp rows plus one trash row), so device
    ring totals divide by their shard count before entering the sum.
    """
    latest = latest_by_component(records)
    state = next(
        (r for r in latest.values() if r.get("category") == "state"), None
    )
    rings = [r for r in latest.values() if r.get("category") == "ring"]
    programs = [
        r for r in latest.values() if r.get("category") == "program"
    ]
    params_bytes = int(((state or {}).get("bytes") or {}).get("params") or 0)
    state_total = int((state or {}).get("total") or 0)
    ring_device = sum(
        int(r.get("total") or 0) // max(1, int(r.get("shards") or 1))
        for r in rings
        if r.get("location") == "device"
    )
    rollout_resident = 0
    transient = 0
    for rec in programs:
        b = rec.get("bytes") or {}
        arg = int(b.get("argument") or 0)
        if str(rec.get("program") or "").startswith("self_play"):
            rollout_resident = max(rollout_resident, max(0, arg - params_bytes))
        peak = rec.get("peak")
        t = (
            int(peak)
            if isinstance(peak, (int, float))
            else int(rec.get("transient") or 0)
        )
        transient = max(transient, t)
    return {
        "train_state_bytes": state_total,
        "replay_ring_bytes": ring_device,
        "rollout_resident_bytes": rollout_resident,
        "program_transient_bytes": transient,
        "total_bytes": state_total + ring_device + rollout_resident + transient,
        "programs": len(programs),
    }


def serve_budget_bytes(record) -> int:
    """Per-device bytes a STANDALONE policy service needs, from its
    serve program's memory record: resident arguments (net variables +
    the slot-array states) plus the dispatch transient (the
    program-reported whole-program peak wins when present). There is
    no learner state and no replay ring on a serving chip — this is
    the `cli serve` pre-flight's budget, next to `compose_budget`'s
    training-process one."""
    if not isinstance(record, dict):
        return 0
    b = record.get("bytes") or {}
    arg = int(b.get("argument") or 0)
    peak = record.get("peak")
    transient = (
        int(peak)
        if isinstance(peak, (int, float))
        else int(record.get("transient") or 0)
    )
    return arg + transient


def fit_verdict(total_bytes, bytes_limit) -> tuple:
    """(exit code, reason) for a budget against a per-device limit."""
    if not isinstance(bytes_limit, (int, float)) or bytes_limit <= 0:
        return FIT_UNKNOWN, (
            "no device byte limit known for this backend (set "
            f"{BYTES_LIMIT_ENV} to assert one)"
        )
    frac = total_bytes / bytes_limit
    if total_bytes <= bytes_limit:
        return FIT_OK, (
            f"fits: {fmt_bytes(total_bytes)} is {frac:.1%} of the "
            f"{fmt_bytes(bytes_limit)} per-device limit"
        )
    return FIT_OVER, (
        f"OVER BUDGET: {fmt_bytes(total_bytes)} is {frac:.1%} of the "
        f"{fmt_bytes(bytes_limit)} per-device limit"
    )


# --- attribution rendering (no JAX on this path) -------------------------


def attribution_rows(records) -> list:
    """(component, total bytes, detail) rows for `cli mem`'s table,
    biggest first."""
    rows = []
    for rec in latest_by_component(records).values():
        b = rec.get("bytes") or {}
        cat = rec.get("category")
        if cat == "program":
            detail = (
                f"args {fmt_bytes(b.get('argument'))}, "
                f"out {fmt_bytes(b.get('output'))}, "
                f"temp {fmt_bytes(b.get('temp'))}, "
                f"code {fmt_bytes(b.get('generated_code'))}"
            )
        elif cat == "state":
            detail = (
                f"params {fmt_bytes(b.get('params'))}, "
                f"opt {fmt_bytes(b.get('opt_state'))}, "
                f"bn {fmt_bytes(b.get('batch_stats'))}"
            )
        elif cat == "ring":
            detail = (
                f"capacity {rec.get('capacity'):,} x {rec.get('shards')} "
                f"shard(s), {rec.get('location')}"
            )
        else:
            detail = ""
        rows.append((rec.get("component") or "?", rec.get("total") or 0, detail))
    rows.sort(key=lambda r: -r[1])
    return rows


# --- pre-flight estimator (JAX-side; `cli fit`) --------------------------


def resolve_bytes_limit(
    limit_gb: "float | None", environ=None
) -> tuple:
    """(per-device byte limit, source) with the `cli fit` resolution
    order shared by fit/tune/serve: an explicit --limit-gb flag wins,
    then the ALPHATRIANGLE_DEVICE_BYTES_LIMIT env override, then the
    smallest limit any local device reports (conservative on
    heterogeneous hosts). (None, "none") when nothing is known —
    FIT_UNKNOWN territory."""
    import os

    env = os.environ if environ is None else environ
    if limit_gb is not None:
        return limit_gb * 2**30, "flag"
    override = str(env.get(BYTES_LIMIT_ENV, "") or "").strip()
    if override:
        try:
            return float(override), "env"
        except ValueError:
            logger.warning(
                "%s=%r is not a number; ignoring.", BYTES_LIMIT_ENV, override
            )
    from .health import device_memory_stats

    limits = [
        m.get("bytes_limit")
        for m in device_memory_stats()
        if isinstance(m.get("bytes_limit"), (int, float))
        and m.get("bytes_limit") > 0
    ]
    if limits:
        return min(limits), "device"
    return None, "none"


def sharded_megastep_dp(train_config) -> int:
    """dp width the sharded megastep family (`megastep/dp<D>_t<T>_k<K>`)
    would run at in THIS process: the device count when the geometry
    divides like the training-time gate (training/setup.py's
    `_make_buffer`), else 1 (the single-device family): `estimate_fit`
    analyzes the program the run will actually dispatch."""
    import jax

    dp = jax.device_count()
    if (
        jax.process_count() == 1
        and dp > 1
        and train_config.BUFFER_CAPACITY % dp == 0
        and train_config.BATCH_SIZE % dp == 0
        and train_config.SELF_PLAY_BATCH_SIZE % dp == 0
    ):
        return dp
    return 1


def estimate_fit(
    env_config,
    model_config,
    mcts_config,
    train_config,
    fused_k: int = 4,
    device_replay: bool = False,
    megastep: bool = False,
    serve: bool = False,
    serve_batch: "int | None" = None,
    serve_buckets=None,
    programs: "set[str] | None" = None,
    progress=None,
) -> dict:
    """Build the run's hot programs AOT (lowered + compiled, never
    executed) and compose the static memory budget for them.

    `programs`: optional name filter (substring match against the
    program labels, same contract as `cli warm --programs`) — the
    autotuner's feasibility oracle analyzes only the programs that
    bound its candidate's budget instead of paying every compile per
    search point. Static records (train state, replay ring) are always
    composed regardless of the filter.

    Returns {"records": [...], "budget": compose_budget(...)}. The
    device-replay gather program is not lowered here — lowering it
    needs the ring allocated, which is exactly the allocation a
    pre-flight must not make; the ring is accounted statically and the
    gather's transient is bounded by the fused program's. `megastep`
    additionally analyzes the fused-megastep program (rl/megastep.py) —
    this one DOES allocate the configured ring (its storage is a
    program argument), so it is opt-in; `cli fit` enables it for the
    configs that run it (`FUSED_MEGASTEP`). `serve` additionally analyzes the
    policy service's `serve/b<B>` search program (serving/service.py;
    B = `serve_batch`, default the self-play lane count) and persists
    its `.mem.json` sidecar — the OOM pre-flight `cli serve` runs
    before occupying a chip. `serve_buckets` (a serving/buckets.py
    ladder spec) analyzes EVERY rung's program: the micro-batcher may
    dispatch any of them, so the pre-flight must budget the whole
    ladder, and each rung gets its own sidecar pair.
    """
    from ..env.engine import TriangleEnv
    from ..features.core import get_feature_extractor
    from ..nn.network import NeuralNetwork
    from ..rl.self_play import SelfPlayEngine
    from ..rl.trainer import Trainer

    def say(msg: str) -> None:
        logger.info(msg)
        if progress is not None:
            progress(msg)

    env = TriangleEnv(env_config)
    extractor = get_feature_extractor(env, model_config)
    net = NeuralNetwork(model_config, env_config, seed=0)
    engine = SelfPlayEngine(
        env, extractor, net, mcts_config, train_config, seed=0
    )
    trainer = Trainer(net, train_config)

    records = [train_state_record(trainer.state)]
    ring_bytes = replay_ring_bytes(
        train_config.BUFFER_CAPACITY,
        (model_config.GRID_INPUT_CHANNELS, env_config.ROWS, env_config.COLS),
        extractor.other_dim,
        env_config.action_dim,
    )
    records.append(
        replay_ring_record(
            ring_bytes,
            train_config.BUFFER_CAPACITY,
            location="device" if device_replay else "host",
        )
    )
    if getattr(mcts_config, "descent_gather", "einsum") == "einsum":
        # The einsum descent gather materializes a (B, W, N) f32
        # one-hot every level (mcts/search.py `_descend_wave`,
        # ops/gather_rows.py). XLA's memory analysis can fuse that
        # temp out of the reported footprint entirely (CPU analyses
        # often report temp=0), so the composed transient silently
        # undercounted the rollout program. This analytic record
        # floors the budget with the one-hot bytes; when the
        # program-reported peak is larger it still wins (max over
        # records in `compose_budget`). The "pallas"/"take" gathers
        # never build the one-hot, so no floor applies there.
        wave = max(
            1,
            min(mcts_config.mcts_batch_size, mcts_config.max_simulations),
        )
        while mcts_config.max_simulations % wave:
            wave -= 1
        onehot_bytes = (
            4
            * train_config.SELF_PLAY_BATCH_SIZE
            * wave
            * (mcts_config.max_simulations + 1)
        )
        records.append(
            {
                "kind": MEMORY_KIND,
                "category": "program",
                "component": "program/descent_gather_onehot",
                "program": "descent_gather_onehot",
                "origin": "analytic",
                "bytes": {"temp": onehot_bytes},
                "total": onehot_bytes,
                "transient": onehot_bytes,
                "time": time.time(),
            }
        )
    chunk = train_config.ROLLOUT_CHUNK_MOVES
    lbatch = train_config.BATCH_SIZE
    targets = [
        (f"self_play_chunk/t{chunk}", lambda: engine.analyze_chunk(chunk)),
        (f"learner_step/b{lbatch}", lambda: trainer.analyze_step(lbatch)),
        (
            f"learner_fused/k{fused_k}",
            lambda: trainer.analyze_steps(fused_k, lbatch),
        ),
    ]
    if megastep:
        from ..rl.megastep import MegastepRunner

        grid_shape = (
            model_config.GRID_INPUT_CHANNELS,
            env_config.ROWS,
            env_config.COLS,
        )
        mega_dp = sharded_megastep_dp(train_config)
        if mega_dp > 1:
            # dp-sharded family: analyze the program a multi-device run
            # will actually dispatch, with dedicated mesh-built
            # components mirroring training/setup.py's wiring. The
            # ring record carries shards=dp so `compose_budget`
            # charges each device its cap_local slice, not the global
            # capacity.
            from ..config.mesh_config import MeshConfig
            from ..rl.sharded_device_buffer import (
                ShardedDeviceReplayBuffer,
            )

            mesh = MeshConfig(DP_SIZE=mega_dp).build_mesh()
            mega_engine = SelfPlayEngine(
                env, extractor, net, mcts_config, train_config,
                seed=0, mesh=mesh,
            )
            mega_trainer = Trainer(net, train_config, mesh=mesh)
            mega_buffer = ShardedDeviceReplayBuffer(
                train_config,
                grid_shape=grid_shape,
                other_dim=extractor.other_dim,
                action_dim=env_config.action_dim,
                mesh=mesh,
            )
            records.append(mega_buffer.memory_record())
            runner = MegastepRunner(
                mega_engine, mega_trainer, mega_buffer, train_config
            )
            targets.append(
                (
                    f"megastep/dp{mega_dp}_t{chunk}_k{fused_k}",
                    lambda: runner.analyze_megastep(chunk, fused_k),
                )
            )
        else:
            from ..rl.device_buffer import DeviceReplayBuffer

            mega_buffer = DeviceReplayBuffer(
                train_config,
                grid_shape=grid_shape,
                other_dim=extractor.other_dim,
                action_dim=env_config.action_dim,
            )
            runner = MegastepRunner(
                engine, trainer, mega_buffer, train_config
            )
            targets.append(
                (
                    f"megastep/t{chunk}_k{fused_k}",
                    lambda: runner.analyze_megastep(chunk, fused_k),
                )
            )
    if serve:
        from ..serving import PolicyService, serve_program_name

        slots = int(serve_batch or train_config.SELF_PLAY_BATCH_SIZE)
        serve_gumbel = (
            getattr(mcts_config, "root_selection", "puct") == "gumbel"
        )
        if serve_gumbel:
            from ..mcts import GumbelMCTS

            serve_mcts = GumbelMCTS(
                env, extractor, net.model, mcts_config, net.support,
                exploit=True,
            )
        else:
            serve_mcts = engine.mcts
        service = PolicyService(
            env, extractor, net, serve_mcts, slots=slots,
            use_gumbel=serve_gumbel, ladder=serve_buckets,
        )
        # One analysis per ladder rung (a fixed-shape service is a
        # one-rung ladder): the micro-batcher dispatches whichever
        # rung fits demand, so the budget must cover all of them.
        # persist=True: each rung's sidecar survives into the cache
        # dir so a later `cli serve` pre-flight reads it without
        # re-lowering.
        for rung in service.ladder.rungs:
            targets.append(
                (
                    serve_program_name(rung),
                    lambda r=rung: service.analyze(persist=True, rung=r),
                )
            )
    if programs:
        targets = [
            (label, fn)
            for label, fn in targets
            if any(p in label for p in programs)
        ]
    for label, fn in targets:
        t0 = time.time()
        try:
            rec = fn()
        except Exception as exc:  # one unanalyzable program != no report
            logger.warning("fit: %s analysis failed (%s)", label, exc)
            rec = None
        if rec is not None:
            records.append(rec)
            say(
                f"fit: {label}: args {fmt_bytes(rec['bytes'].get('argument'))}"
                f" temp {fmt_bytes(rec['bytes'].get('temp'))}"
                f" ({time.time() - t0:.1f}s)"
            )
        else:
            say(f"fit: {label}: no memory analysis available")
    return {"records": records, "budget": compose_budget(records)}
