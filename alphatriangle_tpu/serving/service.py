"""Continuous-batching policy service over the lockstep wave search.

`PolicyService` is the inference front end the ROADMAP's
millions-of-users scenario needs: many concurrent game sessions
(humans playing the current net, arena/eval traffic, a league)
multiplexed onto ONE compiled `BatchedMCTS.search` dispatch shape.
Requests queue between dispatches; each dispatch serves every pending
session in one device program over the full slot array (idle/free
lanes ride along as frozen padding — see serving/session.py for the
lane-isolation argument), the Podracer acting-path pattern
(arXiv:2104.06272) applied to serving.

Composition of existing training plumbing, per the ROADMAP item:

- **AOT warm start** — the search program is wrapped in the compile
  cache as `serve/b<B>` (`cli warm` precompiles it alongside a preset's
  training programs; a warmed `cli serve` starts answering in ~0.5 s instead of
  after a flagship-scale search compile).
- **OOM pre-flight** — `analyze()` AOT-analyzes the serve program's
  HBM footprint without executing it (`estimate_fit(serve=True)`,
  `cli fit --serve`), and persists the `.mem.json` sidecar.
- **Latency SLOs** — per-request queue-wait and move latency land in
  the run's metrics ledger every tick (`serve_*` fields on the
  `kind: "util"` records), so `cli perf` summarizes p50/p95 per-move
  latency and `cli compare` gates regressions.
- **Liveness** — `cli serve` runs a `health.json` heartbeat + stall
  watchdog through the same `RunTelemetry` facade training uses.
- **Hot weight reload** — `reload_weights` swaps `net.variables`
  between dispatches; the compiled search reads variables as an input,
  so a reload never recompiles (the property `greedy_mcts_policy`
  established and test_serving counter-pins).
"""

import logging
import os
import threading
import time
from collections import deque

import numpy as np

from ..mcts.helpers import select_root_actions
from ..telemetry.device_stats import (
    beacon_signature,
    beacons_armed,
    device_stats_signature,
    fold_search_stats,
    merge_search_folds,
    note_dispatch,
)
from ..telemetry.flight import flight_span
from .session import SessionSlots

logger = logging.getLogger(__name__)


def serve_program_name(slots: int) -> str:
    """The compile-cache name of the serve search program for one slot
    shape — `serve/b<B>`, the spelling `cli warm` reports."""
    return f"serve/b{int(slots)}"


def _pct(values: list, q: float) -> "float | None":
    vals = sorted(v for v in values if isinstance(v, (int, float)))
    if not vals:
        return None
    idx = min(len(vals) - 1, max(0, round(q * (len(vals) - 1))))
    return float(vals[idx])


class PolicyService:
    """Request queue + micro-batcher over one `SessionSlots` array.

    Single-dispatcher model: any thread may open/close sessions and
    enqueue move requests (lock-guarded, O(1)); one caller drives
    `dispatch()` in a loop. Admission beyond the slot count raises —
    back-pressure belongs to the caller (the load generator queues,
    an HTTP front end would 503).

    With a `ladder` (serving/buckets.py), the service becomes a
    RUNG-SWITCHING micro-batcher: the compiled dispatch shape walks UP
    one rung when windowed batch fill sustains at/above `high_water`
    (or immediately when an admission would not fit the current shape
    — zero lost requests), and DOWN when fill sustains at/below
    `low_water` and the live sessions fit the smaller shape — the
    inverse of the fleet quarantine's forced walk-down on the same
    ladder. Every rung is AOT-warmed by `warm()` up front, so a switch
    between dispatches never compiles (test_serving pins the
    compile-cache event count across a storm). A switch migrates the
    live sessions lowest-old-slot-first (SessionSlots.migrate), clears
    every carried subtree (`_carry_ok`; reuse never crosses bucket
    shapes), and keeps the one-dispatch-per-wave contract untouched."""

    def __init__(
        self,
        env,
        extractor,
        net,
        mcts,
        slots: int,
        use_gumbel: bool = False,
        telemetry=None,
        rng_seed: int = 0,
        pad_seed: int = 0,
        clock=time.monotonic,
        ladder=None,
        high_water: float = 0.85,
        low_water: float = 0.25,
        sustain: int = 3,
    ):
        import jax

        from ..compile_cache import config_digest, get_compile_cache
        from .buckets import BucketLadder

        self.env = env
        self.extractor = extractor
        self.net = net
        self.mcts = mcts
        self.use_gumbel = bool(use_gumbel)
        self.telemetry = telemetry
        # Flight recorder rides the run telemetry (telemetry/flight.py);
        # None when serving without telemetry (tests, warm-only paths).
        self.flight = getattr(telemetry, "flight", None)
        # Optional trajectory sink (league/emitter.py): when set, every
        # dispatch hands it the pre-step states + search output so
        # served games become training data. None = serve-only.
        self.emitter = None
        self._clock = clock
        # The serve-shape ladder: None = the degenerate single-rung
        # ladder (fixed-shape serving, the historical behavior, bit
        # for bit). `slots` is the starting rung and is always a rung.
        if ladder is None:
            self.ladder = BucketLadder.single(slots)
        else:
            self.ladder = BucketLadder.from_spec(ladder, base=slots)
        self.high_water = float(high_water)
        self.low_water = float(low_water)
        self.sustain = max(1, int(sustain))
        self.rung_switches = 0
        # Recent per-dispatch fills driving walk decisions (distinct
        # from the tick-drained `_win_fill` SLO window).
        self._ladder_fill: deque[float] = deque(maxlen=self.sustain)
        self._pad_seed = int(pad_seed)
        self.sessions = SessionSlots(env, slots, pad_seed=pad_seed)
        # The serve program: the search jit wrapped for AOT executable
        # caching. The digest covers everything that shapes the program
        # but is invisible in its avals (sim budget, net architecture,
        # board, and the search-class/exploit mode, which swap _search
        # bodies entirely).
        extra = (
            config_digest(mcts.config, extractor.model_config, env.cfg)
            + (
                f"|{type(mcts).__name__}"
                f"|exploit{int(getattr(mcts, 'exploit', False))}"
            )
            + device_stats_signature()
            + beacon_signature()
        )
        # Serve-wave stat-packs (telemetry/device_stats.py): snapshot
        # the process-global here — it must match what `mcts` captured
        # at construction, since `out.stats` exists iff the search was
        # built with stats on.
        self._device_stats = bool(getattr(mcts, "device_stats", False))
        self._win_device_stats: list[dict] = []
        self._last_serve_ds: "dict | None" = None
        # Subtree reuse (MCTSConfig.tree_reuse): each lane carries its
        # promoted search tree across dispatches, device-resident. The
        # serve program then fuses search + in-program action argmax +
        # root promotion into the same single dispatch; the host keeps
        # a per-lane validity mask (`_carry_ok`) and clears lanes on
        # churn (admit/retire), episode end, weight reload (carried
        # priors/visits came from the old net) and any lane the wave's
        # promotion advanced but the masked step did not (unserved
        # lanes must not inherit a tree for a move they never played).
        self._tree_reuse = bool(getattr(mcts.config, "tree_reuse", False))
        self._carry_ok = np.zeros(slots, dtype=bool)
        self._carried = None
        if self._tree_reuse:
            import jax.numpy as jnp

            def _serve_search_reuse(variables, states, rng, carried, ok):
                eff = carried.replace(valid=carried.valid & ok)
                out, tree, reused = mcts._search_carried(
                    variables, states, rng, eff
                )
                counts = out.visit_counts
                # Device replica of select_root_actions' PUCT rule
                # (helpers.py: argmax of visits, 0 on zero-visit rows)
                # — same values the host selects, so the promotion
                # follows exactly the action the masked step plays.
                actions = jnp.where(
                    counts.sum(axis=-1) > 0,
                    jnp.argmax(counts, axis=-1).astype(jnp.int32),
                    0,
                )
                return out, mcts.promote(tree, actions), reused

            self._carried = mcts.zero_carried(self.sessions.states)
            self._search_fn = jax.jit(_serve_search_reuse)
        else:
            self._search_fn = mcts.search
        # One CachedProgram per ladder rung, all over the SAME jitted
        # function (batch shape is an aval, not a closure): the cache
        # names them serve/b<rung> so flight spans / warm rows / memory
        # sidecars attribute per shape, and a rung switch just swaps
        # which program the dispatch calls — zero tracing, zero
        # recompiles once warmed.
        self._cache = get_compile_cache()
        self._extra = extra
        self._serialize_artifacts = not beacons_armed()
        self._programs: dict[int, object] = {}
        for rung in self.ladder.rungs:
            self._programs[rung] = self._cache.wrap(
                serve_program_name(rung),
                self._search_fn,
                extra=extra,
                serialize=self._serialize_artifacts,
            )
        self._base_rng = jax.random.PRNGKey(rng_seed)
        self._lock = threading.RLock()
        self._queue: deque[int] = deque()  # sids with a pending request
        # sid -> trace-context fields of the request currently driving
        # that session (telemetry/tracectx.py): the replica front end
        # registers them so the serve/b<B> flight bracket can name the
        # exact trace_ids each device wave served.
        self._session_trace: dict[int, dict] = {}
        # Cumulative counters (UtilizationMeter folds deltas).
        self.dispatch_count = 0
        self.requests_total = 0
        self.episodes_done_total = 0
        self.simulations_total = 0
        # Root visits inherited from carried subtrees across all waves
        # (0 unless tree_reuse): simulations + reused = leaf-equivalent
        # search effort (leaf-evals/s in telemetry/perf.py).
        self.reused_visits_total = 0
        self.weight_reloads = 0
        # Per-tick windows (drained by tick()).
        self._win_wait_ms: list[float] = []
        self._win_lat_ms: list[float] = []
        self._win_batch_ms: list[float] = []
        self._win_fill: list[float] = []
        self._win_requests = 0
        self._last_tick_t = clock()
        # (weights_version, reload count, inference-cast variables)
        # memo for _serve_variables (nn/precision.py).
        self._cast_variables: "tuple | None" = None

    # --- warm start / pre-flight --------------------------------------

    @property
    def _search(self):
        """The compiled program for the CURRENT rung (dispatch shape)."""
        return self._programs[self.sessions.slots]

    @property
    def max_slots(self) -> int:
        """The most sessions this service can ever hold (the ladder's
        top rung) — admission planners size against this, not the
        current shape (loadgen)."""
        return self.ladder.max_rung

    def _serve_variables(self):
        """The variables the serve dispatch reads: the net's, cast to
        the inference precision policy (nn/precision.py). Identity
        under f32; under bf16 the cast copy is memoized per
        (weights version, reload count) so steady-state dispatches
        reuse one device-resident copy and a hot reload re-casts."""
        from ..nn.precision import cast_params_for_inference, inference_dtype

        import jax.numpy as jnp

        cfg = self.extractor.model_config
        if inference_dtype(cfg) == jnp.float32:
            return self.net.variables
        key = (self.net.weights_version, self.weight_reloads)
        if self._cast_variables is not None:
            cached_key, cast = self._cast_variables
            if cached_key == key:
                return cast
        cast = cast_params_for_inference(self.net.variables, cfg)
        self._cast_variables = (key, cast)
        return cast

    def _sample_args_for(self, rung: int):
        """Dispatch-identical argument avals at one rung's shape: the
        current slot array when `rung` is the live shape, a frozen
        padding array otherwise (shapes/dtypes are all that matter —
        warm/analyze never execute)."""
        import jax
        import jax.numpy as jnp

        rung = int(rung)
        if rung == self.sessions.slots:
            states = self.sessions.states
            carried = self._carried
        else:
            keys = jax.random.split(
                jax.random.PRNGKey(self._pad_seed), rung
            )
            base = self.env.reset_batch(keys)
            states = base.replace(
                done=jnp.ones((rung,), dtype=base.done.dtype)
            )
            carried = (
                self.mcts.zero_carried(states) if self._tree_reuse else None
            )
        args = (self._serve_variables(), states, jax.random.PRNGKey(0))
        if self._tree_reuse:
            args += (carried, jnp.zeros(rung, dtype=bool))
        return args

    def _sample_args(self):
        return self._sample_args_for(self.sessions.slots)

    def warm(self) -> bool:
        """AOT-ready the serve program for EVERY ladder rung
        (deserialize or compile+serialize, never execute) — `cli
        warm`'s serve rows and `cli serve`'s startup both come through
        here. Warming every rung up front is what makes a mid-stream
        rung switch zero-recompile. True iff every rung is AOT-ready."""
        ok = True
        for rung in self.ladder.rungs:
            ok = self.warm_rung(rung) and ok
        return ok

    def warm_rung(self, rung: int) -> bool:
        """AOT-ready one rung's serve program (warm.py's per-rung
        target rows)."""
        return self._programs[int(rung)].warm(*self._sample_args_for(rung))

    def analyze(
        self, persist: bool = False, rung: "int | None" = None
    ) -> "dict | None":
        """Memory record for the serve program at one rung (default:
        the current shape; AOT analysis, never executed;
        telemetry/memory.py). `persist=True` writes the `.mem.json`
        sidecar beside the executable artifact."""
        r = self.sessions.slots if rung is None else int(rung)
        return self._programs[r].analyze(
            *self._sample_args_for(r), persist=persist
        )

    # --- the bucket ladder (serving/buckets.py) -----------------------

    def _switch_rung(self, new_rung: int, reason: str) -> None:
        """Swap the compiled dispatch shape between dispatches: migrate
        live sessions into a `new_rung`-lane slot array (identity-
        preserving, lowest-old-slot-first), invalidate every carried
        subtree (a promoted tree's static shape belongs to its bucket;
        reuse never crosses shapes), and reset the walk window. Caller
        holds the lock."""
        old = self.sessions.slots
        if new_rung == old:
            return
        self.sessions = self.sessions.migrate(
            new_rung, pad_seed=self._pad_seed
        )
        self._carry_ok = np.zeros(new_rung, dtype=bool)
        if self._tree_reuse:
            self._carried = self.mcts.zero_carried(self.sessions.states)
        self._ladder_fill.clear()
        self.rung_switches += 1
        logger.info(
            "serve: rung switch b%d -> b%d (%s; live=%d queue=%d)",
            old,
            new_rung,
            reason,
            self.sessions.live_count,
            self.queue_depth,
        )

    def _maybe_walk(self) -> None:
        """The windowed walk decision, taken between dispatches (caller
        holds the lock): up when fill sustains at/above the high-water
        mark, down when it sustains at/below the low-water mark AND the
        live sessions fit the smaller shape. Mirrors the fleet
        quarantine's walk-down on the same ladder — quarantine is this
        move, forced."""
        if len(self._ladder_fill) < self.sustain:
            return
        fill = sum(self._ladder_fill) / len(self._ladder_fill)
        rung = self.sessions.slots
        if fill >= self.high_water and rung < self.ladder.max_rung:
            self._switch_rung(
                self.ladder.up(rung), f"fill {fill:.2f} >= high-water"
            )
        elif fill <= self.low_water and rung > self.ladder.min_rung:
            lower = self.ladder.down(rung)
            if self.sessions.live_count <= lower:
                self._switch_rung(
                    lower, f"fill {fill:.2f} <= low-water"
                )

    # --- session lifecycle --------------------------------------------

    def _grow_for(self, needed: int) -> None:
        """Demand-driven walk-up: when an admission would overflow the
        current shape but fits a higher rung, switch BEFORE admitting —
        a burst is never shed while the ladder has headroom (caller
        holds the lock)."""
        demand = self.sessions.live_count + int(needed)
        if self.sessions.free_count >= needed or demand > self.ladder.max_rung:
            return
        target = self.ladder.rung_for(demand)
        if target > self.sessions.slots:
            self._switch_rung(target, f"admission demand {demand}")

    def open_session(self, reset_key=None, seed: "int | None" = None):
        """Admit one session (fresh game). Returns the Session handle.
        Walks the ladder up when the current shape is full but a
        higher rung exists; raises RuntimeError when every slot of the
        TOP rung is occupied."""
        import jax

        if reset_key is None:
            reset_key = jax.random.PRNGKey(0 if seed is None else seed)
        with self._lock:
            self._grow_for(1)
            s = self.sessions.admit(reset_key)
            self._carry_ok[s.slot] = False
            return s

    def open_sessions(self, reset_keys) -> list:
        with self._lock:
            self._grow_for(len(reset_keys))
            admitted = self.sessions.admit_many(reset_keys)
            for s in admitted:
                self._carry_ok[s.slot] = False
            return admitted

    def set_session_trace(self, sid: int, fields: "dict | None") -> None:
        """Attach (or clear) the trace-context fields of the request
        currently driving session `sid` — stamped onto the serve
        dispatch bracket and the session's result dicts."""
        with self._lock:
            if fields:
                self._session_trace[sid] = dict(fields)
            else:
                self._session_trace.pop(sid, None)

    def close_session(self, sid: int) -> dict:
        with self._lock:
            s = self.sessions.session(sid)
            s.pending_since = None
            self._session_trace.pop(sid, None)
            self._carry_ok[s.slot] = False
            summary = self.sessions.retire(sid)
            if sid in self._queue:
                self._queue.remove(sid)
            if self.emitter is not None:
                try:
                    self.emitter.on_session_close(sid, summary)
                except Exception:
                    logger.exception(
                        "trajectory emitter failed closing session %d", sid
                    )
            return summary

    def request_move(self, sid: int) -> None:
        """Enqueue one move request; a session holds at most one
        outstanding request (it is a lockstep game, not a stream)."""
        with self._lock:
            s = self.sessions.session(sid)
            if s.pending_since is not None:
                raise RuntimeError(f"session {sid} already has a pending move")
            s.pending_since = self._clock()
            self._queue.append(sid)

    @property
    def queue_depth(self) -> int:
        return len(self._queue)

    # --- weights --------------------------------------------------------

    def reload_weights(self, variables=None) -> int:
        """Hot-swap the served net between dispatches (no recompile:
        variables are a program input). `variables=None` records a
        reload performed externally — `Trainer.sync_to_network()`
        already installs a donation-safe copy into the net, and the
        service reads `net.variables` live. Returns the reload count."""
        with self._lock:
            if variables is not None:
                self.net.set_weights(variables)
            self.weight_reloads += 1
            # Carried subtrees were searched under the old net: their
            # interior priors/values no longer match what a fresh
            # search would compute. Reload churn resets every lane to
            # fresh-root (the documented cost of reuse under high
            # reload rates, docs/KERNELS.md).
            self._carry_ok[:] = False
            return self.weight_reloads

    # --- the micro-batch dispatch ---------------------------------------

    def dispatch(self, rng=None) -> list[dict]:
        """Serve every pending request in ONE batched search + step.

        Returns one result dict per served request: action, reward,
        done, score, queue_wait_ms, latency_ms. Empty list when the
        queue is empty (callers idle-wait)."""
        import jax

        with self._lock:
            if not self._queue:
                return []
            served: list = []
            mask = np.zeros(self.sessions.slots, dtype=bool)
            while self._queue:
                s = self.sessions.session(self._queue.popleft())
                mask[s.slot] = True
                served.append(s)
            t0 = self._clock()
            if rng is None:
                rng = jax.random.fold_in(self._base_rng, self.dispatch_count)
            # The trace_ids this wave serves (deduped, order-stable):
            # the flight intent/seal names them so an unsealed serve
            # intent — or the merged fleet timeline — identifies the
            # routed requests that were on the chip.
            wave_trace_ids = list(
                dict.fromkeys(
                    tid
                    for s in served
                    for tid in [
                        self._session_trace.get(s.sid, {}).get("trace_id")
                    ]
                    if tid
                )
            )
            with flight_span(
                self.flight,
                "serve",
                serve_program_name(self.sessions.slots),
                avals=f"b{len(served)}",
                trace=(
                    {"trace_ids": wave_trace_ids} if wave_trace_ids else None
                ),
            ):
                # Chaos hook (docs/ROBUSTNESS.md): env-gated so an
                # unarmed service never imports the fault module. Fires
                # INSIDE the flight bracket — a hang-serve leaves an
                # unsealed serve/b<B> intent (the probe's evidence), a
                # crash-serve seals ok:false and surfaces to the caller.
                if os.environ.get("ALPHATRIANGLE_FAULTS"):
                    from ..supervise.faults import fault_point

                    fault_point(
                        "serve-dispatch",
                        self.dispatch_count,
                        flight_path=getattr(self.flight, "path", None),
                    )
                note_dispatch(serve_program_name(self.sessions.slots))
                reused_d = None
                if self._tree_reuse:
                    import jax.numpy as jnp

                    # Same single dispatch: search seeded with the
                    # carried lanes + fused in-program promotion.
                    out, self._carried, reused_d = self._search(
                        self._serve_variables(),
                        self.sessions.states,
                        rng,
                        self._carried,
                        jnp.asarray(self._carry_ok),
                    )
                else:
                    out = self._search(
                        self._serve_variables(), self.sessions.states, rng
                    )
                actions = select_root_actions(out, self.use_gumbel)
                # The positions the search ran on; the pytree stays
                # valid after step() installs the successor states.
                pre_states = self.sessions.states
                rewards, dones = self.sessions.step(actions, mask)
                # Response materialization: the host sync IS the
                # product here (clients need their move) — ONE fetch
                # per dispatch for all result arrays, not one each.
                fetch = (rewards, dones, self.sessions.states.score)
                if reused_d is not None:
                    fetch += (reused_d,)
                # Serve-wave stat-pack rides the SAME fetch (appended
                # last so the positional `host[3]` reuse access below
                # is untouched) — no extra device_get.
                ds_dev = out.stats if self._device_stats else None
                if ds_dev is not None:
                    fetch += (ds_dev,)
                host = jax.device_get(fetch)  # graftlint: allow(host-sync-in-hot-path) the one deliberate response fetch per dispatch
                rewards_np, dones_np, scores_np = host[:3]
                if ds_dev is not None:
                    ds_fold = fold_search_stats(host[-1])
                    if ds_fold:
                        self._win_device_stats.append(ds_fold)
            t1 = self._clock()

            if self.emitter is not None:
                try:
                    self.emitter.on_dispatch(
                        pre_states,
                        out,
                        served,
                        rewards_np,
                        dones_np,
                        self.weight_reloads,
                    )
                except Exception:
                    logger.exception(
                        "trajectory emitter failed on dispatch %d; "
                        "serving continues",
                        self.dispatch_count,
                    )

            batch_ms = (t1 - t0) * 1e3
            results = []
            for s in served:
                wait_ms = (t0 - s.pending_since) * 1e3
                lat_ms = (t1 - s.pending_since) * 1e3
                s.pending_since = None
                done = bool(dones_np[s.slot])
                if done and not s.done:
                    s.done = True
                    self.episodes_done_total += 1
                s.score = float(scores_np[s.slot])
                result = {
                    "sid": s.sid,
                    "slot": s.slot,
                    "move": s.moves,
                    "action": int(actions[s.slot]),
                    "reward": float(rewards_np[s.slot]),
                    "done": done,
                    "score": s.score,
                    "queue_wait_ms": wait_ms,
                    "latency_ms": lat_ms,
                }
                strace = self._session_trace.get(s.sid)
                if strace and strace.get("trace_id"):
                    result["trace_id"] = strace["trace_id"]
                results.append(result)
                self._win_wait_ms.append(wait_ms)
                self._win_lat_ms.append(lat_ms)
            self.dispatch_count += 1
            self.requests_total += len(results)
            # Device work is the FULL slot array per wave regardless of
            # fill — honest sims accounting for MFU.
            self.simulations_total += (
                self.sessions.slots * self.mcts.config.max_simulations
            )
            if reused_d is not None:
                # Visits the wave inherited instead of re-searching
                # (same full-array accounting as simulations_total).
                self.reused_visits_total += int(host[3].sum())
                # Next wave may reuse only lanes this wave actually
                # advanced (served + stepped) and that didn't finish;
                # unserved lanes were promoted for a move never played.
                self._carry_ok = mask & ~np.asarray(dones_np, dtype=bool)
            self._win_requests += len(results)
            self._win_batch_ms.append(batch_ms)
            fill = len(results) / self.sessions.slots
            self._win_fill.append(fill)
            self._ladder_fill.append(fill)
            # Walk decision BETWEEN dispatches: this wave ran at the
            # old shape; the next one may run at the new.
            self._maybe_walk()
            if self.telemetry is not None:
                self.telemetry.on_rollout(
                    experiences=len(results),
                    episodes=sum(1 for r in results if r["done"]),
                )
            return results

    # --- SLO accounting ---------------------------------------------------

    def serve_stats(self, drain: bool = True) -> dict:
        """The `serve_*` fields for one utilization tick: current
        occupancy + this window's request percentiles. `drain` resets
        the window (the tick cadence).

        Snapshot + reset happen under the service lock — dispatch holds
        the same (reentrant) lock while appending window records, so a
        drain landing mid-dispatch can no longer read the lists and
        then reset them around a concurrent append (the lost-request
        race test_serving pins with a concurrent drainer)."""
        with self._lock:
            return self._serve_stats_locked(drain)

    def _serve_stats_locked(self, drain: bool) -> dict:
        now = self._clock()
        dt = max(1e-9, now - self._last_tick_t)
        snap = self.sessions.snapshot()
        stats = {
            "serve_slots": snap["slots"],
            # The current ladder rung + instantaneous fill gauges
            # (ledger -> Prometheus -> cli perf): serve_bucket tracks
            # the micro-batcher's compiled shape, serve_fill the most
            # recent dispatch's occupancy at that shape.
            "serve_bucket": snap["slots"],
            "serve_fill": (
                round(float(self._win_fill[-1]), 4)
                if self._win_fill
                else None
            ),
            "serve_rung_switches": self.rung_switches,
            "serve_sessions": snap["live"],
            "serve_sessions_admitted": snap["admitted_total"],
            "serve_sessions_retired": snap["retired_total"],
            "serve_queue_depth": self.queue_depth,
            "serve_requests_total": self.requests_total,
            "serve_window_requests": self._win_requests,
            "serve_requests_per_sec": round(self._win_requests / dt, 2),
            "serve_batch_fill": (
                round(float(np.mean(self._win_fill)), 4)
                if self._win_fill
                else None
            ),
            "serve_batch_ms_p50": _pct(self._win_batch_ms, 0.50),
            "serve_batch_ms_p95": _pct(self._win_batch_ms, 0.95),
            "serve_queue_wait_ms_p50": _pct(self._win_wait_ms, 0.50),
            "serve_queue_wait_ms_p95": _pct(self._win_wait_ms, 0.95),
            "serve_move_latency_ms_p50": _pct(self._win_lat_ms, 0.50),
            "serve_move_latency_ms_p95": _pct(self._win_lat_ms, 0.95),
            "serve_weight_reloads": self.weight_reloads,
        }
        if drain:
            # Merge this window's per-wave search folds into one serve
            # leg for tick() (device-stats plane; None when the feature
            # is off or no wave ran this window).
            self._last_serve_ds = merge_search_folds(self._win_device_stats)
            self._win_wait_ms = []
            self._win_lat_ms = []
            self._win_batch_ms = []
            self._win_fill = []
            self._win_requests = 0
            self._win_device_stats = []
            self._last_tick_t = now
        return stats

    def tick(self) -> "dict | None":
        """One telemetry tick: derive + ledger a utilization record
        carrying the serve SLO fields, update the heartbeat. Returns
        the record (None on the baseline tick or without telemetry)."""
        if self.telemetry is None:
            return None
        stats = self.serve_stats(drain=True)
        extra = {k: v for k, v in stats.items() if v is not None}
        serve_ds = getattr(self, "_last_serve_ds", None)
        if serve_ds:
            # Gauge fields for metrics.prom (ledger._PROM_HELP) ride
            # the util record; the full leg lands as a device_stats
            # ledger record below.
            if serve_ds.get("root_entropy") is not None:
                extra["root_visit_entropy"] = serve_ds["root_entropy"]
            if serve_ds.get("occupancy") is not None:
                extra["tree_occupancy"] = serve_ds["occupancy"]
            extra["beacons_armed"] = int(beacons_armed())
        flight = getattr(self.telemetry, "flight", None)
        dispatch_wall = getattr(flight, "sealed_wall_seconds", None)
        record = self.telemetry.on_util_tick(
            step=self.dispatch_count,
            episodes=self.episodes_done_total,
            experiences=self.requests_total,
            simulations=self.simulations_total,
            reused_visits=self.reused_visits_total,
            buffer_size=self.queue_depth,
            dispatch_wall_s=dispatch_wall,
            extra=extra,
        )
        if serve_ds and hasattr(self.telemetry, "record_device_stats"):
            self.telemetry.record_device_stats(
                self.dispatch_count,
                serve=serve_ds,
                program=serve_program_name(self.sessions.slots),
            )
            self._last_serve_ds = None
        self.telemetry.on_tick(
            self.dispatch_count, buffer_size=self.queue_depth
        )
        return record


def build_serve_telemetry(
    run_dir,
    run_name: str,
    env_config,
    model_config,
    telemetry_config=None,
):
    """A RunTelemetry for a serve run: same heartbeat/watchdog/ledger
    stack as training, with a meter whose FLOPs model is the serve
    path's (network forwards only — there is no learner here)."""
    import jax

    from ..telemetry import RunTelemetry
    from ..telemetry.perf import UtilizationMeter
    from ..utils.flops import forward_flops

    device = jax.devices()[0]
    meter = UtilizationMeter(
        forward_flops=forward_flops(
            model_config, env_config, env_config.action_dim
        ),
        train_step_flops=0,
        device_kind=str(getattr(device, "device_kind", device.platform)),
        buffer_capacity=0,
    )
    return RunTelemetry(
        telemetry_config,
        run_dir=run_dir,
        run_name=run_name,
        perf=meter,
    )
