"""Simulated concurrent-session load for the policy service.

The serving smoke story (`cli serve --smoke`, `make serve-smoke`):
drive N concurrent simulated game sessions
through the continuous batcher with real churn — sessions retire as
their games end and replacements are admitted mid-run, exactly the
fluctuating-load shape the slot-array + padding design exists for.
Deterministic given (seed, slot count, traffic shape): reset keys come
from a counted PRNG chain and admission is lowest-free-slot, so smoke
runs are reproducible.
"""

import logging
import time

logger = logging.getLogger(__name__)


def run_simulated_load(
    service,
    total_sessions: int,
    concurrency: "int | None" = None,
    max_moves: int = 200,
    seed: int = 0,
    tick_every: int = 8,
    max_dispatches: "int | None" = None,
    reload_hook=None,
    progress=None,
    clock=time.monotonic,
) -> dict:
    """Serve `total_sessions` games end to end, keeping up to
    `concurrency` live at once (default: every slot).

    `reload_hook(service, dispatch_count)`: optional between-dispatch
    callback — `cli serve` uses it to poll checkpoints for hot weight
    reloads; tests use it to swap weights mid-stream.
    `max_dispatches` is a runaway bound (a session that never finishes
    is truncated by `max_moves` per session anyway).
    Returns the run's summary stats.
    """
    import jax

    # Concurrency is bounded by the most sessions the service can EVER
    # hold — the ladder's top rung under a micro-batching service
    # (serving/buckets.py), the fixed slot count otherwise. Asking for
    # more than the current shape is exactly the sustained-demand
    # signal that drives the ladder walk-up.
    limit = int(getattr(service, "max_slots", service.sessions.slots))
    concurrency = min(concurrency or service.sessions.slots, limit)
    if concurrency < 1:
        raise ValueError("concurrency must be >= 1")
    t_start = clock()
    key_counter = 0

    def next_keys(n: int):
        nonlocal key_counter
        keys = [
            jax.random.fold_in(jax.random.PRNGKey(seed), key_counter + i)
            for i in range(n)
        ]
        key_counter += n
        import jax.numpy as jnp

        return jnp.stack(keys)

    def admit_up_to_target() -> int:
        want = min(
            concurrency - service.sessions.live_count,
            total_sessions - service.sessions.admitted_total,
            service.sessions.free_count,
        )
        if want > 0:
            for s in service.open_sessions(next_keys(want)):
                service.request_move(s.sid)
        return max(0, want)

    admit_up_to_target()
    dispatches = 0
    served_moves = 0
    retired = []
    while service.sessions.live_count > 0:
        results = service.dispatch()
        dispatches += 1
        served_moves += len(results)
        for r in results:
            finished = r["done"] or r["move"] >= max_moves
            if finished:
                retired.append(service.close_session(r["sid"]))
            else:
                service.request_move(r["sid"])
        admit_up_to_target()
        if reload_hook is not None:
            reload_hook(service, dispatches)
        if dispatches % tick_every == 0:
            service.tick()
            if progress is not None:
                progress(
                    f"serve: {len(retired)}/{total_sessions} sessions "
                    f"done, {served_moves} moves, "
                    f"{service.sessions.live_count} live, "
                    f"dispatch {dispatches}"
                )
        if max_dispatches is not None and dispatches >= max_dispatches:
            logger.warning(
                "loadgen: max_dispatches=%d reached with %d live "
                "session(s); truncating",
                max_dispatches,
                service.sessions.live_count,
            )
            for s in list(service.sessions.live_sessions()):
                retired.append(service.close_session(s.sid))
            break
    service.tick()
    elapsed = clock() - t_start
    scores = [r["score"] for r in retired]
    return {
        "sessions_served": len(retired),
        "sessions_finished": sum(1 for r in retired if r["done"]),
        "moves_served": served_moves,
        "dispatches": dispatches,
        "seconds": round(elapsed, 2),
        "moves_per_sec": round(served_moves / max(elapsed, 1e-9), 1),
        "mean_score": (
            round(float(sum(scores)) / len(scores), 2) if scores else None
        ),
        "max_concurrency": concurrency,
        "weight_reloads": service.weight_reloads,
    }
