"""Profiling subsystem: device traces + per-phase host timers.

TPU-native equivalent of the reference's worker profiling
(`alphatriangle/rl/self_play/worker.py:99-104,549-566` cProfile dumps +
`time.monotonic()` span logging) and its offline analyzer
(`alphatriangle/analyze_profiles.py:41-78`):

- `jax.profiler` trace of a bounded window of loop iterations (the
  XLA/TPU story the reference's cProfile cannot see) written to
  `runs/<run>/profile_data/`, viewable in TensorBoard's profile plugin.
- `PhaseTimers`: per-phase wall-clock accumulators (rollout / sample /
  train / checkpoint) kept for the WHOLE run, exported as metrics each
  stats tick and dumped to `phase_timers.json` at exit.
- `analyze_profile_dir`: prints a per-phase summary table from the
  dump, replacing the reference's pstats top-N listing, and for every
  device trace the device seconds per named phase of each program
  (`telemetry/phases.py`), read with `jax.profiler.ProfileData`.
"""

import bisect
import functools
import json
import logging
import re
import threading
import time
from collections import defaultdict
from contextlib import contextmanager
from pathlib import Path

from .telemetry.phases import PHASES
from .telemetry.tracer import ANNOTATION_PREFIX

logger = logging.getLogger(__name__)

OP_NAMES_FILENAME = "op_names.json"
OTHER = "other"


class PhaseTimers:
    """Accumulates wall-clock seconds per named phase.

    Thread-safe: multiple rollout-producer threads time the same
    "rollout" phase concurrently (training/loop.py), so the
    accumulation is locked (a bare `dict[k] += dt` would lose
    increments across interleaved read-modify-writes).
    """

    def __init__(self) -> None:
        self._total: dict[str, float] = defaultdict(float)
        self._count: dict[str, int] = defaultdict(int)
        self._lock = threading.Lock()

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with self._lock:
                self._total[name] += dt
                self._count[name] += 1

    def metrics(self) -> dict[str, float]:
        """Mean milliseconds per phase, for the stats pipeline."""
        with self._lock:  # keys can be inserted by producer threads
            totals = dict(self._total)
            counts = dict(self._count)
        return {
            f"Profile/{name}_ms": 1000.0 * totals[name] / counts[name]
            for name in totals
            if counts[name]
        }

    def summary(self) -> dict[str, dict[str, float]]:
        with self._lock:
            totals = dict(self._total)
            counts = dict(self._count)
        return {
            name: {
                "total_seconds": totals[name],
                "count": counts[name],
                "mean_ms": 1000.0 * totals[name] / max(counts[name], 1),
            }
            for name in sorted(totals)
        }

    def dump(self, path: Path) -> None:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.summary(), indent=2))


class ProfileSession:
    """Owns one run's profiling: a bounded device-trace window + timers.

    The trace covers iterations [trace_start, trace_stop) — after the
    first iteration so compilation doesn't dominate, and bounded so the
    trace stays a viewable size (the reference bounds its cProfile per
    episode for the same reason, `worker.py:172-173`).

    When a `tracer` (telemetry.SpanTracer) is attached, every `phase`
    also records an individual begin/end span — the per-occurrence
    timeline next to these whole-run means.
    """

    def __init__(
        self,
        enabled: bool,
        profile_dir: Path,
        trace_start: int = 1,
        trace_stop: int = 3,
        tracer=None,
    ) -> None:
        if trace_stop <= trace_start:
            # A window that never closes would silently trace the whole
            # run into an unviewably large dump.
            raise ValueError(
                f"trace_stop={trace_stop} must be > trace_start="
                f"{trace_start}"
            )
        self.enabled = enabled
        self.profile_dir = Path(profile_dir)
        self.timers = PhaseTimers()
        self.tracer = tracer
        self._trace_start = trace_start
        self._trace_stop = trace_stop
        self._tracing = False

    @contextmanager
    def phase(self, name: str):
        with self.timers.phase(name):
            if self.tracer is not None:
                with self.tracer.span(name):
                    yield
            else:
                yield

    def on_iteration(self, iteration: int) -> None:
        """Called at the top of each loop iteration."""
        if not self.enabled:
            return
        if iteration == self._trace_start and not self._tracing:
            import jax

            self.profile_dir.mkdir(parents=True, exist_ok=True)
            logger.info(
                "Profiling: starting jax.profiler trace into %s "
                "(iterations %d-%d).",
                self.profile_dir,
                self._trace_start,
                self._trace_stop - 1,
            )
            jax.profiler.start_trace(str(self.profile_dir))
            self._tracing = True
        elif iteration >= self._trace_stop and self._tracing:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax

        # Cleared first: a failing stop_trace must not leave the session
        # retrying forever (and close() must still dump the timers).
        self._tracing = False
        jax.profiler.stop_trace()
        logger.info("Profiling: device trace written to %s.", self.profile_dir)
        # A TPU trace names an operation by its HLO instruction alone;
        # the `op_name` that carries the phase is in the executable.
        try:
            (self.profile_dir / OP_NAMES_FILENAME).write_text(
                json.dumps(program_op_names())
            )
        except Exception:
            logger.exception("Profiling: %s not written.", OP_NAMES_FILENAME)

    def close(self) -> None:
        if self._tracing:
            try:
                self._stop_trace()
            except Exception:
                logger.exception(
                    "jax.profiler.stop_trace failed; dumping phase "
                    "timers anyway."
                )
        if self.enabled:
            self.timers.dump(self.profile_dir / "phase_timers.json")


def analyze_profile_dir(profile_dir: str, top: int = 20) -> int:
    """Print a per-phase summary of a profile run (CLI `analyze`)."""
    root = Path(profile_dir)
    dump = root / "phase_timers.json"
    if dump.exists():
        summary = json.loads(dump.read_text())
        rows = sorted(
            summary.items(),
            key=lambda kv: kv[1]["total_seconds"],
            reverse=True,
        )[:top]
        width = max((len(name) for name, _ in rows), default=5)
        print(f"{'phase':<{width}}  {'total s':>9}  {'count':>7}  {'mean ms':>9}")
        for name, s in rows:
            print(
                f"{name:<{width}}  {s['total_seconds']:>9.2f}  "
                f"{s['count']:>7d}  {s['mean_ms']:>9.2f}"
            )
    else:
        print(f"No phase_timers.json in {root}.")

    traces = sorted(root.glob("**/*.xplane.pb"))
    if traces:
        op_names = {}
        if (root / OP_NAMES_FILENAME).exists():
            op_names = json.loads((root / OP_NAMES_FILENAME).read_text())
        print(f"\n{len(traces)} device trace(s):")
        for t in traces[:top]:
            print(f"  {t}")
            summarize_xplane_trace(t, op_names=op_names, top=top)
        print(
            "View with: tensorboard --logdir "
            f"{root} (PROFILE tab)"
        )
    elif not dump.exists():
        return 1
    return 0


# --- device phases -----------------------------------------------------

_CONTAINERS = ("while", "conditional", "call")
_INSTRUCTION = re.compile(r"\s*(?:ROOT )?(%[\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REFERENCE = re.compile(r"%[\w.\-]+")


def parse_op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> `op_name`, from `compiled.as_text()`.

    An instruction the compiler added (a layout `copy`, an async pair)
    carries no metadata: it takes the name of the first named
    instruction that uses it, else of its first named operand — a copy
    belongs to the phase that needs it."""
    named: dict[str, str] = {}
    operands: dict[str, list[str]] = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name = found.group(1)
        operands[name] = _REFERENCE.findall(line[found.end():])
        op_name = _OP_NAME.search(line)
        # A parameter, and a copy the compiler makes of one, carry the
        # argument's name (`storage['policy_target']`): not a place in
        # the program, which always reads `jit(f)/...`.
        if op_name and "/" in op_name.group(1):
            named[name] = op_name.group(1)
    users: dict[str, list[str]] = defaultdict(list)
    for name, refs in operands.items():
        for ref in refs:
            if ref in operands:
                users[ref].append(name)
    for _ in range(4):  # copy -> tuple -> while: a few rounds reach the named
        grown = dict(named)
        for name, refs in operands.items():
            if name in named:
                continue
            near = [u for u in users[name] if u in named] + [
                r for r in refs if r in named
            ]
            if near:
                grown[name] = named[near[0]]
        if len(grown) == len(named):
            break
        named = grown
    return named


def program_op_names() -> dict[str, dict[str, str]]:
    """{HLO module name: {instruction: op_name}} of every executable the
    compile cache's live programs hold."""
    from .compile_cache import get_compile_cache

    out: dict[str, dict[str, str]] = {}
    for _, compiled in get_compile_cache().executables():
        text = compiled.as_text()
        module = re.match(r"HloModule ([^\s,]+)", text)
        if module:
            out.setdefault(module.group(1), {}).update(parse_op_names(text))
    return out


@functools.lru_cache(maxsize=None)  # a trace repeats a few thousand names
def phase_of(op_name: str) -> str:
    """The innermost phase name in an `op_name`; autodiff's transpose
    of the forward pass is `learner/backward`."""
    if "transpose(" in op_name and "learner/forward_loss" in op_name:
        return "learner/backward"
    return forward_phase_of(op_name)


@functools.lru_cache(maxsize=None)
def forward_phase_of(op_name: str) -> str:
    """The innermost phase name in an `op_name`, whichever pass the
    operation belongs to: for one of the backward pass, the phase of
    the forward pass it transposes (or, under recomputation, runs
    again)."""
    at, phase = -1, OTHER
    for name in PHASES:
        found = op_name.rfind(name)
        # Of two names that start at one place (`net/trunk`,
        # `net/trunk/experts`) the longer is the inner one.
        if found > at or (found == at >= 0 and len(name) > len(phase)):
            at, phase = found, name
    return phase


def _instruction(event_name: str) -> str:
    return event_name.split(" = ", 1)[0]


def _is_container(event_name: str) -> bool:
    """`while`, `conditional` and `call` contain operations that the
    line lists too: counting both would count the time twice."""
    if _instruction(event_name).lstrip("%").split(".")[0] in _CONTAINERS:
        return True
    return bool(re.search(r"\s(?:while|conditional|call)\(", event_name))


def phase_seconds(events, op_names: dict[str, str]) -> dict[str, float]:
    """Device seconds per phase of one program's operations.

    `events` are (name, start_ns, duration_ns) of an `XLA Ops` line, a
    name being the operation's `op_name` itself or its HLO line (a TPU
    trace), which `op_names` maps by instruction. Containers are left
    out; what maps to no phase is `other`, last."""
    total: dict[str, float] = defaultdict(float)
    for name, _, duration in events:
        if _is_container(name):
            continue
        op_name = op_names.get(_instruction(name), name)
        total[phase_of(op_name)] += duration / 1e9
    out = {p: total[p] for p in PHASES if p in total}
    out[OTHER] = total.get(OTHER, 0.0)
    return out


def backward_seconds(events, op_names: dict[str, str]) -> dict[str, float]:
    """`learner/backward`'s device seconds by the phase of the forward
    pass each operation transposes or recomputes (`forward_phase_of`);
    empty for a program without a backward pass."""
    total: dict[str, float] = defaultdict(float)
    for name, _, duration in events:
        if _is_container(name):
            continue
        op_name = op_names.get(_instruction(name), name)
        if phase_of(op_name) == "learner/backward":
            total[forward_phase_of(op_name)] += duration / 1e9
    return {p: total[p] for p in (*PHASES, OTHER) if p in total}


def idle_gap_seconds(modules, host_spans) -> dict[str, dict[str, float]]:
    """For each program of an `XLA Modules` line, the seconds the device
    idled between two of its runs, by the program's own host span that
    covered them (`at:` spans, the prefix taken off; the innermost where
    spans nest); what no span covered is `other`, last. Both lists are
    (name, start_ns, duration_ns) on the trace's one clock. Another
    program's runs inside a gap (the ingest between two chunks) leave
    it a gap: it is this program's device that waits. The program that
    ran longest comes first: of a small one's "gaps" most is the large
    one running."""
    runs: dict[str, list] = defaultdict(list)
    for name, start, duration in sorted(modules, key=lambda e: e[1]):
        runs[name.split("(")[0]].append((start, start + duration))
    out: dict[str, dict[str, float]] = {}
    by_time = sorted(runs, key=lambda p: -sum(b - a for a, b in runs[p]))
    for program, ran in ((p, runs[p]) for p in by_time):
        total: dict[str, float] = defaultdict(float)
        for (_, gap_start), (gap_stop, _) in zip(ran, ran[1:]):
            if gap_stop <= gap_start:
                continue
            over = [
                (max(s, gap_start), min(s + d, gap_stop), s, name)
                for name, s, d in host_spans
                if min(s + d, gap_stop) > max(s, gap_start)
            ]
            edges = sorted(
                {gap_start, gap_stop, *(e for o in over for e in o[:2])}
            )
            for left, right in zip(edges, edges[1:]):
                inside = [o for o in over if o[0] <= left and right <= o[1]]
                # Of nested spans the one begun last is the innermost.
                name = max(inside, key=lambda o: o[2])[3] if inside else OTHER
                total[name] += (right - left) / 1e9
        if len(ran) > 1:
            named = sorted(
                (kv for kv in total.items() if kv[0] != OTHER),
                key=lambda kv: -kv[1],
            )
            out[program] = {**dict(named), OTHER: total.get(OTHER, 0.0)}
    return out


def _by_program(modules, ops) -> dict[str, list]:
    """The operations under the program execution that holds them
    (`XLA Modules` names a run `jit_f(<fingerprint>)`)."""
    runs = sorted(modules, key=lambda e: e[1])
    starts = [e[1] for e in runs]
    out: dict[str, list] = defaultdict(list)
    for op in ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        inside = i >= 0 and op[1] < runs[i][1] + runs[i][2]
        program = runs[i][0].split("(")[0] if inside else "-"
        out[program].append(op)
    return out


def _listed(line) -> list:
    return [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]


def device_operations(planes) -> list[tuple[str, list, list]]:
    """(plane name, operations, program runs) of every device plane
    with an `XLA Ops` line, each event as (name, start_ns, duration_ns);
    `planes` are `jax.profiler.ProfileData.from_file(path).planes`. A
    CPU trace has none."""
    out = []
    for plane in planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:") and "XLA Ops" in lines:
            modules = lines.get("XLA Modules")
            out.append(
                (
                    plane.name,
                    _listed(lines["XLA Ops"]),
                    _listed(modules) if modules is not None else [],
                )
            )
    return out


def summarize_xplane_trace(
    path: Path, op_names: "dict | None" = None, top: int = 20
) -> None:
    """Per device plane and program: device seconds and share per named
    phase, `other` last; then the program's own host spans (`at:`), and
    for each program the idle gaps between its runs by the span that
    covered them (`idle_gap_seconds`).

    Reads the xplane with `jax.profiler.ProfileData` alone. `op_names`
    is `op_names.json` as `ProfileSession` wrote it beside the trace
    ({module: {instruction: op_name}}); without it every operation of a
    TPU trace is `other`."""
    import jax

    try:
        data = jax.profiler.ProfileData.from_file(str(path))
        planes = list(data.planes)
    except Exception as exc:
        print(f"  (unreadable trace: {exc})")
        return
    op_names = op_names or {}
    host: dict[str, list[float]] = defaultdict(lambda: [0.0, 0])
    host_spans = []  # (name without the prefix, start_ns, duration_ns)
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(ANNOTATION_PREFIX):
                    host[e.name][0] += e.duration_ns / 1e6
                    host[e.name][1] += 1
                    host_spans.append(
                        (
                            e.name[len(ANNOTATION_PREFIX):],
                            int(e.start_ns),
                            int(e.duration_ns),
                        )
                    )
    devices = device_operations(planes)
    for plane_name, ops, modules in devices:
        for program, events in sorted(_by_program(modules, ops).items()):
            seconds = phase_seconds(events, op_names.get(program, {}))
            busy = sum(seconds.values())
            print(
                f"\n  plane {plane_name} / program {program}: "
                f"{len(events)} operations, {busy:.3f} s"
            )
            print(f"    {'phase':<24} {'seconds':>10} {'share':>7}")
            for phase, sec in seconds.items():
                print(
                    f"    {phase:<24} {sec:>10.4f} "
                    f"{100.0 * sec / max(busy, 1e-12):>6.1f}%"
                )
            backward = backward_seconds(events, op_names.get(program, {}))
            if backward:
                print("    learner/backward, by the forward phase it transposes:")
                for phase, sec in backward.items():
                    print(
                        f"      {phase:<22} {sec:>10.4f} "
                        f"{100.0 * sec / max(busy, 1e-12):>6.1f}%"
                    )
        for program, gaps in idle_gap_seconds(modules, host_spans).items():
            idle = sum(gaps.values())
            print(
                f"\n  plane {plane_name} / program {program}: idle between "
                f"its runs {idle:.4f} s, by host span ({ANNOTATION_PREFIX}):"
            )
            for name, sec in gaps.items():
                print(
                    f"    {name:<32} {sec:>10.4f} "
                    f"{100.0 * sec / max(idle, 1e-12):>6.1f}%"
                )
    if not devices:
        print("  (no device plane with an XLA Ops line: a CPU trace)")
    if host:
        print(f"\n  host spans ({ANNOTATION_PREFIX}):")
        print(f"    {'span':<32} {'total ms':>10} {'count':>8}")
        rows = sorted(host.items(), key=lambda kv: -kv[1][0])[:top]
        for name, (ms, count) in rows:
            print(f"    {name:<32} {ms:>10.2f} {count:>8d}")
