"""From the profiler's trace to the device's busy time, the time of each
dispatch, the gaps between dispatches and what the host did in them.

`read_xplane` reads the `.xplane.pb` that `jax.profiler` wrote, with
`jax.profiler.ProfileData` and nothing else. The rest are plain
functions over lists of (name, start_ns, duration_ns), which the tests
drive with hand-made events.

On a TPU the device's plane (`/device:TPU:<n>`) has a line of whole
program executions (`XLA Modules`) and a line of single operations
(`XLA Ops`). Busy time is the union of the operations' intervals: an
operation that contains others (a `while`) adds nothing twice.
"""

import re
from pathlib import Path

from .spans import PREFIX

Event = tuple[str, int, int]  # name, start_ns, duration_ns

MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path) -> dict:
    """{"devices": {plane: {line: [Event]}}, "host": [Event]}: every
    line of every device plane, and the harness's own spans from the
    host's planes."""
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    devices: dict[str, dict[str, list[Event]]] = {}
    host: list[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                lines[line.name] = [
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events
                ]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        host.append(
                            (
                                e.name[len(PREFIX):],
                                int(e.start_ns),
                                int(e.duration_ns),
                            )
                        )
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def accelerator_planes(devices: dict) -> dict:
    """The planes that ran operations (a TPU also has planes of its
    own for the host-side runtime, with no operations line)."""
    return {
        name: lines
        for name, lines in devices.items()
        if lines.get(OPS_LINE) or lines.get(MODULES_LINE)
    }


def busy_ns(events: list[Event]) -> int:
    """Length of the union of the events' intervals."""
    total, end = 0, None
    for _, start, duration in sorted(events, key=lambda e: e[1]):
        stop = start + duration
        if end is None or start > end:
            total += duration
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def main_program(modules: list[Event]) -> str:
    """The program that ran longest in all: the cell's one program.
    (Beside it the line holds the small programs of the host's calls:
    an ingest's scatter, a schedule evaluated op by op.)"""
    total: dict[str, int] = {}
    for name, _, duration in modules:
        total[name] = total.get(name, 0) + duration
    return max(total, key=total.get)


def dispatches(modules: list[Event], name: str) -> list[Event]:
    """The executions of the program `name`, in order of start."""
    return sorted((e for e in modules if e[0] == name), key=lambda e: e[1])


def gaps_ns(runs: list[Event]) -> list[tuple[int, int]]:
    """(start, length) of the idle gap after each run but the last."""
    out = []
    for (_, start, duration), (_, nxt, _) in zip(runs, runs[1:]):
        out.append((start + duration, max(0, nxt - (start + duration))))
    return out


def median(values: list) -> float:
    values = sorted(values)
    n = len(values)
    mid = n // 2
    return float(values[mid] if n % 2 else (values[mid - 1] + values[mid]) / 2)


def attribute_gaps(
    gaps: list[tuple[int, int]], host: list[Event]
) -> dict[str, int]:
    """Nanoseconds of the gaps by the host span that covered them; what
    no span covered goes to `other`."""
    by_name: dict[str, int] = {}
    for start, length in gaps:
        stop, covered = start + length, 0
        for name, h_start, h_duration in host:
            overlap = min(stop, h_start + h_duration) - max(start, h_start)
            if overlap > 0:
                by_name[name] = by_name.get(name, 0) + overlap
                covered += overlap
        if length > covered:
            by_name["other"] = by_name.get("other", 0) + length - covered
    return by_name


def short_name(name: str, limit: int = 96) -> str:
    """A TPU trace names an operation by its whole HLO line. Keep the
    result's name, the opcode and the start of the result's type:
    `%copy.476 copy f32[3000001,360]`."""
    if " = " not in name:
        return name[:limit]
    result, rest = name.split(" = ", 1)
    opcode = re.search(r"(?:^|\s)([a-z][\w\-]*)\(", rest)
    kind = re.sub(r"\{[^}]*\}", "", rest[: opcode.start()] if opcode else rest)
    text = f"{result} {opcode.group(1) if opcode else ''} {kind.strip()}"
    return " ".join(text.split())[:limit]


def top_operations(ops: list[Event], n: int = 10) -> list[list]:
    """The operations that took most time, [[name, seconds], ...].
    Containers (`while`, `conditional`, `call`) are left out: their
    bodies are listed."""
    total: dict[str, int] = {}
    for name, _, duration in ops:
        name = short_name(name)
        if name.split(".")[0].split(" ")[0].lstrip("%") in (
            "while", "conditional", "call"
        ):
            continue
        total[name] = total.get(name, 0) + duration
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def summarize(xplane: dict, window_s: float) -> dict:
    """What the per-layer readers and the result line need, averaged
    over the accelerator planes in use."""
    planes = accelerator_planes(xplane["devices"])
    if not planes:
        raise RuntimeError(
            f"the trace has no device operations; planes: "
            f"{sorted(xplane['devices'])}"
        )
    busy, per_dispatch, gap_lengths, gap_host = [], [], [], {}
    ops_all: list[Event] = []
    for lines in planes.values():
        ops = lines.get(OPS_LINE, [])
        busy.append(busy_ns(ops or lines.get(MODULES_LINE, [])))
        ops_all += ops
        modules = lines.get(MODULES_LINE, [])
        program = main_program(modules) if modules else ""
        runs = dispatches(modules, program)
        per_dispatch += [d for _, _, d in runs]
        gaps = gaps_ns(runs)
        gap_lengths += [g for _, g in gaps]
        for name, ns in attribute_gaps(gaps, xplane["host"]).items():
            gap_host[name] = gap_host.get(name, 0) + ns
    idle = sorted(gap_host.items(), key=lambda kv: -kv[1])[:10]
    return {
        "program": program,
        "programs_run": len(modules),
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": window_s,
        "dispatch_ms": [d / 1e6 for d in per_dispatch],
        "gap_ms": [g / 1e6 for g in gap_lengths],
        "breakdown": {
            "device_ops": top_operations(ops_all),
            "idle_gaps": [[name, ns / 1e9] for name, ns in idle],
        },
    }
