#!/usr/bin/env python3
"""One run of one cell of the benchmark, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds what the cell dispatches from `--seed`, warms it up (set-up),
drives it for `--seconds` in whole dispatches, reads the device's
memory, frees the program's state, then has the plain reference decide
`correct`. The last line of standard output is the result; the last
lines of standard error are the numbers compared, each beside its
limit. With `--trace 1` part of the window runs under the profiler and
the line carries the cell's per-layer metrics.

No chip, no result: on a CPU backend, or with fewer chips than the cell
asks for, it exits with code 3 and prints no line.
"""

import time

T0 = time.perf_counter()  # set-up counts from here, before any import

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(message: str) -> None:
    print(message, file=sys.stderr, flush=True)


class CompileClock:
    """Seconds JAX spent getting executables (compiling, or reading its
    persistent cache), and how many times, by its own monitoring."""

    def __init__(self):
        import jax

        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, seconds: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += seconds


def enable_compile_cache() -> None:
    """The program's own cache directory: JAX_COMPILATION_CACHE_DIR, or
    .cache/jax inside the checkout. Small programs are kept too, so a
    second run compiles nothing."""
    import jax
    from alphatriangle_tpu.utils.helpers import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def device_record(peak_bytes=None) -> dict:
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }


def memory_peak_bytes() -> "int | None":
    """The peak on the fullest chip; None where the backend keeps none."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_window(driver, seconds: float, spans, trace_units: int) -> dict:
    """Whole dispatches until `seconds` have passed. With `trace_units`
    the first that many run under the profiler."""
    import jax

    trace_dir, traced = None, None
    work = units = 0
    mark = spans.mark()
    driver.start_window()
    if trace_units:
        trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        spans.annotate = True
    start = time.perf_counter()
    paused = 0.0  # writing the trace out is not part of the window
    while True:
        work += driver.unit()
        units += 1
        now = time.perf_counter()
        if trace_dir is not None and traced is None and units >= trace_units:
            traced = now - start
            spans.annotate = False
            jax.profiler.stop_trace()
            paused = time.perf_counter() - now
            now += paused
        if now - start - paused >= seconds:
            break
    return {
        "work": work,
        "units": units,
        "window_s": now - start - paused,
        "span_mark": mark,
        "trace_dir": trace_dir,
        "traced_s": traced,
    }


def compare(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Every number beside its limit; a number with no limit, or one
    that is not finite, is not correct."""
    import math

    compared, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        compared[name] = {"value": value, "limit": limit}
        if limit is None or not math.isfinite(value) or value > limit:
            ok = False
    return ok, compared


def run_cell(
    cell: dict, seed: int, seconds: float, trace: bool, require_chip: bool = True
) -> "dict | None":
    """The run, as a function: tests hand it a tiny cell and
    `require_chip=False`, which the command line never does."""
    import jax

    devices = jax.devices()
    if require_chip and (
        devices[0].platform != "tpu" or len(devices) < cell["chips"]
    ):
        log(
            f"chipbench: {cell['name']} needs {cell['chips']} TPU chip(s); "
            f"JAX found {len(devices)} x {devices[0].platform}. No result."
        )
        return None

    from alphatriangle_tpu.compile_cache import get_compile_cache

    from chipbench import manifest, trace as trace_mod
    from chipbench.flops import peak
    from chipbench.spans import Spans

    enable_compile_cache()
    clock = CompileClock()

    spans = Spans()
    traffic = cell["traffic_file"]
    driver = importlib.import_module(
        f"chipbench.drivers.{traffic['driver']}"
    ).Driver(cell, manifest.program_configs(cell["config_file"]), seed, spans)
    driver.setup()
    cache = get_compile_cache().stats()
    setup_compile_s = clock.seconds + sum(
        e["seconds"] for e in cache["events"] if e["event"] == "hit"
    )
    setup_s = time.perf_counter() - T0
    log(
        f"chipbench: set-up {setup_s:.1f} s, of it {setup_compile_s:.1f} s "
        f"getting programs (AOT hits {cache['hits']}, misses {cache['misses']})"
    )

    compiles_before = clock.count + len(cache["events"])
    window = run_window(
        driver, seconds, spans, traffic["trace_units"] if trace else 0
    )
    compiles_in_window = (
        clock.count + len(get_compile_cache().stats()["events"]) - compiles_before
    )
    peak_bytes = memory_peak_bytes()
    device = device_record(peak_bytes)
    rate = window["work"] / window["window_s"]
    log(
        f"chipbench: {window['units']} dispatches, {window['work']} "
        f"{driver.unit_name} in {window['window_s']:.3f} s = {rate:.3f}/s; "
        f"peak {peak_bytes} B"
    )

    metrics: dict = {}
    result: dict = {}
    if trace:
        xplane = trace_mod.read_xplane(trace_mod.find_xplane(window["trace_dir"]))
        shutil.rmtree(window["trace_dir"], ignore_errors=True)
        summary = trace_mod.summarize(xplane, window["traced_s"])
        log(
            f"chipbench: traced {len(summary['dispatch_ms'])} runs of "
            f"{summary['program']} among {summary['programs_run']} program "
            f"executions"
        )
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
        ctx = {
            **window,
            "spans": spans,
            "trace": summary,
            "compile_s": setup_compile_s,
            "counters": driver.counters(),
            "peak": peak(device["kind"]) if require_chip else None,
        }
        for metric in manifest.metrics_of(cell["name"], trace=True):
            value = manifest.layer_reader(metric["name"])(ctx)
            if value is not None:
                metrics[metric["name"]] = {
                    "value": value, "unit": metric["unit"]
                }
    else:
        values = {"setup_s": setup_s, traffic["rate_metric"]: rate}
        for metric in manifest.metrics_of(cell["name"], trace=False):
            metrics[metric["name"]] = {
                "value": values[metric["name"]], "unit": metric["unit"]
            }

    # The reference runs last: the peak is read and the ring is freed.
    driver.release()
    started = time.perf_counter()
    numbers = driver.check()
    numbers["window_compiles"] = float(compiles_in_window)
    correct, compared = compare(numbers, cell["limits"])
    correct = correct and driver.failed == 0
    log(
        f"chipbench: reference took {time.perf_counter() - started:.1f} s; "
        f"read {getattr(driver, 'read', None)}"
    )
    for name, pair in compared.items():
        log(f"chipbench: compared {name} {pair['value']:.6g} limit {pair['limit']}")
    log(f"chipbench: correct {correct}")
    return {
        "correct": bool(correct),
        "attempted": window["units"],
        "failed": driver.failed,
        "metrics": metrics,
        "device": device,
        **result,
        "compared": compared,
    }


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    try:
        import alphatriangle_tpu  # noqa: F401
    except ImportError:
        log(
            "chipbench: the program (alphatriangle_tpu/) is not in this "
            "checkout; the benchmark measures it and has nothing to run."
        )
        return 3
    from chipbench import manifest

    result = run_cell(
        manifest.cell(args.workload), args.seed, args.seconds, bool(args.trace)
    )
    if result is None:
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
