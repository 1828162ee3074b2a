#!/usr/bin/env python3
"""Readings for the limits of `correct`, at a cell's own size.

    python3 chipbench/calibrate.py --workload <cell> --seeds 101,102,...

For each seed, in one process: what the program's timed path produced
against the plain reference (the lower readings), the control in the
program's place (the reference with every matmul operand rounded to
fp8, the nearest precision below the bfloat16 the configurations
state), and for a training cell the planted fault it can have (half of
each batch left out, the mean taken over the rest; a state returned
unchanged reads 1 on `change_gap` by the measure itself and needs no
run), and with `bf16` among the parts a second sound witness, the
reference with its operands rounded to bfloat16; for a rollout cell
`backup` plants the root's own value left out of its mean. Each driver's
`calibrate` says how; `correct` in each line is every part sent through
the run's own comparison with the cell's limits. Prints one JSON
line per seed; `PERF.md` keeps the readings the limits were set from.
The benchmark's own runs never call this.
"""

import argparse
import gc
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def readings(cell: dict, seed: int, configs: dict, parts, detail=False) -> dict:
    import importlib

    from chipbench import run
    from chipbench.spans import Spans

    module = importlib.import_module(
        f"chipbench.drivers.{cell['traffic_file']['driver']}"
    )
    driver = module.Driver(cell, configs, seed, Spans())
    out = module.calibrate(driver, parts, detail)
    # Each part's numbers through the run's own comparison, with the
    # cell's limits: the control and the faults have to come out false.
    limits = {k: v for k, v in cell["limits"].items() if k != "window_compiles"}
    out["correct"] = {
        part: run.compare({k: out[part][k] for k in limits}, limits)[0]
        for part in parts
        if part in out
    }
    return {"seed": seed, **out}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--parts", default="program,control,half")
    parser.add_argument("--detail", action="store_true")
    args = parser.parse_args(argv)

    import jax

    from chipbench import manifest, run

    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU; readings come only from the chip", file=sys.stderr)
        return 3
    run.enable_compile_cache()
    cell = manifest.cell(args.workload)
    configs = manifest.program_configs(cell["config_file"])
    for seed in args.seeds.split(","):
        gc.collect()  # the last seed's ring, before the next is built
        print(
            json.dumps(
                readings(
                    cell, int(seed), configs, args.parts.split(","), args.detail
                )
            ),
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
