"""Finds a cell's files by the names in `BENCHMARK.json`.

A cell (`workloads` entry) names a configuration and a traffic mix:
`chipbench/configs/<config>.json` holds the sizes as they are run,
`chipbench/traffic/<traffic>.json` the driver and its parameters,
`chipbench/limits/<cell>.json` the limits of `correct`, and each
per-layer metric has a reader `chipbench/layer_metrics/<name>.py`, or a
`<name>.json` that names the reader it shares.
Adding any of them is adding a file and an entry; nothing here names
one.
"""

import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """The cell's entry with its configuration, traffic and limits."""
    bench = benchmark()
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(
            f"unknown workload {name!r}; BENCHMARK.json has {sorted(entries)}"
        )
    entry = dict(entries[name])
    entry["config_file"] = load_json(HERE / "configs" / f"{entry['config']}.json")
    entry["traffic_file"] = load_json(HERE / "traffic" / f"{entry['traffic']}.json")
    entry["limits"] = load_json(HERE / "limits" / f"{name}.json")
    return entry


def metrics_of(cell_name: str, trace: bool) -> list[dict]:
    """The metrics a run of this cell reports: its end-to-end metrics
    with `--trace 0`, its per-layer metrics with `--trace 1`."""
    bench = benchmark()
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [
        m for m in group if cell_name in m.get("workloads", [cell_name])
    ]


def layer_reader(name: str):
    """The `read(ctx)` of `layer_metrics/<name>.py`; or, where the
    metric has a `layer_metrics/<name>.json` instead, of the reader
    that file names under `reader` (several metrics may share one)."""
    path = HERE / "layer_metrics" / f"{name}.py"
    if not path.exists():
        name = load_json(path.with_suffix(".json"))["reader"]
        path = HERE / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def program_configs(config_file: dict) -> dict:
    """The program's own config objects from a configuration's file.
    Keys the program's classes do not have (DROPOUT_RATE, which is a
    constant of its model code) stay in the file for the reference."""
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        EnvConfig,
        ModelConfig,
        TrainConfig,
    )

    def build(cls, group):
        return cls(**{k: v for k, v in group.items() if k in cls.model_fields})

    env = dict(config_file["env"])
    env["PLAYABLE_RANGE_PER_ROW"] = [
        tuple(r) for r in env["PLAYABLE_RANGE_PER_ROW"]
    ]
    return {
        "env": build(EnvConfig, env),
        "model": build(ModelConfig, config_file["model"]),
        "train": build(
            TrainConfig,
            {**config_file["train"], "AUTO_RESUME_LATEST": False},
        ),
        "mcts": build(AlphaTriangleMCTSConfig, config_file["mcts"]),
    }
