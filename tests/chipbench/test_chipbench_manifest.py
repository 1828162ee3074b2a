"""`BENCHMARK.json` is well formed, and every name in it finds its
file under `chipbench/`."""

import importlib
import re

import pytest

from chipbench import manifest

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

BENCH = manifest.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"][-1].startswith(BENCH["paths"][0] + "/")
    fours = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert fours <= max(1, len(CELLS) // 4)
    assert all(w["chips"] in (1, 4) for w in BENCH["workloads"])


def test_names_units_and_lines():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    assert len(set(CELLS)) == len(CELLS)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for w in BENCH["workloads"]:
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in BENCH["configs"]:
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200 and c["reduced"] == []
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_end_to_end_metrics_and_bounds():
    end = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in end and end["setup_s"]["bound"] <= 0.1
    for m in end.values():
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert "bound" not in m and m["moves"] in end
        for cell in m.get("workloads", CELLS):
            reported = [e["name"] for e in manifest.metrics_of(cell, trace=False)]
            assert m["moves"] in reported


@pytest.mark.parametrize("cell_name", CELLS)
def test_every_cell_finds_its_files(cell_name):
    cell = manifest.cell(cell_name)
    config = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert config["file"] == f"chipbench/configs/{cell['config']}.json"
    assert cell["config_file"]["name"] == cell["config"]
    assert cell["config_file"]["source"].startswith("https://")
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['traffic_file']['driver']}"
    )
    assert hasattr(driver, "Driver")
    rate = cell["traffic_file"]["rate_metric"]
    reported = [m["name"] for m in manifest.metrics_of(cell_name, trace=False)]
    assert sorted(reported) == sorted(["setup_s", rate])
    layer = manifest.metrics_of(cell_name, trace=True)
    assert layer
    for metric in layer:
        assert callable(manifest.layer_reader(metric["name"]))
    assert cell["limits"]["window_compiles"] == 0
    assert all(v >= 0 for v in cell["limits"].values())


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_configuration_is_the_programs_preset_at_width(config):
    """Every size in the file is the preset's own, but for what the
    file lists as assumed."""
    from alphatriangle_tpu.config.presets import baseline_preset

    cfg = manifest.load_json(manifest.HERE / "configs" / f"{config}.json")
    preset = baseline_preset(cfg["preset"])
    built = manifest.program_configs(cfg)
    assert built["model"] == preset["model"]
    assert built["env"] == preset["env"]
    assert built["mcts"] == preset["mcts"]
    assumed = set(cfg["assumed"]) | {"RUN_NAME", "AUTO_RESUME_LATEST"}
    ours, theirs = built["train"].model_dump(), preset["train"].model_dump()
    assert {k for k in ours if ours[k] != theirs[k]} <= assumed
    assert built["train"].BUFFER_CAPACITY == 3_000_000
    assert cfg["action_dim"] == preset["env"].action_dim
