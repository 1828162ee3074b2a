"""`nemotron-super-ep4` / `nemotron-super-rollout`: the configuration's
file against the catalog's published keys, the FLOP count against hand
figures and the program's, and at a tiny size on the CPU the program
against the plain reference (`reference_nemotron_h`): each kind of layer
and the whole forward (logits, not choices), one dispatch of the cell
end to end with the control in the program's place, the shares of the
experts adding up to the uncut layer, the routers' balancing under top
k of many, and the new reader.

Tolerances. The tiny net computes in float32 on both sides, so the two
differ by summation order (and, in the Mamba-2 layers, by the chunked
form against the token-by-token one): logits of size about 1 agree to
1e-3 (seen: 2e-6; the stack is pre-norm, so nothing renormalises a
rounding), a layer's output of size about 10 to 1e-4 (seen: 3e-6).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_ssm_cell import tiny_ssm_cell, tiny_ssm_cfg

from chipbench import (
    flops_exaone_moe, flops_nemotron_h, manifest, reference, router_balance_ssm, run,
)
from chipbench import reference_nemotron_h as plain
from chipbench.drivers import rollout_ssm

SEED = 2**31 + 38
LOGIT_TOLERANCE = 1e-3
LAYER_TOLERANCE = 1e-4
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"
PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEM*EMEMEMEME"
)

# The `config` of the catalog's row NVIDIA-Nemotron-3-Super-120B-A12B-BF16
# (model-configs/architectures.jsonl), copied.
CATALOG = {
    "attention_bias": False,
    "chunk_size": 128,
    "conv_kernel": 4,
    "expand": 2,
    "head_dim": 128,
    "hidden_size": 4096,
    "hybrid_override_pattern": PATTERN,
    "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64,
    "mamba_hidden_act": "silu",
    "mamba_num_heads": 128,
    "mamba_proj_bias": False,
    "max_position_embeddings": 262144,
    "mlp_bias": False,
    "mlp_hidden_act": "relu2",
    "model_type": "nemotron_h",
    "moe_intermediate_size": 2688,
    "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E",
    "n_group": 1,
    "n_groups": 8,
    "n_routed_experts": 512,
    "n_shared_experts": 1,
    "norm_eps": 1e-05,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts_per_tok": 22,
    "num_hidden_layers": 88,
    "num_key_value_heads": 2,
    "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True,
    "residual_in_fp32": False,
    "rope_theta": 10000,
    "routed_scaling_factor": 5,
    "sliding_window": None,
    "ssm_state_size": 128,
    "tie_word_embeddings": False,
    "time_step_floor": 0.0001,
    "time_step_max": 0.1,
    "time_step_min": 0.001,
    "topk_group": 1,
    "use_bias": False,
    "use_conv_bias": True,
    "use_mamba_kernels": True,
    "vocab_size": 131072,
}
NAME = "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"
SOURCE = f"https://huggingface.co/nvidia/{NAME}/blob/main/config.json"
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "n_routed_experts"]
CONFIG, CELL = "nemotron-super-ep4", "nemotron-super-rollout"


def config_file() -> dict:
    return manifest.load_json(manifest.HERE / "configs" / f"{CONFIG}.json")


# --- the configuration's file ------------------------------------------------


def test_every_published_key_stands_unchanged_but_the_three_reduced():
    cfg = config_file()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    differ = {k for k, v in CATALOG.items() if k not in cfg or cfg[k] != v}
    assert differ == set(REDUCED)
    assert cfg["published"] == {
        "num_hidden_layers": 88, "hybrid_override_pattern": PATTERN,
        "n_routed_experts": 512,
    }
    assert [cfg[k] for k in REDUCED] == [11, "MEMEMEM*EME", 128]
    assert PATTERN.startswith(cfg["hybrid_override_pattern"]) and len(PATTERN) == 88
    entry = next(c for c in manifest.benchmark()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    # No width is among the reduced, nor differs: the guide's rule.
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) or k == "num_experts_per_tok"
        for k in REDUCED
    )
    # The guide's floors: a whole period of the pattern (the shortest
    # is *EMEMEMEM, 9 layers) with every kind in the whole model's
    # ratio, at least 8 routed experts held.
    assert (PATTERN.count("M"), PATTERN.count("E"), PATTERN.count("*")) == (40, 40, 8)
    t = plain.trunk_settings(cfg)
    assert t["layer_types"] == [
        {"M": "state_space", "E": "none", "*": "full_attention"}[c]
        for c in "MEMEMEM*EME"
    ]
    assert t["mlp_layer_types"] == [
        "sparse" if c == "E" else "none" for c in "MEMEMEM*EME"
    ]
    assert t["experts_held"] == [0, 128] and t["num_experts"] == 512
    assert (t["n_group"], t["topk_group"], t["num_experts_per_tok"]) == (1, 1, 22)


def test_the_copy_of_the_row_is_the_catalogs_where_the_catalog_is_at_hand():
    try:
        rows = [json.loads(line) for line in open(ROW)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == NAME)
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_the_file_keeps_every_published_width():
    cfg = config_file()
    assert (cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"]) == (4096, 32, 2, 128)
    assert (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["ssm_state_size"],
            cfg["n_groups"], cfg["conv_kernel"], cfg["chunk_size"]) == (
        128, 64, 128, 8, 4, 128
    )
    assert (cfg["moe_intermediate_size"], cfg["moe_latent_size"],
            cfg["num_experts_per_tok"], cfg["routed_scaling_factor"],
            cfg["moe_shared_expert_intermediate_size"]) == (2688, 1024, 22, 5, 5376)
    assert cfg["published"]["n_routed_experts"] == 512


def test_the_file_states_deployment_choices_assumptions_and_departures():
    cfg = config_file()
    assert cfg["deployment"]["expert_parallel"] == 4 and cfg["deployment"]["chip"] == 0
    assert "4 chips share each layer" in cfg["deployment"]["stated"]
    assert cfg["trunk_choices"] == {
        "norm_position": "pre", "qk_norm": "none", "router_bias": True,
        "block_boards": cfg["trunk_choices"]["block_boards"],
    }
    assert {
        "layer_pattern", "positions", "mamba", "gated_norm", "time_step", "router",
        "latent_experts", "router_bias", "block_boards", "board", "mcts", "weights",
    } <= set(cfg["assumed"])
    assert set(cfg["departures"]) >= {"embedding", "output_head", "mtp", "decoding"}
    assert (cfg["env"]["ROWS"], cfg["env"]["COLS"], cfg["action_dim"]) == (12, 21, 756)
    assert cfg["model"]["PARAM_DTYPE"] == cfg["model"]["INFERENCE_PRECISION"] == "bfloat16"
    ling = manifest.load_json(manifest.HERE / "configs" / "ling-flash-ep4.json")
    for group in ("env", "model", "train", "mcts"):
        assert cfg[group] == ling[group], group


def test_the_cell_and_its_metrics_are_entries_of_their_lists():
    """By name: a later PR's entries go after these."""
    bench = manifest.benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "rollout-t1-ssm", 1
    )
    assert len(cell["why"]) <= 200
    metric = next(m for m in bench["per_layer"] if m["name"] == "ssm_tokens_per_s")
    assert metric["workloads"] == [CELL] and metric["layer"] == "net trunk"
    assert metric["moves"] == "selfplay_moves_per_s"
    reported = {m["name"] for m in manifest.metrics_of(CELL, True)}
    lings = {m["name"] for m in manifest.metrics_of("ling-flash-rollout", True)}
    assert reported == lings - {"linear_tokens_per_s"} | {"ssm_tokens_per_s"}
    assert {"mfu.rollout", "device_idle_share.rollout", "expert_assignments_per_s",
            "routed_here_share", "chunk_wait_ms", "compile_s"} <= reported
    assert {m["name"] for m in manifest.metrics_of(CELL, False)} == {
        "selfplay_moves_per_s", "setup_s"
    }
    traffic = manifest.cell(CELL)["traffic_file"]
    hybrid = manifest.cell("ling-flash-rollout")["traffic_file"]
    # Its own: the net, the margins calibration gave, and one traced
    # unit, because a unit here (a period of five dispatches, 24 s)
    # outlasts the window and a second would never be traced.
    own = {"driver", "check", "loop", "candidate_margin", "unsure_most", "trace_units"}
    assert {k: v for k, v in traffic.items() if k not in own} == {
        k: v for k, v in hybrid.items() if k not in own
    }
    assert traffic["driver"] == "rollout_ssm" and traffic["trace_units"] == 1
    for metric in manifest.metrics_of(CELL, True):
        manifest.layer_reader(metric["name"])  # every one has its reader


def test_names_units_and_lines_with_the_reduced_configurations():
    """`test_chipbench_glm.py::test_names_units_and_lines_with_the_reduced_configurations`
    knows four configurations and fails since this one exists (those
    files are the benchmark's and are not this PR's to edit). The same
    lines here, each accepted configuration held to its own names and a
    later one to none."""
    import re

    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    bench = manifest.benchmark()
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in {
            "device_trace", "program_span", "program_counter", "host_clock"
        }
        assert set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "bound", "workloads"
        }
        assert set(m.get("workloads", cells)) <= set(cells)
    for w in bench["workloads"]:
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    reduced = {
        "flagship-p3": [],
        "k-exaone-ep8": ["num_hidden_layers", "num_experts"],
        "ling-flash-ep4": ["num_hidden_layers", "first_k_dense_replace", "num_experts"],
        "glm-flash-ep8": ["num_hidden_layers", "n_routed_experts"],
        CONFIG: REDUCED,
    }
    for c in bench["configs"]:
        assert name.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        assert all(name.match(k) for k in c["reduced"])
        if c["name"] in reduced:
            assert c["reduced"] == reduced[c["name"]], c["name"]
    assert set(reduced) <= {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len((manifest.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_the_programs_trunk_takes_the_files_keys_and_counts_its_bytes():
    """Issue 38's arithmetic, to the parameter."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.trunk import param_shapes

    shapes = param_shapes(TrunkConfig(**plain.trunk_settings(config_file())))
    size = lambda i: sum(  # noqa: E731
        int(np.prod(shape)) for name, (shape, _) in shapes.items()
        if name.startswith(f"l{i}_")
    )
    assert size(0) == 109_640_064  # a Mamba-2 mixer with its norm
    assert size(0) == (
        4096 * (2 * 8192 + 2 * 8 * 128 + 128) + 8192 * 4096 + 10240 * 4 + 10240
        + 3 * 128 + 8192 + 4096
    )
    assert size(7) == 35_655_680  # the attention layer: no q/k norm
    expert = 2 * 1024 * 2688
    assert expert == 5_505_024
    assert size(1) == 54_530_560 + 128 * expert == 759_173_632
    assert sum(int(np.prod(shape)) for shape, _ in shapes.values()) == 4_379_728_256
    float32 = {name.split("_", 1)[1] for name, (_, fan_in) in shapes.items()
               if fan_in == -1 or isinstance(fan_in, str)}
    assert float32 == {"A_log", "D", "dt_bias", "router_bias"}


def test_flops_against_hand_figures_and_the_programs():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.utils.flops import forward_flops as the_programs

    cfg = config_file()
    t = plain.trunk_settings(cfg)
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(update={"TRUNK": TrunkConfig(**t)})
    even = flops_nemotron_h.even_assignments(cfg)
    assert even == 252 * 5 * 22 * 128 / 512
    assert the_programs(model, configs["env"], 756) == flops_nemotron_h.forward_flops(
        cfg, even
    )
    # A Mamba-2 mixer a token: W_in 4096 x 18560, W_out 8192 x 4096, 4
    # taps on 10240 channels, the state written and read: 2 x 2 x 64 x
    # 128 a head.
    mamba = 2 * (4096 * 18560 + 8192 * 4096) + 2 * 4 * 10240 + 128 * 2 * 2 * 64 * 128
    assert flops_nemotron_h.mamba_mixer_flops(t) == mamba == 223_428_608
    pairs = flops_exaone_moe.seen_keys(252, None)
    assert pairs == 252 * 253 // 2
    attention_token = 2 * (4096 * (4096 + 2 * 256) + 4096 * 4096)
    assert flops_nemotron_h.attention_flops(t, 252) == (
        252 * attention_token + 2 * 2 * 4096 * pairs
    )
    expert = 2 * 2 * 1024 * 2688
    assert flops_nemotron_h.expert_flops(t) == expert == 11_010_048
    fixed = 2 * 4096 * 512 + 2 * 2 * 4096 * 1024 + 2 * 2 * 4096 * 5376
    assert flops_nemotron_h.expert_layer_fixed_flops(t) == fixed == 109_051_904
    per_token = 5 * mamba + attention_token + 2 * 2 * 4096 * 126.5 + 5 * fixed
    assert flops_nemotron_h.trunk_fixed_flops(t, 252) == pytest.approx(
        252 * per_token, rel=1e-9
    )
    whole = flops_nemotron_h.forward_flops(cfg, even)
    assert whole == pytest.approx(514.6e9, rel=1e-3)  # 2.042 GFLOP a token x 252
    # Where the work is, if routing is even: the Mamba-2 mixers 55 %,
    # the shared experts 22 %, the routed experts held here 15 %.
    assert 252 * 5 * mamba / whole == pytest.approx(0.547, abs=2e-3)
    assert 252 * 5 * 2 * 2 * 4096 * 5376 / whole == pytest.approx(0.216, abs=2e-3)
    assert even * expert / whole == pytest.approx(0.148, abs=2e-3)


# --- the program against the reference, tiny ---------------------------------


@pytest.fixture(scope="module")
def world():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.network import NeuralNetwork

    cfg = tiny_ssm_cfg(config_file())
    configs = manifest.program_configs(cfg)
    t = plain.trunk_settings(cfg)
    model = configs["model"].model_copy(update={"TRUNK": TrunkConfig(**t)})
    net = NeuralNetwork(model, configs["env"], seed=3)
    rng = np.random.default_rng(0)
    grid = rng.integers(-1, 2, (6, 1, 3, 4)).astype(np.float32)
    other = rng.random((6, model.OTHER_NN_INPUT_FEATURES_DIM)).astype(np.float32)
    return {
        "cfg": cfg, "configs": {**configs, "model": model}, "net": net, "t": t,
        "trunk": model.TRUNK, "grid": grid, "other": other,
    }


def _tokens(world, seq=12, boards=3, seed=4):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(boards, seq, world["t"]["hidden_size"])),
        jnp.float32,
    )


@pytest.mark.parametrize(
    "layer,seq", [(0, 12), (1, 12), (7, 12), (0, 29), (7, 29), (10, 29)]
)
def test_each_layer_against_the_reference(world, layer, seq):
    """Layer 0 is a Mamba-2 mixer (the chunked scan against the
    reference's token-by-token one; 29 tokens are four chunks of 8, the
    last short), layers 1 and 10 routed experts in the latent, layer 7
    the attention; each one half under one norm."""
    from alphatriangle_tpu.nn import trunk as program

    t = world["t"]
    params = world["net"].variables["params"]["DecoderTrunk_0"]
    p = plain.layer_weights(params, layer)
    assert sum(name.endswith("_norm") and name != "gated_norm" for name in p) == 1
    x = _tokens(world, seq)
    got, sizes, _ = program.decoder_layer(p, x, world["trunk"], layer, jnp.float32)
    want = plain.layer(p, x, t, layer, None)
    assert float(jnp.abs(got - want).max()) < LAYER_TOLERANCE
    assert float(jnp.abs(want - x).max()) > 1e-1  # the layer did something
    assert (sizes is None) == (t["mlp_layer_types"][layer] == "none")


@pytest.mark.parametrize(
    "layer,left_out",
    [(0, "D"), (0, "conv_bias"), (0, "dt_bias"), (1, "w_latent_up"), (1, "s_down")],
)
def test_a_path_that_leaves_a_part_out_fails_the_comparison(world, layer, left_out):
    """The seeded weights give each part a say: with D, the
    convolution's bias, the step's bias, the latent's up-projection
    (the routed experts whole) or the shared expert at nought, the
    layer's output lies a thousand tolerances off."""
    t = world["t"]
    params = world["net"].variables["params"]["DecoderTrunk_0"]
    p = plain.layer_weights(params, layer)
    x = _tokens(world)
    want = plain.layer(p, x, t, layer, None)
    without = plain.layer({**p, left_out: jnp.zeros_like(p[left_out])}, x, t, layer, None)
    assert float(jnp.abs(without - want).max()) > 1000 * LAYER_TOLERANCE


def test_bfloat16_where_the_file_says_float32_fails_the_comparison(world):
    """The steps, decays and states are float32 in the file's
    `precision`: with the step and the log decay rounded to bfloat16
    before the scan, a Mamba-2 layer's output over a dozen tokens is
    off by ten tolerances and more (seen: 25); so is the whole net
    computed in bfloat16."""
    from alphatriangle_tpu.nn import state_space, trunk as program
    from alphatriangle_tpu.nn.network import NeuralNetwork

    rounded = lambda v: v.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    exact = state_space.chunked

    def lossy(x, step, log_a, *rest):
        return exact(x, rounded(step), rounded(log_a), *rest)

    params = world["net"].variables["params"]["DecoderTrunk_0"]
    p, x = plain.layer_weights(params, 0), _tokens(world)
    want = plain.layer(p, x, world["t"], 0, None)
    monkey = pytest.MonkeyPatch()
    monkey.setattr(state_space, "chunked", lossy)
    try:
        got, _, _ = program.decoder_layer(p, x, world["trunk"], 0, jnp.float32)
    finally:
        monkey.undo()
    assert float(jnp.abs(got - want).max()) > 10 * LAYER_TOLERANCE

    model = world["configs"]["model"].model_copy(update={"COMPUTE_DTYPE": "bfloat16"})
    net = NeuralNetwork(model, world["configs"]["env"], variables=world["net"].variables)
    probs, _ = net.evaluate_features(world["grid"], world["other"])
    exact_probs, _ = world["net"].evaluate_features(world["grid"], world["other"])
    assert np.abs(np.log(probs) - np.log(exact_probs)).max() > 10 * LOGIT_TOLERANCE


def test_logits_through_the_network_wrapper(world):
    net, cfg = world["net"], world["cfg"]
    probs, values = net.evaluate_features(world["grid"], world["other"])
    logits, value_logits = plain.forward(
        net.variables["params"], cfg, world["grid"], world["other"]
    )
    want = jax.nn.log_softmax(logits, axis=-1)
    assert np.abs(np.log(probs) - np.asarray(want)).max() < LOGIT_TOLERANCE
    support = np.linspace(
        cfg["model"]["VALUE_MIN"], cfg["model"]["VALUE_MAX"],
        cfg["model"]["NUM_VALUE_ATOMS"],
    )
    want_value = (np.asarray(jax.nn.softmax(value_logits, axis=-1)) * support).sum(-1)
    assert np.abs(values - want_value).max() < LOGIT_TOLERANCE
    # The control is another net: fp8 moves the logits past any rounding.
    rounded, _ = plain.forward(
        net.variables["params"], cfg, world["grid"], world["other"], reference.fp8
    )
    assert float(jnp.abs(rounded - logits).max()) > 10 * LOGIT_TOLERANCE


def test_one_dispatch_end_to_end_and_the_control_in_its_place():
    """`play_moves_device` through the cell's own driver and comparison:
    the program is correct; the fp8 net in its place is not."""
    result = run.run_cell(tiny_ssm_cell(), SEED, 0.3, False, require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["window_compiles"]["value"] == 0
    assert set(result["metrics"]) == {"selfplay_moves_per_s", "setup_s"}

    from chipbench.spans import Spans

    cell = tiny_ssm_cell()
    driver = rollout_ssm.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    driver.start_window()
    driver.unit()
    driver.release()
    limits = {k: v for k, v in cell["limits"].items() if k != "window_compiles"}
    assert run.compare(driver.check(), limits)[0]
    assert driver.read["roots"] > 0 and len(driver.read["routed_here_by_layer"]) == 5
    assert not run.compare(driver.check(quant=reference.fp8), limits)[0]
    counters = driver.counters()
    tokens = np.asarray(counters["expert_tokens"])
    assert tokens.shape == (5, 2) and tokens.sum() > 0
    assert 0 < tokens.sum() <= counters["routed"]
    # Every evaluation's 12 tokens went through the five Mamba-2 layers
    # and were routed three ways in each of the five expert layers.
    assert counters["ssm_tokens"] == counters["routed"] // 3 > 0
    fixed = flops_nemotron_h.forward_fixed_flops(cell["config_file"])
    assert counters["forward_flops"] > fixed
    ctx = {"window_s": 2.0, "counters": counters}
    assert manifest.layer_reader("ssm_tokens_per_s")(ctx) == counters["ssm_tokens"] / 2.0
    assert manifest.layer_reader("ssm_tokens_per_s")({**ctx, "counters": {}}) is None
    for name in ("expert_assignments_per_s", "routed_here_share"):
        assert manifest.layer_reader(name)(ctx) > 0


# --- the share and the model --------------------------------------------------


def _whole_layer_weights(world, key=11):
    """Layer 1's weights with all 8 experts: the share's router, latent
    projections and shared expert, and experts drawn afresh so that each
    of the 4 shares holds 2 of them."""
    t = world["t"]
    trunk = world["net"].variables["params"]["DecoderTrunk_0"]
    p = plain.layer_weights(trunk, 1)
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    lat, im = t["moe_latent_size"], t["moe_intermediate_size"]
    p["e_up"] = jax.random.normal(keys[0], (8, lat, im)) / np.sqrt(lat)
    p["e_down"] = jax.random.normal(keys[1], (8, im, lat)) / np.sqrt(im)
    # A bias that moves choices, as a balanced checkpoint's would.
    p["router_bias"] = 0.05 * jax.random.normal(keys[2], (8,))
    return t, p, _tokens(world, boards=8)


def test_the_shares_add_up_to_the_uncut_layer(world):
    """8 experts as 4 shares of 2, top 3: the program's routed parts of
    the four shares, each projected up from the latent by its own chip,
    plus the shared expert once, are the reference's uncut layer."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    uncut = plain.experts(p, x, t, None, held=(0, 8))
    flat = x.reshape(-1, x.shape[-1])
    parts = jnp.zeros_like(flat)
    here = []
    for chip in range(4):
        cfg = TrunkConfig(**{**t, "experts_held": (2 * chip, 2)})
        mine = {**p, **{k: p[k][2 * chip : 2 * chip + 2] for k in ("e_up", "e_down")}}
        out, sizes = program.sparse_mlp(
            {**mine, "s_down": jnp.zeros_like(p["s_down"])}, x, cfg, jnp.float32
        )
        chosen, _ = program.route(p, flat, cfg, jnp.float32)
        assert int(sizes.sum()) == int(((chosen // 2) == chip).sum())
        here.append(int(sizes.sum()))
        parts = parts + out.reshape(flat.shape)
    assert sum(here) == 3 * flat.shape[0] and min(here) > 0
    shared = plain.relu2_mlp(flat, p["s_up"], p["s_down"], None)
    assert np.abs(
        np.asarray(parts + shared) - np.asarray(uncut.reshape(flat.shape))
    ).max() < LAYER_TOLERANCE


def test_the_programs_choice_is_the_references_sort(world):
    """Both sides on the same scores, with a bias: the same experts in
    the same order, and the raw scores' weights."""
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    flat = x.reshape(-1, x.shape[-1])
    chosen, weight = program.route(p, flat, world["trunk"], jnp.float32)
    want, want_weight = plain.route(p, flat, t, None)
    assert chosen.shape == (flat.shape[0], 3)
    assert (np.asarray(chosen) == np.asarray(want)).all()
    assert np.abs(np.asarray(weight) - np.asarray(want_weight)).max() < 1e-5
    assert np.allclose(np.asarray(weight).sum(axis=-1), 5.0, atol=1e-5)


# --- the routers' selection biases ---------------------------------------------


def test_the_balancing_rule_evens_a_router_that_sends_all_cells_one_way():
    """Scores whose spread over the experts is a hundred times their
    spread over the tokens: unbiased, every token picks the same 3 of
    16; the bias the rule rests at gives each expert its share. The
    count by the token's bar is the count of the chosen."""
    key = jax.random.PRNGKey(0)
    scores = jax.nn.sigmoid(
        2.0 * jax.random.normal(key, (16,))
        + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (4096, 16))
    )
    before = np.asarray(router_balance_ssm.loads(scores, 3))
    assert before.max() == 4096 and before.sum() == 3 * 4096
    _, chosen = jax.lax.top_k(scores, 3)
    assert (before == np.bincount(np.asarray(chosen).reshape(-1), minlength=16)).all()
    bias = router_balance_ssm.balanced_bias(scores, 3)
    assert bias.dtype == jnp.float32
    after = np.asarray(router_balance_ssm.loads(scores + bias, 3))
    # Biases that are sums of the rule's few steps leave a token or two
    # whose bar two experts reach alike: counted twice, chosen once.
    assert 0 <= after.sum() - 3 * 4096 < 16 and after.max() / after.mean() < 1.05


def test_balancing_sets_the_biases_and_nothing_else(world):
    """By the reference's layers alone; the program, handed the tree,
    then loads this share (2 of 8 experts, top 3) with a quarter of the
    sample's assignments in all five expert layers."""
    from alphatriangle_tpu.nn.trunk import counters_of

    net, cfg, configs = world["net"], world["cfg"], world["configs"]
    params = net.variables["params"]
    rng = np.random.default_rng(0)
    grid = rng.integers(-1, 2, (64, 1, 3, 4)).astype(np.float32)
    balanced = router_balance_ssm.balance(params, cfg, grid, block=16)
    before, after = params["DecoderTrunk_0"], balanced["DecoderTrunk_0"]
    for name in before:
        if name.endswith("router_bias"):
            assert after[name].dtype == jnp.float32
            assert float(jnp.abs(after[name]).max()) > 0
        else:
            assert after[name] is before[name]
    assert all(balanced[k] is params[k] for k in params if k != "DecoderTrunk_0")

    other = np.zeros((64, configs["model"].OTHER_NN_INPUT_FEATURES_DIM), np.float32)
    _, state = net.model.apply(
        {"params": balanced}, grid, other, train=False, mutable=["counters"]
    )
    counted = counters_of(state)
    share = np.asarray(5 * counted["expert_tokens"].sum(axis=1) / counted["routed"])
    assert np.abs(share - 0.25).max() < 0.04, share
    assert int(counted["ssm_tokens"]) == 64 * 12 * 5


# --- the parent ------------------------------------------------------------------


def test_a_program_without_these_layers_is_refused_at_once(monkeypatch):
    """The parent's `TrunkConfig` knows no state-space layer and no
    layer of one half: the driver exits before anything is built."""
    from typing import Literal

    from pydantic import BaseModel

    import alphatriangle_tpu.config as config
    from chipbench.spans import Spans

    class ParentsTrunkConfig(BaseModel):
        hidden_size: int
        layer_types: list[Literal["sliding_attention", "full_attention",
                                  "linear_attention", "latent_attention"]]
        mlp_layer_types: list[Literal["dense", "sparse"]]

    monkeypatch.setattr(config, "TrunkConfig", ParentsTrunkConfig)
    cell = tiny_ssm_cell()
    with pytest.raises(SystemExit, match="state_space"):
        rollout_ssm.Driver(cell, {}, SEED, Spans())
