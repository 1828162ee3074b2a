"""`ling-flash-ep4` / `ling-flash-rollout`: the configuration's file
against the catalog's published keys, the FLOP count against hand
figures and the program's, and at a tiny size on the CPU the program
against the plain reference (`reference_ling_hybrid`): each mixer and
the whole forward (logits, not choices), one dispatch of the cell end
to end with the control in the program's place, the shares of the
experts adding up to the uncut layer, the grouped choice, the routers'
balancing under it, and the new reader.

Tolerances. The tiny net computes in float32 on both sides, so the two
differ by summation order (and, in the linear layers, by the chunked
form against the token-by-token one): logits of size about 1 agree to
1e-3 (seen: 4e-6; the stack is pre-norm, so nothing renormalises a
rounding).
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_hybrid_cell import tiny_hybrid_cell, tiny_hybrid_cfg

from chipbench import (
    flops_exaone_moe, flops_ling_hybrid, manifest, reference,
    router_balance_hybrid, run,
)
from chipbench import reference_ling_hybrid as plain
from chipbench.drivers import rollout_hybrid

SEED = 2**31 + 32
LOGIT_TOLERANCE = 1e-3
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

# The `config` of the catalog's row Ling-3.0-flash
# (model-configs/architectures.jsonl), copied: the two limit lists are
# the 42 published entries, written by their runs.
CATALOG = {
    "expert_swiglu_limit_list": [0] * 35 + [4] * 7,
    "first_k_dense_replace": 2,
    "gated_attention_proj_granularity_type": "head_wise",
    "group_norm_size": 1,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 2560,
    "intermediate_size": 6144,
    "kda_lower_bound": -5,
    "kda_safe_gate": True,
    "kv_lora_rank": 512,
    "layer_group_size": 6,
    "linear_silu": True,
    "max_position_embeddings": 262144,
    "max_window_layers": 20,
    "moe_intermediate_size": 768,
    "moe_router_enable_expert_bias": True,
    "moe_shared_expert_intermediate_size": 768,
    "mtp_loss_scaling_factor": 0,
    "mtp_use_kda": False,
    "n_group": 8,
    "no_kda_lora": True,
    "norm_topk_prob": True,
    "num_attention_heads": 32,
    "num_experts": 512,
    "num_experts_per_tok": 8,
    "num_hidden_layers": 42,
    "num_key_value_heads": 32,
    "num_kv_heads_for_linear_attn": 0,
    "num_nextn_predict_layers": 1,
    "num_shared_experts": 1,
    "partial_rotary_factor": 0.5,
    "q_lora_rank": None,
    "qk_head_dim": 192,
    "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_interleave": True,
    "rope_scaling": None,
    "rope_theta": 6000000,
    "rotary_dim": 64,
    "routed_scaling_factor": 2.5,
    "scale_router_input": False,
    "score_function": "sigmoid",
    "scoring_func": "sigmoid",
    "seq_aux": True,
    "share_expert_swiglu_limit_list": [0] * 34 + [5] * 6 + [7] * 2,
    "short_conv_kernel_size": 4,
    "tie_word_embeddings": False,
    "topk_group": 4,
    "topk_method": "noaux_tc",
    "up_proj_norm": False,
    "use_bias": False,
    "use_kda_lora": False,
    "use_mla_nope": False,
    "use_nGPT": False,
    "use_qk_norm": True,
    "use_qkv_bias": False,
    "v_head_dim": 128,
    "value_norm": False,
    "vocab_size": 157184,
    "model_type": "bailing_hybrid",
}
SOURCE = "https://huggingface.co/inclusionAI/Ling-3.0-flash/blob/main/config.json"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts"]


def config_file() -> dict:
    return manifest.load_json(manifest.HERE / "configs" / "ling-flash-ep4.json")


# --- the configuration's file ------------------------------------------------


def test_every_published_key_stands_unchanged_but_the_three_reduced():
    cfg = config_file()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    differ = {k for k, v in CATALOG.items() if k not in cfg or cfg[k] != v}
    assert differ == set(REDUCED)
    assert cfg["published"] == {
        "num_hidden_layers": 42, "first_k_dense_replace": 2, "num_experts": 512
    }
    assert [cfg[k] for k in REDUCED] == [7, 1, 128]
    entry = next(
        c for c in manifest.benchmark()["configs"] if c["name"] == "ling-flash-ep4"
    )
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "chipbench/configs/ling-flash-ep4.json"
    # No width is among the reduced, nor differs: the guide's rule.
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) or k == "num_experts_per_tok"
        for k in REDUCED
    )
    # The guide's floors: a whole period (five linear to one latent)
    # after the leading dense layer, at least 8 routed experts held.
    t = plain.trunk_settings(cfg)
    assert t["layer_types"] == (
        ["linear_attention"] * 5 + ["latent_attention", "linear_attention"]
    )
    assert t["mlp_layer_types"] == ["dense"] + ["sparse"] * 6
    assert t["experts_held"] == [0, 128] and t["num_experts"] == 512
    assert (t["n_group"], t["topk_group"], t["num_experts_per_tok"]) == (8, 4, 8)


def test_the_copy_of_the_row_is_the_catalogs_where_the_catalog_is_at_hand():
    try:
        rows = [json.loads(line) for line in open(ROW)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Ling-3.0-flash")
    assert row["config"] == CATALOG and row["source_url"] == SOURCE


def test_the_file_states_deployment_choices_assumptions_and_departures():
    cfg = config_file()
    assert cfg["deployment"]["expert_parallel"] == 4 and cfg["deployment"]["chip"] == 0
    assert "4 chips share each layer" in cfg["deployment"]["stated"]
    assert cfg["trunk_choices"] == {
        "norm_position": "pre", "qk_norm": "l2", "rope_layers": "latent",
        "router_bias": True, "block_boards": cfg["trunk_choices"]["block_boards"],
        "linear_chunk": 64,
    }
    assert set(cfg["trunk_choices"]) - {"rope_layers"} | {
        "layer_placement", "decay", "rope", "gates", "absent", "router", "board",
        "mcts", "weights", "short_conv", "mla_form",
    } <= set(cfg["assumed"])
    assert set(cfg["departures"]) >= {
        "embedding", "output_head", "mtp", "swiglu_limits", "decoding"
    }
    # The clamps that are not run are nought on every layer kept.
    depth = cfg["num_hidden_layers"]
    assert not any(cfg["expert_swiglu_limit_list"][:depth])
    assert not any(cfg["share_expert_swiglu_limit_list"][:depth])
    assert (cfg["env"]["ROWS"], cfg["env"]["COLS"], cfg["action_dim"]) == (12, 21, 756)
    assert cfg["model"]["PARAM_DTYPE"] == cfg["model"]["INFERENCE_PRECISION"] == "bfloat16"
    k_exaone = manifest.load_json(manifest.HERE / "configs" / "k-exaone-ep8.json")
    for group in ("env", "model", "train", "mcts"):
        assert cfg[group] == k_exaone[group], group


def test_the_cell_and_its_metrics_are_entries_at_the_end_of_their_lists():
    bench = manifest.benchmark()
    assert bench["configs"][-1]["name"] == "ling-flash-ep4"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "ling-flash-rollout", "ling-flash-ep4", "rollout-t1-hybrid", 1
    )
    assert len(cell["why"]) <= 200 and len(bench["configs"][-1]["why"]) <= 200
    assert bench["per_layer"][-1]["name"] == "linear_tokens_per_s"
    assert bench["per_layer"][-1]["workloads"] == ["ling-flash-rollout"]
    reported = {m["name"] for m in manifest.metrics_of("ling-flash-rollout", True)}
    assert reported == {
        "compile_s", "host_gap_ms.rollout", "leaf_evals_per_s", "chunk_device_ms",
        "ingest_ms.rollout", "ingest_wait_ms.rollout", "ingest_tree_ms.rollout",
        "mfu.rollout", "device_idle_share.rollout", "expert_assignments_per_s",
        "expert_load_max_over_mean", "routed_here_share", "linear_tokens_per_s",
    }
    assert {m["name"] for m in manifest.metrics_of("ling-flash-rollout", False)} == {
        "selfplay_moves_per_s", "setup_s"
    }
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "ling-flash-rollout" in m.get("workloads", []):
            assert m["workloads"][-1] == "ling-flash-rollout"
    traffic = manifest.cell("ling-flash-rollout")["traffic_file"]
    t1 = manifest.cell("k-exaone-rollout")["traffic_file"]
    same = {"driver", "check", "loop", "candidate_margin"}
    assert {k: v for k, v in traffic.items() if k not in same} == {
        k: v for k, v in t1.items() if k not in same
    }
    assert traffic["driver"] == "rollout_hybrid"


def test_names_units_and_lines_with_the_reduced_configurations():
    """`test_chipbench_manifest.py::test_names_units_and_lines` and
    `test_chipbench_k_exaone.py::test_names_units_and_lines_with_a_reduced_configuration`
    hold every configuration but the ones they know to `reduced == []`
    and fail since this cell's is a cut of a published model
    (CHANGES.md, PR 32: those files are the benchmark's and are not this
    PR's to edit). The same lines here, each accepted configuration held
    to its own names."""
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
        "k-exaone-ep8": ["num_hidden_layers", "num_experts"],
        "ling-flash-ep4": REDUCED,
    }
    for c in bench["configs"]:
        assert name.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        assert c["reduced"] == reduced.get(c["name"], []), c["name"]
        assert all(name.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len((manifest.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def test_the_programs_trunk_takes_the_files_keys_and_counts_its_bytes():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.trunk import param_shapes

    trunk = TrunkConfig(**plain.trunk_settings(config_file()))
    count = sum(int(np.prod(shape)) for shape, _ in param_shapes(trunk).values())
    assert count == pytest.approx(4.968e9, rel=1e-3)  # 9.94 GB bfloat16


def test_flops_against_hand_figures_and_the_programs():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.utils.flops import forward_flops as the_programs

    cfg = config_file()
    t = plain.trunk_settings(cfg)
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(update={"TRUNK": TrunkConfig(**t)})
    even = flops_ling_hybrid.even_assignments(cfg)
    assert even == 252 * 6 * 8 * 128 / 512
    assert the_programs(model, configs["env"], 756) == flops_ling_hybrid.forward_flops(
        cfg, even
    )
    # A KDA mixer a token: q, k, v, f, o at 2 x 10.49M, the two
    # head-wise projections, three convolutions of 4 taps, and the
    # state read, written, read: 3 x 2 x 128 x 128 a head.
    kda = (
        5 * 2 * 2560 * 4096 + 2 * 2 * 2560 * 32 + 3 * 2 * 4 * 4096
        + 32 * 3 * 2 * 128 * 128
    )
    assert flops_ling_hybrid.linear_mixer_flops(t) == kda == 108_429_312
    mla_token = 2 * (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    )
    pairs = flops_exaone_moe.seen_keys(252, None)
    assert pairs == 252 * 253 // 2
    assert flops_ling_hybrid.latent_mixer_flops(t, 252) == (
        252 * mla_token + 2 * 32 * (192 + 128) * pairs
    )
    expert = 2 * 3 * 2560 * 768
    assert flops_exaone_moe.expert_flops(t) == expert == 11_796_480
    per_token = (
        6 * kda + mla_token + 2 * 32 * 320 * 126.5
        + 2 * 3 * 2560 * 6144 + 6 * (expert + 2 * 2560 * 512)
    )
    assert flops_ling_hybrid.trunk_fixed_flops(t, 252) == pytest.approx(
        252 * per_token, rel=1e-9
    )
    whole = flops_ling_hybrid.forward_flops(cfg, even)
    assert whole == pytest.approx(262.6e9, rel=1e-3)  # 1.042 GFLOP a token x 252
    # Where the work is, if routing is even: the linear mixers 62 %.
    assert 252 * 6 * kda / whole == pytest.approx(0.624, abs=2e-3)
    assert even * expert / whole == pytest.approx(0.136, abs=2e-3)


# --- the program against the reference, tiny ---------------------------------


@pytest.fixture(scope="module")
def world():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.network import NeuralNetwork

    cfg = tiny_hybrid_cfg(config_file())
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


@pytest.mark.parametrize("layer,seq", [(0, 12), (1, 12), (2, 12), (0, 41), (2, 41)])
def test_each_mixer_against_the_reference(world, layer, seq):
    """Layers 0-1 are linear (the chunked recurrence against the
    reference's token-by-token scan; 41 tokens are three chunks of 16,
    the last short), layer 2 latent."""
    from alphatriangle_tpu.nn import trunk as program

    t = world["t"]
    params = world["net"].variables["params"]["DecoderTrunk_0"]
    p = plain.layer_weights(params, layer)
    x = _tokens(world, seq)
    got = program.attention_block(p, x, world["trunk"], layer, jnp.float32)
    want = plain.mixer_half(p, x, t, layer, None)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want - x).max()) > 1e-2  # the mixer did something


def test_the_decay_lies_between_the_lower_bound_and_nought(world):
    from alphatriangle_tpu.nn import trunk as program

    params = world["net"].variables["params"]["DecoderTrunk_0"]
    g = program.log_decay(
        plain.layer_weights(params, 0), _tokens(world), world["trunk"], jnp.float32
    )
    assert g.shape == (3, 12, 4, 16)
    assert float(g.min()) > -5.0 and float(g.max()) < 0.0
    assert float(g.min()) < -4.0 and float(g.max()) > -1.0  # and uses the range


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
    result = run.run_cell(tiny_hybrid_cell(), SEED, 0.3, False, require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["window_compiles"]["value"] == 0
    assert set(result["metrics"]) == {"selfplay_moves_per_s", "setup_s"}

    from chipbench.spans import Spans

    cell = tiny_hybrid_cell()
    driver = rollout_hybrid.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    driver.start_window()
    driver.unit()
    driver.release()
    limits = {k: v for k, v in cell["limits"].items() if k != "window_compiles"}
    assert run.compare(driver.check(), limits)[0]
    assert driver.read["roots"] > 0 and len(driver.read["routed_here_by_layer"]) == 2
    assert not run.compare(driver.check(quant=reference.fp8), limits)[0]
    counters = driver.counters()
    tokens = np.asarray(counters["expert_tokens"])
    assert tokens.shape == (2, 2) and tokens.sum() > 0
    assert 0 < tokens.sum() <= counters["routed"]
    # Every evaluation's 12 tokens went through the two linear layers
    # and were routed twice over in the two sparse ones.
    assert counters["linear_tokens"] == counters["routed"] // 2 > 0
    fixed = flops_ling_hybrid.forward_fixed_flops(cell["config_file"])
    assert counters["forward_flops"] > fixed
    ctx = {"window_s": 2.0, "counters": counters}
    assert manifest.layer_reader("linear_tokens_per_s")(ctx) == (
        counters["linear_tokens"] / 2.0
    )
    for name in ("expert_assignments_per_s", "routed_here_share"):
        assert manifest.layer_reader(name)(ctx) > 0


# --- the share and the model --------------------------------------------------


def _whole_layer_weights(world, key=11):
    """Layer 1's weights with all 8 experts: the share's router, and
    experts drawn afresh so that each of the 4 shares holds 2 of them."""
    t = world["t"]
    trunk = world["net"].variables["params"]["DecoderTrunk_0"]
    p = plain.layer_weights(trunk, 1)
    keys = jax.random.split(jax.random.PRNGKey(key), 4)
    d, im = t["hidden_size"], t["moe_intermediate_size"]
    p["e_gate"] = jax.random.normal(keys[0], (8, d, im)) / np.sqrt(d)
    p["e_up"] = jax.random.normal(keys[1], (8, d, im)) / np.sqrt(d)
    p["e_down"] = jax.random.normal(keys[2], (8, im, d)) / np.sqrt(im)
    # A bias that moves choices, as a balanced checkpoint's would.
    p["router_bias"] = 0.05 * jax.random.normal(keys[3], (8,))
    return t, p, _tokens(world, boards=8)


def test_the_shares_add_up_to_the_uncut_layer(world):
    """8 experts in 2 groups as 4 shares of 2: the programs' routed
    parts of the four shares, plus the shared expert once, are the
    reference's uncut layer output."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    uncut = plain.sparse_mlp(p, x, t, None, held=(0, 8))
    flat = x.reshape(-1, x.shape[-1])
    parts = jnp.zeros_like(flat)
    here = []
    for chip in range(4):
        cfg = TrunkConfig(**{**t, "experts_held": (2 * chip, 2)})
        mine = {
            **p,
            **{k: p[k][2 * chip : 2 * chip + 2] for k in ("e_gate", "e_up", "e_down")},
        }
        chosen, weight = program.route(p, flat, cfg, jnp.float32)
        routed, sizes = program.routed_experts(
            mine, flat, chosen, weight, cfg, jnp.float32
        )
        assert int(sizes.sum()) == int(((chosen // 2) == chip).sum())
        here.append(int(sizes.sum()))
        parts = parts + routed
    # One group of two stays: a token's two experts lie in one group,
    # shares 0-1 or shares 2-3, and both groups are chosen by some.
    assert sum(here) == 2 * flat.shape[0]
    assert here[0] + here[1] > 0 and here[2] + here[3] > 0
    shared = plain.swiglu(flat, p["s_gate"], p["s_up"], p["s_down"], None)
    assert np.abs(
        np.asarray(parts + shared) - np.asarray(uncut.reshape(flat.shape))
    ).max() < 1e-4


def test_a_share_whose_groups_were_not_chosen_adds_the_shared_expert_alone(world):
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    cfg = TrunkConfig(**{**t, "experts_held": (6, 2)})  # the second group's end
    mine = {**p, **{k: p[k][6:8] for k in ("e_gate", "e_up", "e_down")}}
    out, _ = program.sparse_mlp(mine, x, cfg, jnp.float32)
    chosen, _ = program.route(p, x.reshape(-1, x.shape[-1]), cfg, jnp.float32)
    first_group = np.asarray((chosen < 4).all(axis=-1))
    assert first_group.any() and not first_group.all()
    shared = program.swiglu(x, p["s_gate"], p["s_up"], p["s_down"], jnp.float32)
    flat_out, flat_shared = (np.asarray(a).reshape(-1, x.shape[-1]) for a in (out, shared))
    assert (flat_out[first_group] == flat_shared[first_group]).all()
    assert (flat_out != flat_shared).any()


def test_the_programs_choice_is_the_references_sort(world):
    """Both sides on the same scores, with a bias: the same experts in
    the same order, and the raw scores' weights."""
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    flat = x.reshape(-1, x.shape[-1])
    chosen, weight = program.route(p, flat, world["trunk"], jnp.float32)
    want, want_weight = plain.route(p, flat, t, None)
    assert (np.asarray(chosen) == np.asarray(want)).all()
    assert np.abs(np.asarray(weight) - np.asarray(want_weight)).max() < 1e-5
    assert np.allclose(np.asarray(weight).sum(axis=-1), 2.5, atol=1e-5)
    # Both chosen experts of a token stand in one group of four.
    assert (np.asarray(chosen)[:, 0] // 4 == np.asarray(chosen)[:, 1] // 4).all()
    # The balancing's top_k form is the same choice, ties included.
    tied = jnp.round(jax.random.normal(jax.random.PRNGKey(0), (2048, 8)) * 2) / 2 + 0.0
    assert (
        np.asarray(plain.choose(tied, t)) == np.asarray(router_balance_hybrid.choose(tied, t))
    ).all()


# --- the routers' selection biases ---------------------------------------------


def test_the_balancing_rule_evens_a_grouped_router_that_sends_all_cells_one_way():
    """Scores whose spread over the experts is a hundred times their
    spread over the tokens: unbiased, every token picks the same 2 of
    8, in one group; the bias the rule rests at gives each expert, and
    so each group, its share."""
    t = {"n_group": 2, "topk_group": 1, "num_experts_per_tok": 2}
    key = jax.random.PRNGKey(0)
    scores = jax.nn.sigmoid(
        2.0 * jax.random.normal(key, (8,))
        + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (4096, 8))
    )
    before = np.asarray(router_balance_hybrid.loads(scores, t))
    assert before.max() == 4096
    bias = router_balance_hybrid.balanced_bias(scores, t)
    assert bias.dtype == jnp.float32
    after = np.asarray(router_balance_hybrid.loads(scores + bias, t))
    assert after.sum() == 2 * 4096 and after.max() / after.mean() < 1.05
    assert abs(after[:4].sum() / after.sum() - 0.5) < 0.02


def test_balancing_sets_the_biases_and_nothing_else(world):
    """By the reference's layers alone; the program, handed the tree,
    then loads this share (2 of 8 experts, top 2 of one group) with a
    quarter of the sample's assignments in both sparse layers."""
    from alphatriangle_tpu.nn.trunk import counters_of

    net, cfg, configs = world["net"], world["cfg"], world["configs"]
    params = net.variables["params"]
    rng = np.random.default_rng(0)
    grid = rng.integers(-1, 2, (64, 1, 3, 4)).astype(np.float32)
    balanced = router_balance_hybrid.balance(params, cfg, grid, block=16)
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
    share = np.asarray(4 * counted["expert_tokens"].sum(axis=1) / counted["routed"])
    assert np.abs(share - 0.5).max() < 0.06, share  # 2 sparse layers: 4 x / 2
    assert int(counted["linear_tokens"]) == 64 * 12 * 2


# --- the parent ------------------------------------------------------------------


def test_a_program_without_the_hybrid_layers_is_refused_at_once(monkeypatch):
    """The parent's `TrunkConfig` knows two kinds of layer and one
    placement of the norms: the driver exits before anything is built."""
    from typing import Literal

    from pydantic import BaseModel

    import alphatriangle_tpu.config as config
    from chipbench.spans import Spans

    class ParentsTrunkConfig(BaseModel):
        hidden_size: int
        layer_types: list[Literal["sliding_attention", "full_attention"]]
        norm_position: Literal["post"] = "post"

    monkeypatch.setattr(config, "TrunkConfig", ParentsTrunkConfig)
    cell = tiny_hybrid_cell()
    with pytest.raises(SystemExit, match="linear_attention"):
        rollout_hybrid.Driver(cell, {}, SEED, Spans())
