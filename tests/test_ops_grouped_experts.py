"""A round of the routed experts as two kernels (ops/grouped_experts.py),
interpreted on the CPU: against the `jax.lax.ragged_dot` chain the
expert layer runs everywhere else (`nn/trunk.ragged_experts`: the
products, the activation, the casts, the `where` on the rows past the
round as passes of their own), at widths of whole
128-lane blocks; its tile schedule; the choice between the two; and
`routed_experts` through the kernels inside a net. What the TPU's
compiler says of them is tests/test_chip_compile.py's; what they cost
is the chip's to say (PERF.md)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.config import TrunkConfig
from alphatriangle_tpu.nn import trunk
from alphatriangle_tpu.nn.model import AlphaTriangleNet
from alphatriangle_tpu.ops.grouped_experts import (
    column_block,
    expert_path,
    grouped_experts,
    schedule,
)

TM = 128  # rows a tile: a round of 300 rows is 2.34 tiles


def _operands(rows, d, inter, held, gated, scale, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = (scale * jax.random.normal(keys[0], (rows, d))).astype(jnp.bfloat16)

    def weight(key, fan_in, shape):
        return (jax.random.normal(key, shape) * fan_in**-0.5).astype(jnp.bfloat16)

    up = weight(keys[1], d, (held, d, inter))
    down = weight(keys[2], inter, (held, inter, d))
    gate = weight(keys[3], d, (held, d, inter)) if gated else None
    return x, up, down, gate


# (rows, d, inter, sizes, act, limit): act "silu" is gated, "relu2" not;
# every round leaves rows past its assignments but "full".
CASES = {
    "silu-a-half": (300, 128, 256, [33, 40, 12, 35, 30], "silu", None),
    "silu-limit-10-full": (256, 256, 128, [100, 0, 90, 66], "silu", 10.0),
    "relu2-a-quarter": (300, 128, 128, [10, 0, 20, 5, 15, 10, 10, 5], "relu2", None),
    "relu2-empty-experts": (300, 256, 128, [0, 60, 0, 0, 57, 0], "relu2", None),
    "silu-one-expert-takes-all": (300, 128, 128, [0, 0, 170, 0], "silu", None),
    "silu-nothing-assigned": (40, 128, 128, [0, 0, 0], "silu", 10.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernels_are_the_ragged_chain(case):
    """The round's output, to a step of bfloat16 in the largest entry
    (the only freedom: the order of the float32 sums over d and inter,
    which may move the hidden's rounding), and every row past the
    round's assignments nought, the last tile ragged. With limit 10 the
    products reach past it (inputs 8 wide), so the clamp is exercised."""
    rows, d, inter, sizes, act, limit = CASES[case]
    x, up, down, gate = _operands(
        rows, d, inter, len(sizes), act == "silu", 8.0 if limit else 1.0
    )
    sizes = jnp.asarray(sizes, jnp.int32)
    got = grouped_experts(
        x, sizes, up, down, gate, limit=limit, dtype=jnp.bfloat16, interpret=True
    )
    want = trunk.ragged_experts(x, sizes, up, down, gate, limit=limit, dtype=jnp.bfloat16)
    assert got.shape == (rows, d) and got.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    total = int(sizes.sum())
    assert (got[total:] == 0).all()
    assert np.abs(got - want).max() <= 2**-7 * max(np.abs(want).max(), 1e-6)
    if limit:  # the clamp bites: some gate past it
        g = trunk._ragged(x, gate, sizes, jnp.bfloat16)[:total]
        assert total == 0 or float(g.max()) > limit


@pytest.mark.parametrize(
    "sizes",
    [[33, 40, 12, 35, 30], [0, 0, 300, 0], [128, 0, 128, 1, 15, 0], [0, 0, 0], [1] * 8],
)
def test_the_schedule_visits_each_tile_in_turn_and_the_rest_once(sizes):
    """Steps past the assignments keep the last expert and input tile;
    every tile a step writes follows its previous visit at once; the
    tiles past the assignments are each written, by the down call's
    steps past them; the grid is `rows / tm + held - 1` steps."""
    rows, held = 300, len(sizes)
    experts, tiles_in, tiles_out, bounds, active = (
        np.asarray(a)
        for a in jax.jit(schedule, static_argnums=(1, 2))(
            jnp.asarray(sizes, jnp.int32), rows, TM
        )
    )
    tiles = -(-rows // TM)
    assert len(experts) == tiles + held - 1
    n = int(active[0])
    assert bounds.tolist() == [0, *np.cumsum(sizes).tolist()]
    filled = -(-sum(sizes) // TM)
    for i in range(n):  # the expert owns a row of the tile
        e, t = experts[i], tiles_in[i]
        assert bounds[e] < (t + 1) * TM and bounds[e + 1] > t * TM
    assert n == sum(
        (b - 1) // TM - a // TM + 1 for a, b in zip(bounds[:-1], bounds[1:]) if b > a
    )
    assert (experts[n:] == experts[max(n - 1, 0)]).all()
    assert (tiles_in[n:] == tiles_in[max(n - 1, 0)]).all()
    assert (tiles_out[:n] == tiles_in[:n]).all()
    assert sorted(set(tiles_out.tolist())) == list(range(tiles))
    changes = np.flatnonzero(np.diff(tiles_out)) + 1
    assert len(set(tiles_out[np.r_[0, changes]].tolist())) == len(changes) + 1
    assert set(tiles_out[n:].tolist()) >= set(range(filled, tiles))


CELL = dict(  # ling-flash-rollout's round, a block of 64 boards
    partitioned=False, backend="tpu", train=False, rows=64512, d=2560,
    inter=768, gated=True, dtype=jnp.bfloat16,
)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "kernel"),
        ({"rows": 177408, "d": 1024, "inter": 2688, "gated": False}, "kernel"),  # nemotron
        ({"rows": 44352, "d": 1024, "inter": 2688, "gated": False}, "kernel"),  # ragged tile
        ({"rows": 16128, "d": 7168, "inter": 2048}, "kernel"),  # gigachat35
        ({"rows": 32256, "d": 6144, "inter": 2048}, "kernel"),  # k-exaone
        ({"rows": 40, "d": 128, "inter": 128}, "kernel"),  # a TPU at 128
        ({"backend": "cpu"}, "ragged"),
        ({"partitioned": True}, "ragged"),
        ({"train": True}, "ragged"),  # the kernels have no transpose
        ({"d": 96}, "ragged"),  # not whole 128-lane blocks
        ({"inter": 96}, "ragged"),
        ({"dtype": jnp.float32}, "ragged"),
        ({"d": 98304}, "ragged"),  # a step's blocks pass the plan
    ],
)
def test_path_is_chosen_by_what_the_call_observes(change, path):
    assert expert_path(**{**CELL, **change}) == path


@pytest.mark.parametrize(
    "k,n,weights,block",
    [
        (2560, 768, 2, 768), (768, 2560, 1, 2560),  # ling-flash: whole
        (1024, 2688, 1, 2688), (2688, 1024, 1, 1024),  # nemotron: whole
        (7168, 2048, 2, 512), (2048, 7168, 1, 3584),  # gigachat35: cut
        (6144, 2048, 2, 512), (2048, 6144, 1, 3072),  # k-exaone: cut
    ],
)
def test_the_column_block_is_the_widest_the_plan_holds(k, n, weights, block):
    assert column_block(128, k, n, weights, 2) == block


def test_what_the_kernels_cannot_take_is_refused():
    x, up, down, gate = _operands(32, 96, 128, 2, True, 1.0)
    with pytest.raises(ValueError, match="128-lane"):
        jax.eval_shape(
            lambda *a: grouped_experts(*a, dtype=jnp.bfloat16),
            x, jnp.zeros((2,), jnp.int32), up, down, gate,
        )
    x, up, down, gate = _operands(32, 128, 128, 2, True, 1.0)
    with pytest.raises(ValueError, match="float32"):
        jax.eval_shape(
            lambda *a: grouped_experts(*a, dtype=jnp.float32),
            x, jnp.zeros((2,), jnp.int32), up, down, gate,
        )


# Stacks at widths the kernels take: a SwiGLU stack (clamped) and a
# relu^2 stack whose experts live in a latent.
SILU_STACK = dict(
    hidden_size=128, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=128, num_experts=32,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=2.5,
    sliding_window=4, swiglu_limit=10.0,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"], experts_held=(2, 4),
)
RELU2_STACK = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=24, moe_intermediate_size=256, num_experts=8,
    num_experts_per_tok=3, num_shared_experts=1, routed_scaling_factor=5.0,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=8, n_groups=2,
    conv_kernel=4, chunk_size=8, moe_latent_size=128,
    moe_shared_expert_intermediate_size=48, mlp_hidden_act="relu2",
    layer_types=["state_space", "none", "full_attention", "none"],
    mlp_layer_types=["none", "sparse", "none", "sparse"],
    experts_held=(2, 4), norm_position="pre", qk_norm="none", router_bias=True,
)
STACKS = {"silu": SILU_STACK, "relu2_latent": RELU2_STACK}


@pytest.mark.parametrize("name", sorted(STACKS))
def test_routed_experts_through_the_kernels(monkeypatch, name):
    """`routed_experts` on a backend said to be a TPU (the kernels
    interpreted) against its `ragged_dot` path: the held experts' part
    of the routed sum to a step of bfloat16 (the outputs are rounded
    there on both paths) and the same counts. The SwiGLU stack's
    tokens all choose held experts, four rounds of its buffers; the
    relu^2 stack's choose among all eight, one round."""
    cfg = TrunkConfig(**STACKS[name])
    t, k, held = 100, cfg.num_experts_per_tok, cfg.experts_held[1]
    d, inter = cfg.moe_latent_size or cfg.hidden_size, cfg.moe_intermediate_size
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    x, up, down, gate = _operands(t, d, inter, held, cfg.mlp_hidden_act == "silu", 1.0)
    params = {"e_up": up, "e_down": down} | ({"e_gate": gate} if gate is not None else {})
    low = cfg.experts_held[0] if name == "silu" else 0
    high = low + held if name == "silu" else cfg.num_experts
    chosen = jax.random.randint(keys[0], (t, k), low, high)
    weight = jax.random.uniform(keys[1], (t, k))

    def run():  # traced anew each time: the path is read at trace
        return jax.jit(
            lambda p, x, c, w: trunk.routed_experts(p, x, c, w, cfg, jnp.bfloat16)
        )(params, x, chosen, weight)

    ragged, ragged_sizes = run()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "grouped_experts", functools.partial(trunk.grouped_experts, interpret=True)
    )
    assert trunk.routed_path(cfg, x, jnp.bfloat16, False) == "kernel"
    kernel, kernel_sizes = run()
    rows = trunk.round_rows(cfg, t)
    assert (-(-t * k // rows) if name == "silu" else 1) == (4 if name == "silu" else 1)
    np.testing.assert_array_equal(kernel_sizes, ragged_sizes)
    assert bool(jnp.isfinite(kernel).all())
    gap = float(jnp.abs(kernel - ragged).max())
    assert gap <= 2**-7 * float(jnp.abs(ragged).max())


@pytest.mark.parametrize("name", sorted(STACKS))
def test_the_trunk_instant_says_which_path_the_experts_took(
    tiny_model_config, tiny_env_config, monkeypatch, name
):
    """The `net.trunk` instant's `expert_path`: every sparse layer
    `ragged_dot` on the CPU, the kernels on a backend said to be a TPU,
    `ragged_dot` there again in a training forward."""
    model = tiny_model_config.model_copy(
        update={"COMPUTE_DTYPE": "bfloat16", "TRUNK": TrunkConfig(**STACKS[name])}
    )
    module = AlphaTriangleNet(model, tiny_env_config.action_dim)
    grid = jnp.zeros((3, 1, 3, 4))
    other = jnp.zeros((3, model.OTHER_NN_INPUT_FEATURES_DIM))
    shapes = jax.eval_shape(
        functools.partial(module.init, train=False), jax.random.PRNGKey(0), grid, other
    )
    from alphatriangle_tpu.telemetry.tracer import default_tracer

    seen = []
    monkeypatch.setattr(
        default_tracer(), "instant",
        lambda name, **fields: seen.append(fields) if name == "net.trunk" else None,
    )

    def trace(train):
        jax.eval_shape(
            functools.partial(module.apply, train=train, mutable=["counters"]),
            shapes, grid, other,
        )

    seen.clear()  # init traced the net too
    trace(False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trace(False)
    trace(True)
    assert [fields["expert_path"] for fields in seen] == [
        {"kernel": 0, "ragged": 2}, {"kernel": 2, "ragged": 0}, {"kernel": 0, "ragged": 2},
    ]
