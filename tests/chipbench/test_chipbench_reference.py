"""The plain reference against the program's own net on the CPU, at a
tiny size and in float32, where the two must agree to rounding. The
training case holds the reference's dropout masks against Flax's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_cell import tiny_cell

from chipbench import manifest, reference, rows


@pytest.fixture(scope="module")
def net():
    from alphatriangle_tpu.nn.model import AlphaTriangleNet

    cfg = tiny_cell()["config_file"]
    configs = manifest.program_configs(cfg)
    module = AlphaTriangleNet(configs["model"], cfg["action_dim"])
    made = rows.make_rows(
        rows.seed_key(2**31 + 3), jnp.arange(16), cfg["env"], 14, cfg["action_dim"],
        16,
    )
    variables = module.init(
        jax.random.PRNGKey(0), made["grid"], made["other"], train=False
    )
    return cfg, module, variables, made


@pytest.mark.parametrize("train", [False, True], ids=["eval", "train-dropout"])
def test_forward_agrees_with_the_program(net, train):
    cfg, module, variables, made = net
    key = jax.random.PRNGKey(9) if train else None
    want = module.apply(
        variables, made["grid"], made["other"], train=train,
        rngs={"dropout": key} if train else None,
    )
    got = reference.forward(
        variables["params"], cfg["model"], made["grid"], made["other"], key
    )
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5)
    if train:  # the masks matter: without them the outputs differ
        plain = reference.forward(
            variables["params"], cfg["model"], made["grid"], made["other"]
        )
        assert float(jnp.abs(plain[0] - got[0]).max()) > 1e-3


def test_two_hot_is_the_programs_projection():
    from alphatriangle_tpu.rl.trainer import project_to_support

    returns = jnp.asarray([-12.0, -10.0, -0.3, 0.0, 0.4, 9.99, 10.0, 14.0])
    np.testing.assert_allclose(
        reference.two_hot(returns, 51, -10.0, 10.0),
        project_to_support(returns, 51, -10.0, 10.0),
        atol=1e-6,
    )


def test_rows_are_valid_distinct_and_a_function_of_seed_and_slot():
    cfg = tiny_cell()["config_file"]
    key = rows.seed_key(7)
    a = rows.make_rows(key, jnp.arange(64), cfg["env"], 14, 12, 64)
    again = rows.make_rows(key, jnp.asarray([5, 63]), cfg["env"], 14, 12, 64)
    np.testing.assert_array_equal(a["policy"][jnp.asarray([5, 63])], again["policy"])
    np.testing.assert_allclose(a["policy"].sum(axis=1), 1.0, atol=1e-5)
    assert set(np.unique(a["grid"])) <= {-1.0, 0.0, 1.0}
    assert len(np.unique(np.asarray(a["other"]), axis=0)) == 64
    other = rows.make_rows(rows.seed_key(8), jnp.arange(64), cfg["env"], 14, 12, 64)
    assert not np.array_equal(a["ret"], other["ret"])
    ret = np.asarray(a["ret"])  # the older half low, the newer high, in the support
    assert ret[:32].max() <= -2 and ret[32:].min() >= 2 and np.abs(ret).max() <= 6
    shown = np.asarray(a["policy"]) > 0  # and their targets on halves of their own
    assert not shown[:32, 6:].any() and not shown[32:, :6].any()
    assert not np.array_equal(
        jax.random.key_data(rows.seed_key(5)),
        jax.random.key_data(rows.seed_key(5 + 2**31)),
    )
