"""The net's weights from the seed: one jitted call of the module's own
initialiser on the device, float32 as the configuration serves them.
The same arrays go to the program and, as its input, to the reference."""

import jax
import jax.numpy as jnp


def make_variables(configs: dict, key):
    """(module variables, their `params`) for the program's net."""
    from alphatriangle_tpu.nn.model import AlphaTriangleNet

    env, model = configs["env"], configs["model"]
    module = AlphaTriangleNet(model, env.action_dim)
    grid = (1, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS)
    return jax.jit(
        lambda k: module.init(
            k,
            jnp.zeros(grid, jnp.float32),
            jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM), jnp.float32),
            train=False,
        )
    )(key)
