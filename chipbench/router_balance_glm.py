"""The routers' starting selection biases of the GLM-4.7-Flash trunk on
the learner path, made from `--seed` with the weights
(`chipbench/configs/glm-flash-ep8.json`, `assumed.router_bias_start`).

`router_balance.py`'s rule (it says why a seeded router needs it: run to
rest on a sample, layer by layer, each router balanced on what the
layers before it, already balanced, hand it), with two things of its
own. The sample is of the boards the learner trains on: rows of the
seeded ring (`rows.make_rows`), taken evenly over its slots, not boards
of fresh games. And the activations a router reads are
`reference_glm_moe`'s, whose layers are pre-norm with a latent mixer: a
router reads RMSNorm(h), h being what the layer's mixer left.

These are where the biases START. From the first step on the program's
own rule moves them (`nn/trunk.py` `moved_router_biases`), and the
cell's `expert_load_max_over_mean.learner` says whether it holds what
set-up made. Nothing here is the program's; the biases go into the
`params` tree as data, for the program and the reference alike.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_glm_moe as plain
from . import rows
from .router_balance import balanced_bias

BOARDS = 256
BLOCK = 16  # boards the plain net takes at a time


def sample_boards(cfg: dict, row_key, count: int = BOARDS) -> np.ndarray:
    """The grid planes (count, C, H, W) of `count` rows of the run's
    ring, evenly over its slots (so of its older and its newer half)."""
    capacity = cfg["train"]["BUFFER_CAPACITY"]
    count = min(count, capacity)
    index = (np.arange(count) * (capacity // count)).astype(np.int32)
    made = jax.jit(
        lambda key, index: rows.make_rows(
            key, index, cfg["env"], cfg["model"]["OTHER_NN_INPUT_FEATURES_DIM"],
            cfg["action_dim"], capacity,
        )["grid"]
    )(row_key, index)
    return np.asarray(made)


def _advance(before, p, y, *, cfg, t, i):
    """A block of boards from where layer i - 1's mixer half left it
    (the grid planes, for i = 0) to where layer i's leaves it, and the
    scores layer i's router gives it there (None on a dense layer).
    `before` holds layer i - 1's weights (the stem's, for i = 0)."""
    if i == 0:
        y = plain.stem(before, cfg["model"], y, None)
    else:
        y, _ = plain.mlp_half(before, y, t, i - 1, None)
    y = plain.mixer_half(p, y, t, None)
    if t["mlp_layer_types"][i] == "dense":
        return y, None
    read = plain.mlp_input(p, y, t)
    scores = plain.matmul(read.reshape(-1, read.shape[-1]), p["w_router"], None)
    return y, jax.nn.sigmoid(scores)


def balance(params: dict, cfg: dict, grid: np.ndarray, block: int = BLOCK) -> dict:
    """`params` with every sparse layer's `router_bias` set so that the
    boards `grid` load the layer's experts evenly. Only the biases are
    made anew; every other leaf is the array it was."""
    t = plain.trunk_settings(cfg)
    trunk = dict(params["DecoderTrunk_0"])
    sparse = plain.sparse_layers(t)
    block = min(block, len(grid))
    if len(grid) % block:
        raise ValueError(f"{len(grid)} boards are not whole blocks of {block}")
    solve = jax.jit(functools.partial(balanced_bias, k=t["num_experts_per_tok"]))

    x = grid  # on the host between the layers, a block at a time on the device
    before = {name: v for name, v in params.items() if name != "DecoderTrunk_0"}
    for i in range(sparse[-1] + 1):
        p = plain.layer_weights(trunk, i)
        advance = jax.jit(functools.partial(_advance, cfg=cfg, t=t, i=i))
        blocks, scores = [], []
        for at in range(0, len(x), block):
            y, s = advance(before, p, x[at : at + block])
            scores.append(s)
            if i < sparse[-1]:  # nothing reads past the last router
                blocks.append(jax.device_get(y))
        if i in sparse:
            bias = solve(jnp.concatenate(scores))
            trunk[f"l{i}_router_bias"] = p["router_bias"] = bias
        x, before = (np.concatenate(blocks) if blocks else None), p
    return {**params, "DecoderTrunk_0": trunk}
