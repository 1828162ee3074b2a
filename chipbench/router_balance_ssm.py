"""The routers' selection biases of a trunk whose layers are one half
each and whose routers choose 22 of 512, made from `--seed` with the
weights (`chipbench/configs/nemotron-super-ep4.json`, `assumed`).

`router_balance.py`'s rule, steps and sample (it says why a seeded
router needs them), with two things of its own. The activations a
router reads are `reference_nemotron_h`'s: an `E` layer is a layer of
its own, its router reads RMSNorm(x), x being what the layer before
left, so each layer is taken whole, and an `E` layer twice: once for
its scores, and once more, its bias set, for what it hands on. And
an expert's load is counted without a scatter, over every fourth cell
of every sample board: the 22nd best of a token's biased scores is the
token's bar, and an expert is loaded by the tokens whose bar it reaches
(with 22 choices of 512, sorting 64.5k tokens' scores 1,500 times a
layer was 90 of a run's 114 s of balancing, my chip run, PR 38; a
board's neighbouring cells look alike, and 16.1k tokens still bring an
expert 693, what a block of the run brings it).

Nothing here is the program's; the biases go into the `params` tree as
data, for the program and the reference alike.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_nemotron_h as plain
from .router_balance import BLOCK, BOARDS, MOST_MOVES, STEPS, sample_boards  # noqa: F401

CELL_STRIDE = 4  # the rule reads every fourth cell of a sample board


def loads(biased, k: int):
    """How many of the tokens `biased` (N, E) choose each expert among
    their `k` best: (E,) float32. Experts that reach a token's bar
    alike are all counted, where the choice takes the first."""
    bar = jax.lax.top_k(biased, k)[0][:, -1:]
    return (biased >= bar).sum(axis=0, dtype=jnp.float32)


def balanced_bias(scores, k: int, steps: int = STEPS):
    """The selection bias (E,), float32, under which the top-k choice
    over `scores` (N, E) gives every expert the same number of tokens,
    as near as `steps` of the balancing rule bring it
    (`router_balance.balanced_bias` says which rule)."""
    n, e = scores.shape
    share = n * k / e
    rates = 0.1 * (1e-5 ** (jnp.arange(steps) / (steps - 1.0)))

    def step(bias, rate):
        return bias + rate * jnp.sign(share - loads(scores + bias, k)), None

    bias, _ = jax.lax.scan(step, jnp.zeros((e,), jnp.float32), rates)
    return bias


def _scores(p, x, *, t, i):
    read = plain.layer_input(p, x, t, i)
    return plain.scores_of(
        plain._f32(p), read.reshape(-1, read.shape[-1]), None
    )


def balance(
    params: dict, cfg: dict, grid: np.ndarray, block: int = BLOCK, steps: int = STEPS
) -> dict:
    """`params` with every sparse layer's `router_bias` set so that the
    boards `grid` load the layer's experts evenly. Only the biases are
    made anew; every other leaf is the array it was."""
    t = plain.trunk_settings(cfg)
    trunk = dict(params["DecoderTrunk_0"])
    sparse = [i for i, kind in enumerate(t["mlp_layer_types"]) if kind == "sparse"]
    block = min(block, len(grid))
    if len(grid) % block:
        raise ValueError(f"{len(grid)} boards are not whole blocks of {block}")
    solve = jax.jit(
        functools.partial(balanced_bias, k=t["num_experts_per_tok"], steps=steps)
    )
    rest = {name: v for name, v in params.items() if name != "DecoderTrunk_0"}
    stem = jax.jit(lambda p, g: plain.stem(p, cfg["model"], g, None))

    def blocks_of(fn, p, x):  # on the host between the layers
        out, last = [], None
        for at in range(0, len(x), block):
            y = fn(p, x[at : at + block])
            if last is not None:  # fetched while the next block runs
                out.append(jax.device_get(last))
            last = y
        out.append(jax.device_get(last))
        return np.concatenate(out)

    x = blocks_of(stem, rest, grid)
    for i in range(sparse[-1] + 1):
        p = plain.layer_weights(trunk, i)
        if i in sparse:
            scores = jax.jit(functools.partial(_scores, t=t, i=i))
            bias = solve(jnp.asarray(blocks_of(scores, p, x)[::CELL_STRIDE]))
            trunk[f"l{i}_router_bias"] = p["router_bias"] = bias
        if i < sparse[-1]:  # nothing reads past the last router
            x = blocks_of(
                jax.jit(functools.partial(plain.layer, t=t, i=i, quant=None)), p, x
            )
    return {**params, "DecoderTrunk_0": trunk}
