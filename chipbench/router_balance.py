"""The routers' selection biases of a routed trunk, made from `--seed`
with the weights (`chipbench/configs/k-exaone-ep8.json`, `assumed`).

A router of this family selects by score + a per-expert bias that
training moves until the experts' loads are even. Seeded weights have
had no such training, and on a board, whose cells look alike, an
unbalanced seeded router sends every cell to the same few experts: one
chip's share of the work then hangs on the seed (PERF.md section 6,
PR 27). So the weights a run serves are the seeded ones with the bias
a balanced checkpoint would bring: the balancing rule run to rest on
sample boards, layer by layer, each router balanced on what the layers
before it, already balanced, hand it.

Nothing here is the program's: the activations a router reads are the
plain reference's (`reference_exaone_moe`, float32, a block of boards a
call, held on the host between the layers so that the device's peak
stays the window's), and the biases go into the `params` tree as data,
for the program and the reference alike. The sample's boards are fresh
games after random legal moves by the program's `env`, as every board
of the harness is; `reference_env` follows those rules in the run's
comparison.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_exaone_moe as plain

# 256 games after 0..15 random legal moves each, board i after
# i % 16: what a search's roots and leaves look like in a game's first
# dozen moves, where a window of `run_seconds` plays. The boards of one
# game resemble each other, so the sample wants hundreds of games: with
# 64 games after 0..31 moves a share's load still moved by 5 % from seed
# to seed (my chip runs, PR 27).
BOARDS = 256
MOST_MOVES = 15
STEPS = 1500  # of the balancing rule, a layer
BLOCK = 16  # boards the plain net takes at a time


def balanced_bias(scores, k: int, steps: int = STEPS):
    """The selection bias (E,), float32, under which the top-k choice
    over `scores` (N, E) gives every expert the same number of tokens,
    as near as `steps` of the balancing rule bring it: raise the bias of
    an expert chosen less than its share and lower that of one chosen
    more, by one step whatever the gap (what a bias-balanced router's
    training does, run here to rest on one sample), the step shrinking
    from a tenth to a millionth as it goes."""
    n, e = scores.shape
    share = n * k / e
    rates = 0.1 * (1e-5 ** (jnp.arange(steps) / (steps - 1.0)))

    def step(bias, rate):
        _, chosen = jax.lax.top_k(scores + bias, k)
        load = jnp.zeros((e,), jnp.float32).at[chosen.reshape(-1)].add(1.0)
        return bias + rate * jnp.sign(share - load), None

    bias, _ = jax.lax.scan(step, jnp.zeros((e,), jnp.float32), rates)
    return bias


def sample_boards(configs: dict, key, count: int = BOARDS, most_moves: int = MOST_MOVES):
    """The grid planes (count, C, H, W) of `count` boards, board i being
    a fresh game after i % (most_moves + 1) random legal moves."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor

    env = TriangleEnv(configs["env"])
    reset_key, key = jax.random.split(key)
    states = env.reset_batch(jax.random.split(reset_key, count))
    moves = jnp.arange(count) % (most_moves + 1)

    def move(t, carry):
        states, key = carry
        key, sub = jax.random.split(key)
        valid = jax.vmap(env.valid_action_mask)(states)
        action = jax.random.categorical(sub, jnp.where(valid, 0.0, -jnp.inf))
        stepped, _, _ = jax.vmap(env.step)(states, action.astype(jnp.int32))
        go = (t < moves) & ~states.done & valid.any(axis=1)
        keep = lambda new, old: jnp.where(  # noqa: E731
            go.reshape((-1,) + (1,) * (new.ndim - 1)), new, old
        )
        return jax.tree_util.tree_map(keep, stepped, states), key

    states, _ = jax.jit(
        lambda s, k: jax.lax.fori_loop(0, most_moves, move, (s, k))
    )(states, key)
    grid, _ = get_feature_extractor(env, configs["model"]).extract_batch(states)
    return np.asarray(grid)


def _advance(before, p, y, *, cfg, t, i):
    """A block of boards from where layer i - 1's attention half left it
    (the grid planes, for i = 0) to where layer i's leaves it, and the
    scores layer i's router gives it there (None on a dense layer).
    `before` holds layer i - 1's weights (the stem's, for i = 0)."""
    if i == 0:
        y = plain.stem(before, cfg["model"], y, None)
    else:
        y = plain.mlp_half(before, y, t, i - 1, None)
    y = plain.attention_half(p, y, t, i, None)
    if t["mlp_layer_types"][i] == "dense":
        return y, None
    scores = plain.matmul(
        y.reshape(-1, y.shape[-1]), p["w_router"].astype(jnp.float32), None
    )
    return y, jax.nn.sigmoid(scores)


def balance(params: dict, cfg: dict, grid: np.ndarray, block: int = BLOCK) -> dict:
    """`params` with every sparse layer's `router_bias` set so that the
    boards `grid` load the layer's experts evenly. Only the biases are
    made anew; every other leaf is the array it was."""
    t = plain.trunk_settings(cfg)
    trunk = dict(params["DecoderTrunk_0"])
    sparse = [i for i, kind in enumerate(t["mlp_layer_types"]) if kind == "sparse"]
    block = min(block, len(grid))
    if len(grid) % block:
        raise ValueError(f"{len(grid)} boards are not whole blocks of {block}")
    solve = jax.jit(functools.partial(balanced_bias, k=t["num_experts_per_tok"]))

    x = grid  # on the host between the layers, a block at a time on the device
    before = {name: v for name, v in params.items() if name != "DecoderTrunk_0"}
    for i in range(sparse[-1] + 1):
        p = plain.layer_weights(trunk, i)
        advance = jax.jit(functools.partial(_advance, cfg=cfg, t=t, i=i))
        blocks, scores, last = [], [], None
        for at in range(0, len(x), block):
            y, s = advance(before, p, x[at : at + block])
            scores.append(s)
            if i == sparse[-1]:
                continue  # nothing reads past the last router
            if last is not None:  # fetched while the next block runs
                blocks.append(jax.device_get(last))
            last = y
        if last is not None:
            blocks.append(jax.device_get(last))
        if i in sparse:
            bias = solve(jnp.concatenate(scores))
            trunk[f"l{i}_router_bias"] = p["router_bias"] = bias
        x, before = (np.concatenate(blocks) if blocks else None), p
    return {**params, "DecoderTrunk_0": trunk}
