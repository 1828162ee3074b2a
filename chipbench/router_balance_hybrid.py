"""The routers' selection biases of a trunk whose router chooses among
groups, made from `--seed` with the weights
(`chipbench/configs/ling-flash-ep4.json`, `assumed`).

`router_balance.py`'s rule and sample (it says why a seeded router
needs them), with two things of its own. The choice the rule balances
is the grouped one (`reference_ling_hybrid.choose`: the experts in
`n_group` groups, the `topk_group` groups whose two best biased scores
sum highest stay, the `num_experts_per_tok` best among them are
chosen), so the biases it rests at even the experts' loads under the
choice the run makes. And the activations a router reads are
`reference_ling_hybrid`'s, whose layers are pre-norm: a router reads
RMSNorm(h), h being what the layer's mixer left.

Nothing here is the program's; the biases go into the `params` tree as
data, for the program and the reference alike.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_ling_hybrid as plain
from .router_balance import BLOCK, BOARDS, MOST_MOVES, STEPS, sample_boards  # noqa: F401


def choose_and_count(biased, t: dict):
    """`reference_ling_hybrid.choose` without a sort, and how many of
    the tokens `biased` (N, E) choose each expert: (chosen (N, k),
    loads (E,) float32). The rule takes it 1,500 times a layer over
    64.5k tokens, where sorting 33M scores twice a step would be most
    of a run's set-up; here a step is a dozen passes over them. Of
    equals the first, as the stable sort there."""
    groups, e = t["n_group"], biased.shape[-1]
    n, wide = biased.shape[0], e // groups
    by_group = biased.reshape(n, groups, wide)
    best = by_group.max(axis=-1)
    first = jnp.argmax(by_group, axis=-1)
    second = jnp.where(
        jnp.arange(wide) == first[..., None], -jnp.inf, by_group
    ).max(axis=-1)
    _, kept = jax.lax.top_k(best + second, t["topk_group"])  # (N, stay): small
    stays = (kept[:, :, None] == jnp.arange(groups)).any(axis=1)
    among = jnp.where(jnp.repeat(stays, wide, axis=1), biased, -jnp.inf)
    experts = jnp.arange(e)
    chosen, loads = [], jnp.zeros((e,), jnp.float32)
    for _ in range(t["num_experts_per_tok"]):
        top = jnp.argmax(among, axis=-1)
        hit = experts == top[:, None]
        loads = loads + hit.sum(axis=0, dtype=jnp.float32)
        among = jnp.where(hit, -jnp.inf, among)
        chosen.append(top)
    return jnp.stack(chosen, axis=-1), loads


def choose(biased, t: dict):
    return choose_and_count(biased, t)[0]


def loads(biased, t: dict):
    """How many of the tokens `biased` (N, E) choose each expert."""
    return choose_and_count(biased, t)[1]


def balanced_bias(scores, t: dict, steps: int = STEPS):
    """The selection bias (E,), float32, under which the grouped choice
    over `scores` (N, E) gives every expert the same number of tokens,
    as near as `steps` of the balancing rule bring it
    (`router_balance.balanced_bias` says which rule)."""
    n, e = scores.shape
    share = n * t["num_experts_per_tok"] / e
    rates = 0.1 * (1e-5 ** (jnp.arange(steps) / (steps - 1.0)))

    def step(bias, rate):
        return bias + rate * jnp.sign(share - loads(scores + bias, t)), None

    bias, _ = jax.lax.scan(step, jnp.zeros((e,), jnp.float32), rates)
    return bias


def _advance(before, p, y, *, cfg, t, i):
    """A block of boards from where layer i - 1's mixer half left it
    (the grid planes, for i = 0) to where layer i's leaves it, and the
    scores layer i's router gives it there (None on a dense layer).
    `before` holds layer i - 1's weights (the stem's, for i = 0)."""
    if i == 0:
        y = plain.stem(before, cfg["model"], y, None)
    else:
        y = plain.mlp_half(before, y, t, i - 1, None)
    y = plain.mixer_half(p, y, t, i, None)
    if t["mlp_layer_types"][i] == "dense":
        return y, None
    read = plain.mlp_input(p, y, t)
    scores = plain.matmul(
        read.reshape(-1, read.shape[-1]), p["w_router"].astype(jnp.float32), None
    )
    return y, jax.nn.sigmoid(scores)


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
    solve = jax.jit(functools.partial(balanced_bias, t=t, steps=steps))

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
