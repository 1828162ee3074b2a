"""The plain fast search, for `correct` in a rollout cell.

A fast search of the playout cap (`fast_simulations` = `gumbel_m` = one
wave) takes no random draw, so the plain reference can say what it has
to produce from the boards alone:

- the net gives the root's policy logits and value `v0` (the mean of
  the C51 head's distribution over its support);
- the candidates are the `gumbel_m` valid actions of highest logit;
  each is played once on a copy of the board (`reference_env.Rules`)
  and is worth q = reward + discount x the child's value by the net,
  nought for a child on which the game is over;
- the policy target is softmax(logit + (c_visit + 1) x c_scale x q)
  over the valid actions, an action that was not a candidate taking
  `v0` for its q; the root's value is (v0 + the sum of the candidates'
  q) / (1 + their number).

`quant` runs the net in a narrower type: the control.
"""

import functools
import json

import jax
import numpy as np

from . import reference


@functools.lru_cache(maxsize=None)
def _forward(model_json: str, quant):
    """One jitted net for a configuration and a precision."""
    model = json.loads(model_json)
    return jax.jit(
        lambda p, g, o: reference.forward(p, model, g, o, quant=quant)
    )


def evaluate(params, model: dict, grid, other, quant=None, block=2048):
    """The plain net in blocks of rows: (policy logits (N, A), values
    (N,))."""
    forward = _forward(json.dumps(model, sort_keys=True), quant)
    support = np.linspace(
        model["VALUE_MIN"], model["VALUE_MAX"], model["NUM_VALUE_ATOMS"]
    )
    if len(grid) == 0:
        return np.zeros((0, 0)), np.zeros(0)
    logits, values = [], []
    for at in range(0, len(grid), block):
        g, o = grid[at : at + block], other[at : at + block]
        short = block - len(g)  # one shape for every block
        if short:
            g = np.concatenate([g, np.repeat(g[:1], short, axis=0)])
            o = np.concatenate([o, np.repeat(o[:1], short, axis=0)])
        pol, val = jax.device_get(forward(params, g, o))
        val = np.asarray(val, np.float64)
        prob = np.exp(val - val.max(axis=1, keepdims=True))
        prob /= prob.sum(axis=1, keepdims=True)
        logits.append(np.asarray(pol)[: block - short])
        values.append((prob * support).sum(axis=1)[: block - short])
    return np.concatenate(logits), np.concatenate(values)


def worth(rules, params, cfg, roots: dict, picks, quant=None):
    """q of the actions `picks` ((N, K) int, -1 where a lane has fewer)
    from the boards `roots`: (N, K), nan where there is no pick."""
    env, discount = cfg["env"], cfg["mcts"]["discount"]
    lane, col = np.nonzero(picks >= 0)
    action = picks[lane, col]
    slot, origin = action // rules.cells, action % rules.cells
    hand = roots["hand"][lane]
    child, gain = rules.place(
        roots["occupied"][lane], hand[np.arange(len(lane)), slot], origin
    )
    hand = rules.hand_after(hand, slot, roots["drawn"][lane])
    stuck = ~rules.legal(child, hand).any(axis=1)
    grid, other = rules.features(
        child, hand, roots["score"][lane] + gain, roots["steps"][lane] + 1
    )
    _, value = evaluate(params, cfg["model"], grid, other, quant)
    out = np.full(picks.shape, np.nan)
    out[lane, col] = (
        gain
        + np.where(stuck, env["PENALTY_GAME_OVER"], 0.0)
        + discount * np.where(stuck, 0.0, value)
    )
    return out


def scale(mcts: dict) -> float:
    """What a q is multiplied by beside a logit: every candidate of a
    fast search is visited once."""
    return (mcts["gumbel_c_visit"] + 1.0) * mcts["gumbel_c_scale"]


def search(rules, params, cfg, roots: dict, valid, top: int, quant=None) -> dict:
    """What a fast search has to produce, all in one precision: the
    policy target (N, A), the root's value (N,) and the candidates
    (N, A) bool. Lanes with fewer than `top` valid actions come out as
    nan: the search spends its spare simulations there by another rule."""
    grid, other = rules.features(
        roots["occupied"], roots["hand"], roots["score"], roots["steps"]
    )
    logits, v0 = evaluate(params, cfg["model"], grid, other, quant)
    masked = np.where(valid, logits, -np.inf)
    picks = np.argsort(-masked, axis=1, kind="stable")[:, :top]
    enough = valid.sum(axis=1) >= top
    picks = np.where(enough[:, None], picks, -1)
    q = worth(rules, params, cfg, roots, picks, quant)
    completed = np.repeat(v0[:, None], valid.shape[1], axis=1)
    chosen = np.zeros_like(valid)
    rows_ = np.flatnonzero(enough)
    completed[rows_[:, None], picks[rows_]] = q[rows_]
    chosen[rows_[:, None], picks[rows_]] = True
    score = np.where(valid, logits + scale(cfg["mcts"]) * completed, -np.inf)
    score = (score - score.max(axis=1, keepdims=True)).astype(np.float32)
    policy = np.exp(score)
    policy /= policy.sum(axis=1, keepdims=True)
    root = (v0 + np.nansum(q, axis=1)) / (1.0 + top)
    return {
        "policy": np.where(enough[:, None], policy, np.nan),
        "root_value": np.where(enough, root, np.nan),
        "v0": v0,
        "chosen": chosen,
    }
