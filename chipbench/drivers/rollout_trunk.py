"""The rollout driver for a net whose trunk is a decoder stack
(`ModelConfig.TRUNK`): `rollout.Driver`'s dispatch, release and the
parts of its comparison that know no net (`follow_moves`, `returns`,
`masked_rows`, `boards_of`, `searched`, `candidates_of`), with four
things of its own.

- The program's `ModelConfig` gets its `TRUNK` group from the
  configuration's file (`reference_exaone_moe.trunk_settings`, the same
  dict the reference reads). `weights.make_variables` then draws the
  whole tree on the device in the file's `PARAM_DTYPE` (bfloat16): one
  copy, which the engine serves from and the reference widens layer by
  layer afterwards. The routers' selection biases are set from the same
  key before anything is served (`router_balance`).
- The plain net is `reference_exaone_moe.forward`, handed to the plain
  fast search as an argument: `PlainSearch` is `reference_search`'s
  `evaluate`, `worth` and `search` with the forward and its block of
  boards given, and `first_move_numbers` / `compare_dispatch` are
  `rollout.py`'s with that search given. `rollout.py` names
  `reference.forward` through module globals, so these are copies: no
  number, rule or limit is changed in them, and PERF.md section 7 asks
  the next `benchmark` PR to make the forward a parameter there and
  fold the two.
- A unit of the window is a whole period of the playout cap: one-move
  dispatches up to and with the next full search. A move costs seconds
  here and a full search four times a fast one, so a window closed at
  the first dispatch past `--seconds` holds another amount of work
  whenever that mark falls near a dispatch's end (with `engine_seed` 0
  it fell on the eighth's: 8 or 9 dispatches by chance). Closed at the
  end of a period it holds the same whole periods in every run, each
  with its one full search. Set-up's warm-up and `calibrate` drive
  single dispatches.
- The routed trunk's counters out of the chunk's harvest: the
  assignments each held expert computed and the assignments routed
  anywhere, summed over the window for the per-layer metrics, and
  `forward_flops` as the FLOP an evaluation really computed (the fixed
  part plus one expert's SwiGLU for each assignment counted here).

A program without `ModelConfig.TRUNK` cannot run the cell: the driver
says so and exits before anything is built.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import (
    flops_exaone_moe,
    reference_env,
    reference_exaone_moe,
    reference_search,
    router_balance,
    weights,
)
from . import rollout
from .rollout import SHOWN, boards_of, candidates_of, searched


class PlainSearch:
    """`reference_search`'s fast search with the net given: `forward`
    (params, grid, other, quant) -> (policy logits, value logits), taken
    `block` boards at a time."""

    def __init__(self, forward, model: dict, block: int):
        self.forward, self.block = forward, block
        self.support = np.linspace(
            model["VALUE_MIN"], model["VALUE_MAX"], model["NUM_VALUE_ATOMS"]
        )

    def evaluate(self, params, grid, other, quant=None):
        """(policy logits (N, A), values (N,)), in blocks of rows."""
        block = self.block
        if len(grid) == 0:
            return np.zeros((0, 0)), np.zeros(0)
        logits, values = [], []
        for at in range(0, len(grid), block):
            g, o = grid[at : at + block], other[at : at + block]
            short = block - len(g)  # one shape for every block
            if short:
                g = np.concatenate([g, np.repeat(g[:1], short, axis=0)])
                o = np.concatenate([o, np.repeat(o[:1], short, axis=0)])
            pol, val = jax.device_get(self.forward(params, g, o, quant))
            val = np.asarray(val, np.float64)
            prob = np.exp(val - val.max(axis=1, keepdims=True))
            prob /= prob.sum(axis=1, keepdims=True)
            logits.append(np.asarray(pol)[: block - short])
            values.append((prob * self.support).sum(axis=1)[: block - short])
        return np.concatenate(logits), np.concatenate(values)

    def worth(self, rules, params, cfg, roots: dict, picks, quant=None):
        """q of the actions `picks` ((N, K) int, -1 where a lane has
        fewer) from the boards `roots`: (N, K), nan where there is no
        pick."""
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
        _, value = self.evaluate(params, grid, other, quant)
        out = np.full(picks.shape, np.nan)
        out[lane, col] = (
            gain
            + np.where(stuck, env["PENALTY_GAME_OVER"], 0.0)
            + discount * np.where(stuck, 0.0, value)
        )
        return out

    def search(self, rules, params, cfg, roots: dict, valid, top: int, quant=None):
        """What a fast search has to produce, all in one precision: the
        policy target (N, A), the root's value (N,) and the candidates
        (N, A) bool; nan for lanes with fewer than `top` valid actions."""
        grid, other = rules.features(
            roots["occupied"], roots["hand"], roots["score"], roots["steps"]
        )
        logits, v0 = self.evaluate(params, grid, other, quant)
        masked = np.where(valid, logits, -np.inf)
        picks = np.argsort(-masked, axis=1, kind="stable")[:, :top]
        enough = valid.sum(axis=1) >= top
        picks = np.where(enough[:, None], picks, -1)
        q = self.worth(rules, params, cfg, roots, picks, quant)
        completed = np.repeat(v0[:, None], valid.shape[1], axis=1)
        chosen = np.zeros_like(valid)
        rows_ = np.flatnonzero(enough)
        completed[rows_[:, None], picks[rows_]] = q[rows_]
        chosen[rows_[:, None], picks[rows_]] = True
        score = np.where(
            valid, logits + reference_search.scale(cfg["mcts"]) * completed, -np.inf
        )
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


def first_move_numbers(plain, cfg, rules, params0, host, got, valid, traffic) -> dict:
    """`rollout.first_move_numbers` with the plain search given: the
    first move's search against the plain one. `got` holds the policy
    target (B, A), the root's value (B,) and, for the control, the
    candidates it took; the program's are read off its target."""
    top = candidates_of(cfg["mcts"], cfg["action_dim"])
    crowd, margin = traffic["crowd"], traffic["candidate_margin"]
    roots = boards_of(host["boards"], rules)
    _, roots["drawn"] = reference_env.draw_hands(
        roots["key"], rules.slots, len(rules.bank)
    )
    lanes = len(valid)
    grid, other = rules.features(
        roots["occupied"], roots["hand"], roots["score"], roots["steps"]
    )
    logits, v0 = plain.evaluate(params0, grid, other)
    masked = np.where(valid, logits, -np.inf)
    order = np.argsort(-masked, axis=1, kind="stable")

    # Whether an action was a candidate is the plain net's to say, but
    # for those within `margin` of the 16th best logit: there the
    # program's rounding decides, and either answer is sound.
    widest = top + traffic["unsure_most"]
    picks = np.full((lanes, widest), -1)
    sure = np.zeros((lanes, widest), bool)
    readable = np.zeros(lanes, bool)
    for i in range(lanes):
        if int(valid[i].sum()) < top:
            continue  # spare simulations go by another rule: not read
        bar = masked[i, order[i, top - 1]]
        near = np.flatnonzero(masked[i] >= bar - margin)
        if len(near) > widest:
            continue
        readable[i] = True
        picks[i, : len(near)] = near
        sure[i, : len(near)] = masked[i, near] > bar + margin
    q = plain.worth(rules, params0, cfg, roots, picks)

    gaps = np.zeros(lanes)
    root_gaps = np.zeros(lanes)
    target_gaps = np.zeros(lanes)
    counted = roots_read = targets_read = 0
    scale = reference_search.scale(cfg["mcts"])
    for i in range(lanes):
        policy = got["policy"][i]
        if int(valid[i].sum()) >= top + crowd and np.isfinite(policy).all():
            chosen = (
                searched(policy, logits[i], valid[i], crowd)
                if got.get("chosen") is None
                else got["chosen"][i]
            )
            if chosen.any():
                counted += 1
                bar = masked[i, order[i, top - 1]]
                gaps[i] = max(0.0, float(bar - masked[i][chosen].min()))
                if chosen.sum() > top:
                    gaps[i] = np.inf  # more searched than a fast search has
        if not readable[i] or not np.isfinite(got["root_value"][i]):
            continue
        here = picks[i] >= 0
        unsure = np.sort(q[i, here & ~sure[i]])
        spare = top - int(sure[i].sum())
        fixed = v0[i] + q[i, sure[i]].sum()
        low = (fixed + unsure[:spare].sum()) / (1.0 + top)
        high = (fixed + unsure[len(unsure) - spare :].sum()) / (1.0 + top)
        value = float(got["root_value"][i])
        root_gaps[i] = max(0.0, low - value, value - high)
        roots_read += 1

        # The target: log ratio to the plain prior over the scale is
        # each shown action's q (or the root's value, for one that was
        # no candidate), to one constant, fixed on the likeliest action.
        shown = np.flatnonzero(valid[i] & (policy >= SHOWN))
        if len(shown) < 2:
            continue
        ratio = (np.log(policy[shown].astype(np.float64)) - logits[i, shown]) / scale
        can_be = []
        for action in shown:
            at = np.flatnonzero(picks[i] == action)
            if len(at) == 0:
                can_be.append([v0[i]])
            elif sure[i, at[0]]:
                can_be.append([q[i, at[0]]])
            else:
                can_be.append([q[i, at[0]], v0[i]])
        best = int(np.argmax(policy[shown]))
        target_gaps[i] = min(
            max(
                min(abs(ratio[j] - x - (ratio[best] - anchor)) for x in can_be[j])
                for j in range(len(shown))
            )
            for anchor in can_be[best]
        )
        targets_read += 1

    # Means over the lanes read, not the widest (`rollout.py` says why).
    floor = traffic["min_read_share"] * lanes
    means = {
        "candidate_gap_mean": gaps.sum() / max(counted, 1),
        "root_value_gap_mean": root_gaps.sum() / max(roots_read, 1),
        "target_value_gap_mean": target_gaps.sum() / max(targets_read, 1),
    }
    for name, count in zip(means, (counted, roots_read, targets_read)):
        if count < floor:
            means[name] = np.inf  # too few lanes could be read
    return {
        **{name: float(value) for name, value in means.items()},
        "read": {
            "candidates": counted,
            "roots": roots_read,
            "targets": targets_read,
            # the widest, read and not compared: they swing by nature
            "candidate_gap_widest": float(gaps.max()),
            "root_value_gap_widest": float(root_gaps.max()),
            "target_value_gap_widest": float(target_gaps.max()),
        },
    }


def compare_dispatch(plain, cfg, params0, host, traffic, quant=None, spoil=None):
    """`rollout.compare_dispatch` with the plain search given: the
    numbers of one kept dispatch, and how much was read. With `quant`
    the control takes the program's place in the first move's search;
    `spoil` alters what the plain search put there: a planted fault."""
    rules = reference_env.Rules(cfg["env"])
    train = cfg["train"]
    n_step = train["N_STEP_RETURNS"]
    boards = host["boards"]
    roots = boards_of(boards, rules)
    valid = rules.legal(roots["occupied"], roots["hand"])
    slot = host["first_move"] % n_step
    if quant is None and spoil is None:
        got = {
            "policy": np.asarray(host["flush"]["policy"])[0, :, slot],
            "root_value": np.asarray(host["harvest"]["root_value"])[0],
        }
    else:
        _, roots["drawn"] = reference_env.draw_hands(
            roots["key"], rules.slots, len(rules.bank)
        )
        got = plain.search(
            rules, params0, cfg, roots, valid,
            candidates_of(cfg["mcts"], cfg["action_dim"]), quant,
        )
    if spoil is not None:  # a planted fault, for `calibrate`
        got = spoil(got)
    invalid_mass = float(
        np.where(valid, 0.0, np.nan_to_num(got["policy"])).sum(axis=1).max()
    )
    first = first_move_numbers(plain, cfg, rules, params0, host, got, valid, traffic)
    read = first.pop("read")
    moves = rollout.follow_moves(rules, host, n_step, train["MAX_EPISODE_MOVES"])
    read["moves_followed"] = moves.pop("followed")
    rets = rollout.returns(host, n_step, train["GAMMA"])
    read["returns"], read["return_gap_widest"] = rets["rows"], rets["widest"]

    ring, want = host["ring"], rollout.masked_rows(host)
    ring_mismatch = sum(
        int((np.asarray(ring[f]) != want[f]).sum())
        if np.asarray(ring[f]).shape == want[f].shape
        else want[f].size + 1
        for f in want
    )
    # A lane whose game did not end in the dispatch is T moves on.
    moved = np.asarray(host["after"].step_count) - np.asarray(boards.step_count)
    ended = np.asarray(host["harvest"]["ending"]).any(axis=0)
    stalled = int((~ended & (moved != len(host["harvest"]["ending"]))).sum())
    numbers = {
        "feature_mismatch": float(moves["feature_mismatch"]),
        "invalid_mass": invalid_mass,
        **first,
        "step_mismatch": float(moves["step_mismatch"]),
        "return_mismatch": float(rets["return_mismatch"]),
        "ring_mismatch": float(ring_mismatch),
        "stalled_lanes": float(stalled),
    }
    return numbers, read


def make_variables(configs: dict, cfg: dict, key):
    """The program's variables from the seed (`weights.make_variables`),
    the routers' selection biases set on sample boards drawn from the
    same key (`router_balance`; the configuration's file says why, under
    `assumed`). Returns them and the seconds the balancing took."""
    variables = weights.make_variables(configs, key)
    jax.block_until_ready(variables)
    started = time.perf_counter()
    grid = router_balance.sample_boards(configs, jax.random.fold_in(key, 7))
    params = router_balance.balance(variables["params"], cfg, grid)
    jax.block_until_ready(params)
    return {**variables, "params": params}, time.perf_counter() - started


class Driver(rollout.Driver):
    def __init__(self, cell, configs, seed, spans):
        from alphatriangle_tpu.config import ModelConfig

        if "TRUNK" not in ModelConfig.model_fields:
            raise SystemExit(
                f"chipbench: {cell['name']} needs a program whose ModelConfig "
                "has a TRUNK group (nn/trunk.py); this checkout's has none."
            )
        from alphatriangle_tpu.config import TrunkConfig

        trunk = TrunkConfig(**reference_exaone_moe.trunk_settings(cell["config_file"]))
        configs = {
            **configs, "model": configs["model"].model_copy(update={"TRUNK": trunk})
        }
        super().__init__(cell, configs, seed, spans)
        cfg = self.cfg
        self.plain = PlainSearch(
            lambda p, g, o, quant: reference_exaone_moe.forward(p, cfg, g, o, quant),
            cfg["model"],
            self.traffic["reference_block"],
        )
        self.whole_periods = True  # a unit: dispatches up to a full search
        self.expert_tokens = 0  # (sparse layers, held) over the window
        self.routed = 0
        self.dispatches_before = 0  # the warm-up's

    def setup(self) -> None:
        """`rollout.Driver.setup` with this driver's weights."""
        from alphatriangle_tpu.env.engine import TriangleEnv
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        env_cfg, model, train = (
            self.configs["env"], self.configs["model"], self.configs["train"]
        )
        self.moves = self.traffic["chunk_moves"]
        self.lanes = train.SELF_PLAY_BATCH_SIZE
        self.n_step = train.N_STEP_RETURNS
        variables, balancing_s = make_variables(
            self.configs, self.cfg, jax.random.fold_in(self.key, 1)
        )
        print(
            f"chipbench: routers balanced in {balancing_s:.1f} s of set-up",
            file=sys.stderr, flush=True,
        )
        self.params0 = variables["params"]
        env = TriangleEnv(env_cfg)
        self.engine = SelfPlayEngine(
            env,
            get_feature_extractor(env, model),
            NeuralNetwork(model, env_cfg, variables=variables),
            self.configs["mcts"],
            train,
            seed=self.traffic["engine_seed"],
        )
        self.buffer = DeviceReplayBuffer(
            train,
            (model.GRID_INPUT_CHANNELS, env_cfg.ROWS, env_cfg.COLS),
            model.OTHER_NN_INPUT_FEATURES_DIM,
            env_cfg.action_dim,
            seed=self.seed,
        )
        self.capacity = train.BUFFER_CAPACITY
        self._copy = jax.jit(lambda s: jax.tree_util.tree_map(jnp.copy, s))
        self.dispatch()  # warm-up: loads or compiles the two programs

    def start_window(self) -> None:
        super().start_window()
        self.expert_tokens, self.routed = 0, 0
        self.dispatches_before = self.dispatches

    def dispatch(self) -> int:
        """One whole dispatch (`rollout.Driver.unit`) and its counters."""
        work = super().unit()
        harvest = self.engine.last_trace
        self.expert_tokens = self.expert_tokens + np.asarray(
            harvest["expert_tokens"], np.int64
        ).sum(axis=0)
        self.routed += int(np.asarray(harvest["routed"], np.int64).sum())
        return work

    def unit(self) -> int:
        """Dispatches up to and with the next one that held a full
        search; returns the lane-moves they completed."""
        work = self.dispatch()
        while self.whole_periods and not np.asarray(
            self.engine.last_trace["is_full"]
        ).any():
            work += self.dispatch()
        return work

    def counters(self) -> dict:
        roots = (self.dispatches - self.dispatches_before) * self.lanes * self.moves
        evaluations = self.simulations + roots
        here = int(np.sum(self.expert_tokens))
        return {
            "simulations": self.simulations,
            "forward_flops": flops_exaone_moe.forward_flops(
                self.cfg, here / max(evaluations, 1)
            ),
            "expert_tokens": np.asarray(self.expert_tokens).tolist(),
            "routed": self.routed,
        }

    def check(self, quant=None, spoil=None) -> dict:
        numbers, self.read = compare_dispatch(
            self.plain, self.cfg, self.params0, self.host, self.traffic, quant, spoil
        )
        # Read, not compared: each sparse layer's share of the window's
        # assignments that fell on the experts held here.
        tokens = np.asarray(self.expert_tokens, np.float64)
        if self.routed and tokens.ndim == 2:
            self.read["routed_here_by_layer"] = [
                round(float(x), 4)
                for x in tokens.sum(axis=1) * len(tokens) / self.routed
            ]
        return numbers


def calibrate(driver, parts, with_detail=False) -> dict:
    """`rollout.calibrate`, its few dispatches single ones."""
    driver.whole_periods = False
    return rollout.calibrate(driver, parts, with_detail)
