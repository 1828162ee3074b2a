"""The rollout driver: what `TrainingLoop._process_rollout` does in
device-replay mode. One dispatch is `SelfPlayEngine.play_moves_device(T)`
(T lockstep moves of every lane, search and all, and the fetch of its
small harvest) and `DeviceReplayBuffer.ingest_payload` of the rows it
left on the device.

Weights come from `--seed`. The engine's own key is the traffic file's
`engine_seed`, the same in every run: the engine draws each move's
full-or-fast search from that key, so a key from the seed would give
every run another amount of work (a window of 64 moves holds 16 +- 3.5
full searches, each twice a fast one).

`correct` is decided on the last whole dispatch of the window whose
first move was a fast search, from the boards copied on the device
before that dispatch, the rows and the per-move harvest (rewards, root
values, endings) it produced, and the harvest of the dispatch before
it. The plain reference (`reference_env`, `reference_search`,
`reference`) makes everything else itself:

- features: the net's inputs, board plane and the 30 other features,
  are the plain ones of the boards (`feature_mismatch`, exact);
- the first move, a fast search, which takes no random draw:
  * the policy target has no mass on an action the plain rules forbid
    (`invalid_mass`), and every action it shows as searched is one of
    the 16 best valid actions by the plain float32 net, to rounding
    (`candidate_gap_mean`: how far under the 16th best, lane by lane);
  * the root's value the program harvested is the plain one: the mean
    of the plain net's value of the root and of reward + plain value of
    the child board for each candidate played by the plain rules
    (`root_value_gap_mean`): the value head, the leaf evaluations, the env's
    step and rewards inside the search, the backup;
  * the policy target's log ratio to the plain prior, over the
    search's scale, is each shown action's plain q to one shared
    constant (`target_value_gap_mean`): the improved policy as it goes into
    the ring;
- all T moves: from one move's board to the next's there is a legal
  action by the plain rules that gives the next features, the harvested
  reward and the harvested ending (`step_mismatch`, exact), lane by
  lane until the lane's game ends;
- every row the dispatch put out carries the n-step return of the
  harvested rewards and root values (`return_mismatch`, exact to
  float32 rounding);
- the rows the ring holds are the masked rows of the payload, bit for
  bit, in the payload's order (`ring_mismatch`); a lane whose game did
  not end is T moves on (`stalled_lanes`).

A full 64-simulation Gumbel search is not followed: its halving and its
argmax flip on rounding, and a replayed tree parts from the program's
at the first flip (PERF.md section 2).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops, reference, reference_env, reference_search, rows
from ..weights import make_variables
from ..spans import Spans

RING_FIELDS = {
    "grid": "grid",
    "other_features": "other",
    "policy_target": "policy",
    "value_target": "ret",
    "policy_weight": "pw",
}
CALIBRATE_UNITS = 4  # dispatches `calibrate` drives before it reads
FEATURE_TOLERANCE = 1e-5  # float32 rounding of a mean or a quotient
RETURN_TOLERANCE = 1e-5  # and of five discounted rewards summed
SHOWN = 1e-37  # a policy mass below float32's normal range is not read


class Driver:
    unit_name = "lane-moves"

    def __init__(self, cell: dict, configs: dict, seed: int, spans: Spans):
        self.cell = cell
        self.cfg = cell["config_file"]
        self.traffic = cell["traffic_file"]
        self.configs = configs
        self.seed = int(seed)
        self.spans = spans
        self.key = rows.seed_key(self.seed)
        self.failed = 0
        self.dispatches = 0
        self.simulations = 0
        self.written = 0  # rows the ring has taken since it was built
        self.harvest_before: dict | None = None  # the last dispatch's
        self.kept: dict | None = None  # the dispatch `correct` looks at

    # --- set-up -----------------------------------------------------------

    def setup(self) -> None:
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
        grid_shape = (model.GRID_INPUT_CHANNELS, env_cfg.ROWS, env_cfg.COLS)
        other_dim = model.OTHER_NN_INPUT_FEATURES_DIM

        variables = make_variables(
            self.configs, jax.random.fold_in(self.key, 1)
        )
        self.params0 = variables["params"]
        env = TriangleEnv(env_cfg)
        net = NeuralNetwork(model, env_cfg, variables=variables)
        self.engine = SelfPlayEngine(
            env,
            get_feature_extractor(env, model),
            net,
            self.configs["mcts"],
            train,
            seed=self.traffic["engine_seed"],
        )
        self.buffer = DeviceReplayBuffer(
            train, grid_shape, other_dim, env_cfg.action_dim, seed=self.seed
        )
        self.capacity = train.BUFFER_CAPACITY
        self._copy = jax.jit(
            lambda s: jax.tree_util.tree_map(jnp.copy, s)
        )
        self.unit()  # warm-up: loads or compiles the two programs

    # --- the timed path ---------------------------------------------------

    def start_window(self) -> None:
        """The counters count the window's dispatches, not the warm-up."""
        self.simulations = 0

    def unit(self) -> int:
        """One whole dispatch; returns the lane-moves it completed."""
        engine, spans = self.engine, self.spans
        boards = self._copy(engine.states)  # the program donates them
        with spans.span("rollout"):
            result, payload = engine.play_moves_device(self.moves)
        with spans.span("ingest"):
            added = self.buffer.ingest_payload(payload)
        if self.kept is not None and "after" not in self.kept:
            self.kept["after"] = boards  # where the kept dispatch left them
        first_move = self.moves * self.dispatches
        self.dispatches += 1
        self.simulations += int(result.total_simulations)
        harvest = engine.last_trace  # small, on the host already
        if not bool(harvest["is_full"][0]):
            self.kept = {
                "boards": boards,
                "payload": payload,
                "harvest": harvest,
                "harvest_before": self.harvest_before,
                "first_move": first_move,
                "ring_start": self.written % self.capacity,
                "added": added,
            }
        self.harvest_before = harvest
        self.written += added
        return self.lanes * self.moves

    def counters(self) -> dict:
        return {
            "simulations": self.simulations,
            "forward_flops": flops.forward_flops(
                self.cfg["model"], self.cfg["env"], self.cfg["action_dim"]
            ),
        }

    # --- after the window -------------------------------------------------

    def release(self) -> None:
        """Bring what `correct` needs to the host, then free the
        program's state: the ring, the engine, the payload."""
        kept = self.kept
        if kept is None:
            raise RuntimeError(
                "no dispatch of the window began with a fast search; "
                "choose another engine_seed in the traffic file"
            )
        kept.setdefault("after", self._copy(self.engine.states))
        at = (kept["ring_start"] + np.arange(kept["added"])) % self.capacity
        self.host = {
            "boards": jax.device_get(kept["boards"]),
            "after": jax.device_get(kept["after"]),
            "ring": jax.device_get(
                {f: v[at] for f, v in self.buffer.storage.items()}
            ),
            "mat": jax.device_get(kept["payload"]["mat"]),
            "flush": jax.device_get(kept["payload"]["flush"]),
            "harvest": kept["harvest"],
            "harvest_before": kept["harvest_before"],
            "first_move": kept["first_move"],
        }
        self.kept = self.engine = self.buffer = None

    def check(self, quant=None, spoil=None) -> dict:
        """The numbers `correct` compares; `self.read` says how much of
        the dispatch each could read."""
        numbers, self.read = compare_dispatch(
            self.cfg, self.params0, self.host, self.traffic, quant, spoil
        )
        return numbers


def masked_rows(host: dict) -> dict:
    """The payload's rows as the ring must hold them: matured rows, then
    flushed rows, each in the order of its leading axes."""
    out = {}
    for ring_name, field in RING_FIELDS.items():
        parts = [
            np.asarray(host[block][field])[np.asarray(host[block]["mask"])]
            for block in ("mat", "flush")
        ]
        rows_ = np.concatenate([p.reshape(len(p), *p.shape[1:]) for p in parts])
        out[ring_name] = rows_.astype(np.int8 if ring_name == "grid" else np.float32)
    return out


def searched(policy, prior_logits, valid, crowd=8):
    """The actions a fast search's policy target shows as searched.

    The target is softmax(prior logit + 51 x value), the value being the
    action's own where the search tried it and the root's where it did
    not. So the actions never searched keep their prior times one
    common factor (or underflow to nought beside a better one), and a
    searched one is moved by its own value. An action above nought
    whose log ratio to the prior is shared, within a half, by `crowd`
    actions or more is one of the unsearched crowd (or one of as many
    searched actions of equal value: leaving those out costs readings,
    never a false alarm). What else shows above nought was searched.
    The caller reads only lanes with `top + crowd` valid actions or
    more, so the unsearched, where they show at all, are a crowd."""
    shown = np.flatnonzero(valid & (policy > 0))
    ratio = np.log(policy[shown]) - prior_logits[shown]
    near = (np.abs(ratio[:, None] - ratio[None, :]) <= 0.5).sum(axis=1)
    out = np.zeros_like(valid)
    out[shown[near < crowd]] = True
    return out


def candidates_of(mcts: dict, action_dim: int) -> int:
    """How many root actions a fast search takes as candidates: the
    configuration's `gumbel_m`, held to one wave of the fast search."""
    wave = min(mcts["mcts_batch_size"], mcts["fast_simulations"])
    return min(mcts["gumbel_m"], wave, action_dim)


def boards_of(states, rules) -> dict:
    """The program's boards (an `EnvState` on the host) as the plain
    rules take them."""
    env = rules.env
    return {
        "occupied": np.stack(
            [reference_env.unpack(w, env).reshape(-1) for w in np.asarray(states.occupied)]
        ),
        "hand": np.asarray(states.shape_idx).astype(np.int64),
        "score": np.array(states.score, np.float32),
        "steps": np.asarray(states.step_count).astype(np.int64),
        "key": np.array(states.key),
    }


def follow_moves(rules, host: dict, n_step: int, max_moves: int) -> dict:
    """The T moves of the kept dispatch by the plain rules, lane by lane
    until the lane's game ends: the features the program wrote at each
    move against the plain ones of the board the plain rules reach, and
    for each move a legal action that explains the next features, the
    harvested reward and the harvested ending."""
    flush, harvest = host["flush"], host["harvest"]
    penalty = rules.env["PENALTY_GAME_OVER"]
    now = boards_of(host["boards"], rules)
    after = boards_of(host["after"], rules)
    moves, lanes = np.asarray(harvest["reward"]).shape
    following = np.ones(lanes, bool)  # lanes whose game has not ended yet
    feature_mismatch = step_mismatch = followed = 0

    def written(t):
        """The features the program wrote for the boards before move t."""
        if t == moves:
            return rules.features(
                after["occupied"], after["hand"], after["score"], after["steps"]
            )
        slot = (host["first_move"] + t) % n_step
        return (
            np.asarray(flush["grid"])[t, :, slot],
            np.asarray(flush["other"])[t, :, slot],
        )

    def differ(mine, theirs):
        return np.abs(mine - theirs) > FEATURE_TOLERANCE

    for t in range(moves):
        grid, other = rules.features(
            now["occupied"], now["hand"], now["score"], now["steps"]
        )
        their_grid, their_other = written(t)
        feature_mismatch += int(
            differ(grid, their_grid)[following].sum()
            + differ(other, their_other)[following].sum()
        )
        followed += int(following.sum())
        legal = rules.legal(now["occupied"], now["hand"])
        lane, action = np.nonzero(legal & following[:, None])
        slot, origin = action // rules.cells, action % rules.cells
        child, gain = rules.place(
            now["occupied"][lane], now["hand"][lane, slot], origin
        )
        now["key"], drawn = reference_env.draw_hands(
            now["key"], rules.slots, len(rules.bank)
        )
        reward = np.asarray(harvest["reward"])[t]
        ending = np.asarray(harvest["ending"])[t]
        next_grid, next_other = written(t + 1)
        next_board = next_grid[:, 0].reshape(lanes, -1) > 0
        # An action explains the move if it is legal, earns the reward
        # (less the penalty where no shape fits after it), where the
        # game goes on leaves the next board and the next features, and
        # ends the game where the harvest says it ended.
        lost = gain == (reward - penalty)[lane]
        maybe = ((gain == reward[lane]) | lost) & (
            (child == next_board[lane]).all(axis=1) | ending[lane]
        )
        at = np.flatnonzero(maybe)
        hand = rules.hand_after(now["hand"][lane[at]], slot[at], drawn[lane[at]])
        stuck = ~rules.legal(child[at], hand).any(axis=1)
        score = now["score"][lane[at]] + gain[at]
        steps = now["steps"][lane[at]] + 1
        _, other_after = rules.features(child[at], hand, score, steps)
        explains = (
            ((stuck | (steps >= max_moves)) == ending[lane[at]])
            & (stuck == lost[at])
            & (
                ending[lane[at]]
                | ~differ(other_after, next_other[lane[at]]).any(axis=1)
            )
        )
        found = np.zeros(lanes, bool)
        for j in np.flatnonzero(explains)[::-1]:  # the first one stays
            i = lane[at[j]]
            found[i] = True
            now["occupied"][i], now["hand"][i] = child[at[j]], hand[j]
            now["score"][i], now["steps"][i] = score[j], steps[j]
        step_mismatch += int((following & ~found).sum())
        following &= found & ~ending
    return {
        "feature_mismatch": feature_mismatch,
        "step_mismatch": step_mismatch,
        "followed": followed,
    }


def returns(host: dict, n_step: int, gamma: float) -> dict:
    """Every row's value target against the n-step return of the
    harvested rewards: a matured row, added n moves before, is its n
    rewards discounted and the matured move's root value at gamma^n; a
    flushed row is the rewards since it was added. The gap is against
    the return's size or 1, whichever is larger; a row counts as a
    mismatch beyond float32 rounding (RETURN_TOLERANCE)."""
    now, before = host["harvest"], host["harvest_before"]
    moves = len(np.asarray(now["reward"]))
    reward = np.asarray(now["reward"], np.float64)
    if before is not None:
        reward = np.concatenate([np.asarray(before["reward"], np.float64), reward])
    start = len(reward) - moves  # where the kept dispatch begins
    root = np.asarray(now["root_value"], np.float64)

    def discounted(first, last, lane):
        """Rewards of moves first..last of the kept dispatch."""
        if start + first < 0:
            return None
        span = reward[start + first : start + last + 1, lane]
        return float((span * gamma ** np.arange(len(span))).sum())

    gaps = []
    mat, flush = host["mat"], host["flush"]
    for t, lane in zip(*np.nonzero(np.asarray(mat["mask"]))):
        want = discounted(t - n_step, t - 1, lane)
        if want is None:
            continue
        want += gamma**n_step * root[t, lane]
        got = float(np.asarray(mat["ret"])[t, lane])
        gaps.append(abs(got - want) / max(1.0, abs(want)))
    for t, lane, slot in zip(*np.nonzero(np.asarray(flush["mask"]))):
        move = host["first_move"] + t
        want = discounted(t - (move - slot) % n_step, t, lane)
        if want is None:
            continue
        got = float(np.asarray(flush["ret"])[t, lane, slot])
        gaps.append(abs(got - want) / max(1.0, abs(want)))
    gaps = np.asarray(gaps)
    return {
        "return_mismatch": int((gaps > RETURN_TOLERANCE).sum()),
        "rows": len(gaps),
        "widest": float(gaps.max()) if len(gaps) else 0.0,
    }


def first_move_numbers(cfg, rules, params0, host, got, valid, traffic) -> dict:
    """The first move's search against the plain one. `got` holds the
    policy target (B, A), the root's value (B,) and, for the control,
    the candidates it took; the program's are read off its target."""
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
    logits, v0 = reference_search.evaluate(params0, cfg["model"], grid, other)
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
    q = reference_search.worth(rules, params0, cfg, roots, picks)

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

    # Means over the lanes read, not the widest: one lane where the
    # program's rounding put a candidate beyond the margin reads a whole
    # reward off, and must not decide a sound run; a fault of the
    # program is in every lane.
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


def compare_dispatch(cfg, params0, host, traffic, quant=None, spoil=None):
    """The numbers of one kept dispatch, and how much was read. With
    `quant` the control takes the program's place in the first move's
    search: the plain fast search with the net in that precision, read
    against the float32 one as the program's is. `spoil` alters what
    the plain search put in the program's place: a planted fault."""
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
        got = reference_search.search(
            rules, params0, cfg, roots, valid,
            candidates_of(cfg["mcts"], cfg["action_dim"]), quant,
        )
    if spoil is not None:  # a planted fault, for `calibrate`
        got = spoil(got)
    invalid_mass = float(
        np.where(valid, 0.0, np.nan_to_num(got["policy"])).sum(axis=1).max()
    )
    first = first_move_numbers(cfg, rules, params0, host, got, valid, traffic)
    read = first.pop("read")
    moves = follow_moves(rules, host, n_step, train["MAX_EPISODE_MOVES"])
    read["moves_followed"] = moves.pop("followed")
    rets = returns(host, n_step, train["GAMMA"])
    read["returns"], read["return_gap_widest"] = rets["rows"], rets["widest"]

    ring, want = host["ring"], masked_rows(host)
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


def calibrate(driver, parts, with_detail=False) -> dict:
    """The readings of one seed after a few dispatches: the program's,
    and the control's in its place."""
    driver.setup()
    for _ in range(CALIBRATE_UNITS):
        driver.unit()
    driver.release()
    out = {}
    if "program" in parts:
        started = time.perf_counter()
        out["program"] = driver.check()
        out["program_read"] = driver.read
        out["reference_s"] = time.perf_counter() - started
    if "backup" in parts:
        # The plain search in the program's place with the root's own
        # value left out of its mean: the smallest fault of the level
        # (one share in seventeen) that `root_value_gap_mean` is for.
        top = candidates_of(driver.cfg["mcts"], driver.cfg["action_dim"])
        out["backup"] = driver.check(
            spoil=lambda got: {
                **got,
                "root_value": (
                    got["root_value"] * (top + 1.0) - got["v0"]
                ) / top,
            }
        )
    if "control" in parts:
        out["control"] = driver.check(quant=reference.fp8)
        out["control_read"] = driver.read
    return out
