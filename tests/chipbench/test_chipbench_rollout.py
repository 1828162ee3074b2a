"""The rollout driver: its plain rules of the board (legality, moves,
rewards, hands, endings, features) against the engine's, its reading of
a fast search's policy target on hand-made targets, one run end to end
at the tiny board, the control in the program's place, and the faults a
rollout cell can have, each of which has to come out as not correct."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_cell import tiny_cell

from chipbench import manifest, reference_env, run
from chipbench.drivers import rollout

SEED = 2**31 + 11


def test_plain_rules_agree_with_the_engine_on_the_real_board():
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor

    cfg = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    built = manifest.program_configs(cfg)
    env = TriangleEnv(built["env"])
    extract = get_feature_extractor(env, built["model"]).extract_batch
    rules = reference_env.Rules(cfg["env"])
    assert [tuple(s) for s in rules.bank] == [tuple(s) for s in env.bank.shapes]
    assert (rules.death == np.asarray(env.geometry.death)).all()
    assert {tuple(np.flatnonzero(line)) for line in rules.lines} == {
        tuple(np.flatnonzero(m.reshape(-1))) for m in env.geometry.line_masks
    }
    lanes = 24
    states = env.reset_batch(jax.random.split(jax.random.PRNGKey(1), lanes))
    step = jax.jit(jax.vmap(env.step))
    mask = jax.jit(jax.vmap(env.valid_action_mask))
    rng = np.random.default_rng(0)
    ended = cleared = 0
    for _ in range(30):
        live = ~np.asarray(states.done)
        board = np.stack(
            [reference_env.unpack(w, cfg["env"]).reshape(-1) for w in np.asarray(states.occupied)]
        )
        hand = np.asarray(states.shape_idx)
        valid = np.asarray(mask(states))
        assert (rules.legal(board, hand)[live] == valid[live]).all()
        grid, other = rules.features(
            board, hand, np.asarray(states.score), np.asarray(states.step_count)
        )
        their_grid, their_other = extract(states)
        assert (grid == np.asarray(their_grid)).all()
        assert np.abs(other - np.asarray(their_other)).max() < 1e-6
        moves = np.array([rng.choice(np.flatnonzero(v)) if v.any() else 0 for v in valid])
        slot, origin = moves // rules.cells, moves % rules.cells
        child, gain = rules.place(board, hand[np.arange(lanes), slot], origin)
        keys, drawn = reference_env.draw_hands(
            np.asarray(states.key), rules.slots, len(rules.bank)
        )
        left = rules.hand_after(hand, slot, drawn)
        stuck = ~rules.legal(child, left).any(axis=1)
        states, reward, done = step(states, jnp.asarray(moves, jnp.int32))
        after = np.stack(
            [reference_env.unpack(w, cfg["env"]).reshape(-1) for w in np.asarray(states.occupied)]
        )
        assert (after[live] == child[live]).all()
        assert (np.asarray(states.shape_idx)[live] == left[live]).all()
        assert (np.asarray(states.key)[live] == keys[live]).all()
        assert (np.asarray(done)[live] == stuck[live]).all()
        mine = gain + np.where(stuck, cfg["env"]["PENALTY_GAME_OVER"], 0.0)
        assert (np.asarray(reward)[live] == mine[live]).all()
        ended += int((stuck & live).sum())
        cleared += int((np.asarray(states.last_cleared) > 0)[live].sum())
    assert ended >= lanes // 2 and cleared > 0  # both were seen


def _target(logits, valid, tried, value, root_value):
    """softmax(logit + 51 x value) as a fast search writes it."""
    completed = np.where(tried, value, root_value)
    score = np.where(valid, logits + 51.0 * completed, -np.inf).astype(np.float32)
    shifted = np.exp(score - score.max(), dtype=np.float32)
    return shifted / shifted.sum()


@pytest.mark.parametrize(
    "best,why",
    [(3.0, "the unsearched underflow"), (0.4, "the unsearched show, as a crowd"),
     (-9.0, "every candidate is a lost game: only the crowd shows")],
)
def test_searched_actions_are_read_off_the_target(best, why):
    rng = np.random.default_rng(3)
    valid = np.zeros(360, bool)
    valid[rng.choice(360, 60, replace=False)] = True
    logits = rng.normal(0, 0.3, 360).astype(np.float32)
    tried = np.zeros(360, bool)
    tried[np.argsort(np.where(valid, logits, -np.inf))[-16:]] = True
    value = rng.normal(0, 0.2, 360) + np.linspace(best - 1, best, 360)
    policy = _target(logits, valid, tried, value, 0.1)
    seen = rollout.searched(policy, logits + rng.normal(0, 0.01, 360), valid)
    assert not (seen & ~tried).any(), why
    assert seen.any() == (best > -5)


@pytest.fixture(scope="module")
def ran():
    return run.run_cell(
        tiny_cell("flagship-rollout"), seed=SEED, seconds=0.3, trace=False,
        require_chip=False,
    )


def test_a_run_is_correct_and_counts_lane_moves(ran):
    assert ran["correct"] is True, ran["compared"]
    assert set(ran["metrics"]) == {"selfplay_moves_per_s", "setup_s"}
    assert ran["metrics"]["selfplay_moves_per_s"]["value"] > 0
    exact = (
        "feature_mismatch", "invalid_mass", "step_mismatch", "return_mismatch",
        "ring_mismatch", "stalled_lanes",
    )
    assert all(ran["compared"][n] == {"value": 0.0, "limit": 0} for n in exact)
    # float32 compute at the tiny size: the plain search, the moves and
    # the n-step returns agree with the program to rounding.
    for name in ("root_value_gap_mean", "target_value_gap_mean"):
        assert ran["compared"][name]["value"] < 1e-5, name


def test_the_control_in_the_programs_place_is_not_correct():
    from chipbench import reference
    from chipbench.spans import Spans

    cell = tiny_cell("flagship-rollout")
    driver = rollout.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    for _ in range(3):
        driver.unit()
    driver.release()
    ok, numbers = run.compare(driver.check(), _limits(cell))
    assert ok, numbers
    ok, numbers = run.compare(driver.check(quant=reference.fp8), _limits(cell))
    assert not ok, numbers


def _limits(cell):
    return {k: v for k, v in cell["limits"].items() if k != "window_compiles"}


def _answers_altered(monkeypatch):
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine

    real = SelfPlayEngine.play_moves_device

    def broken(self, num_moves):
        result, payload = real(self, num_moves)
        flush = dict(payload["flush"])  # each lane gets its neighbour's target
        flush["policy"] = jnp.roll(flush["policy"], 1, axis=1)
        return result, {**payload, "flush": flush}

    monkeypatch.setattr(SelfPlayEngine, "play_moves_device", broken)


def _harvest(monkeypatch, change):
    """The fault sits where the chunk's outputs reach the host."""
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine

    real = SelfPlayEngine.play_moves_device

    def broken(self, num_moves):
        result, payload = real(self, num_moves)
        payload = {k: dict(v) for k, v in payload.items()}
        self.last_trace = dict(self.last_trace)
        change(self.last_trace, payload)
        return result, payload

    monkeypatch.setattr(SelfPlayEngine, "play_moves_device", broken)


def _reward_altered(monkeypatch):
    def change(harvest, payload):
        harvest["reward"] = harvest["reward"] + np.float32(1.0)

    _harvest(monkeypatch, change)


def _return_altered(monkeypatch):
    def change(harvest, payload):  # every value target a little high
        payload["mat"]["ret"] = payload["mat"]["ret"] + 0.01
        payload["flush"]["ret"] = payload["flush"]["ret"] + 0.01

    _harvest(monkeypatch, change)


def _root_value_altered(monkeypatch):
    def change(harvest, payload):  # the backup's mean taken over one too many
        harvest["root_value"] = harvest["root_value"] * np.float32(0.9) - 0.05

    _harvest(monkeypatch, change)


def _state_unchanged(monkeypatch):
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine

    real = SelfPlayEngine.play_moves_device

    def broken(self, num_moves):
        kept = jax.tree_util.tree_map(jnp.copy, self._carry)
        out = real(self, num_moves)
        self._carry = kept  # the boards never move on
        return out

    monkeypatch.setattr(SelfPlayEngine, "play_moves_device", broken)


@pytest.mark.parametrize(
    "fault",
    [
        _answers_altered, _state_unchanged, _reward_altered, _return_altered,
        _root_value_altered,
    ],
)
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault):
    fault(monkeypatch)
    out = run.run_cell(
        tiny_cell("flagship-rollout"), seed=SEED, seconds=0.1, trace=False,
        require_chip=False,
    )
    assert out["correct"] is False
    assert [n for n, c in out["compared"].items() if c["value"] > c["limit"]]
