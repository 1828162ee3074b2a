"""Batched MCTS tests: helper contracts, search invariants on the tiny
env, and the acceptance bar — MCTS with an untrained net
beats uniform-random play on average score."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.config import AlphaTriangleMCTSConfig
from alphatriangle_tpu.env.engine import TriangleEnv
from alphatriangle_tpu.features.core import get_feature_extractor
from alphatriangle_tpu.mcts import (
    BatchedMCTS,
    PolicyGenerationError,
    policy_target_from_visits,
    select_action_from_visits,
)
from alphatriangle_tpu.mcts.helpers import select_action_from_visits_dict
from alphatriangle_tpu.nn.network import NeuralNetwork


class TestHelpers:
    def test_policy_target_normalizes(self):
        counts = jnp.array([[4.0, 0.0, 12.0, 0.0]])
        target = policy_target_from_visits(counts)
        np.testing.assert_allclose(
            np.asarray(target[0]), [0.25, 0.0, 0.75, 0.0], rtol=1e-6
        )

    def test_policy_target_zero_visits_fallback(self):
        counts = jnp.zeros((1, 4))
        mask = jnp.array([[True, False, True, False]])
        target = policy_target_from_visits(counts, mask)
        np.testing.assert_allclose(np.asarray(target[0]), [0.5, 0, 0.5, 0])

    def test_greedy_selection(self):
        counts = jnp.array([[1.0, 7.0, 2.0, 0.0]])
        a = select_action_from_visits(counts, 0.0, jax.random.PRNGKey(0))
        assert int(a[0]) == 1

    def test_sampling_never_picks_zero_count(self):
        counts = jnp.array([[0.0, 5.0, 5.0, 0.0]])
        for seed in range(20):
            a = select_action_from_visits(
                counts, 1.5, jax.random.PRNGKey(seed)
            )
            assert int(a[0]) in (1, 2)

    def test_low_temperature_concentrates(self):
        counts = jnp.array([[1.0, 10.0, 2.0, 1.0]])
        picks = [
            int(
                select_action_from_visits(
                    counts, 0.1, jax.random.PRNGKey(s)
                )[0]
            )
            for s in range(25)
        ]
        assert picks.count(1) >= 23

    def test_all_zero_row_yields_sentinel(self):
        counts = jnp.array([[0.0, 0.0], [3.0, 1.0]])
        a = select_action_from_visits(counts, 0.0, jax.random.PRNGKey(0))
        assert a.tolist() == [-1, 0]

    def test_per_game_temperature_vector(self):
        counts = jnp.array([[1.0, 9.0], [9.0, 1.0]])
        a = select_action_from_visits(
            counts, jnp.array([0.0, 0.0]), jax.random.PRNGKey(0)
        )
        assert a.tolist() == [1, 0]

    def test_dict_adapter(self):
        assert select_action_from_visits_dict({3: 10, 1: 1}, 6, 0.0) == 3
        with pytest.raises(PolicyGenerationError):
            select_action_from_visits_dict({}, 6, 0.0)
        with pytest.raises(PolicyGenerationError):
            select_action_from_visits_dict({9: 3}, 6, 0.0)


@pytest.fixture(scope="module")
def mcts_world(tiny_env_config, tiny_model_config, tiny_mcts_config):
    env = TriangleEnv(tiny_env_config)
    fe = get_feature_extractor(env, tiny_model_config)
    net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
    mcts = BatchedMCTS(env, fe, net.model, tiny_mcts_config, net.support)
    return env, fe, net, mcts


class TestSearch:
    B = 8

    def _roots(self, env, seed=0):
        return env.reset_batch(jax.random.split(jax.random.PRNGKey(seed), self.B))

    def test_visit_counts_invariants(self, mcts_world, tiny_mcts_config):
        env, _, net, mcts = mcts_world
        roots = self._roots(env)
        out = mcts.search(net.variables, roots, jax.random.PRNGKey(1))
        counts = np.asarray(out.visit_counts)
        assert counts.shape == (self.B, env.action_dim)
        # Every simulation backs up through exactly one root child.
        np.testing.assert_allclose(
            counts.sum(axis=1), tiny_mcts_config.max_simulations
        )
        # Visits only on valid root actions.
        valid = np.asarray(env.valid_mask_batch(roots))
        assert np.all(counts[~valid] == 0)

    def test_root_value_finite(self, mcts_world):
        env, _, net, mcts = mcts_world
        out = mcts.search(
            net.variables, self._roots(env), jax.random.PRNGKey(2)
        )
        rv = np.asarray(out.root_value)
        assert np.all(np.isfinite(rv))

    def test_deterministic_given_rng(self, mcts_world):
        env, _, net, mcts = mcts_world
        roots = self._roots(env)
        o1 = mcts.search(net.variables, roots, jax.random.PRNGKey(7))
        o2 = mcts.search(net.variables, roots, jax.random.PRNGKey(7))
        np.testing.assert_array_equal(
            np.asarray(o1.visit_counts), np.asarray(o2.visit_counts)
        )

    def test_noise_changes_with_rng(self, mcts_world):
        env, _, net, mcts = mcts_world
        roots = self._roots(env)
        o1 = mcts.search(net.variables, roots, jax.random.PRNGKey(7))
        o2 = mcts.search(net.variables, roots, jax.random.PRNGKey(8))
        assert not np.array_equal(
            np.asarray(o1.root_prior), np.asarray(o2.root_prior)
        )

    def test_mcts_beats_random(
        self, mcts_world, tiny_env_config, tiny_mcts_config
    ):
        """The bar: untrained-net MCTS > uniform random play."""
        env, _, net, mcts = mcts_world
        B, max_moves = 16, 40
        rng = np.random.default_rng(0)

        def play(policy_fn, seed):
            states = env.reset_batch(
                jax.random.split(jax.random.PRNGKey(seed), B)
            )
            for move in range(max_moves):
                done = np.asarray(states.done)
                if done.all():
                    break
                actions = policy_fn(states, move)
                states, _, _ = env.step_batch(
                    states, jnp.asarray(actions, dtype=jnp.int32)
                )
            return float(np.asarray(states.score).mean())

        def random_policy(states, move):
            masks = np.asarray(env.valid_mask_batch(states))
            logits = np.where(masks, rng.random(masks.shape), -np.inf)
            # Finished games have all-False masks; action 0 is a no-op.
            return np.where(masks.any(axis=1), logits.argmax(axis=1), 0)

        def mcts_policy(states, move):
            out = mcts.search(
                net.variables, states, jax.random.PRNGKey(1000 + move)
            )
            counts = np.asarray(out.visit_counts)
            return np.where(
                counts.sum(axis=1) > 0, counts.argmax(axis=1), 0
            )

        random_score = np.mean([play(random_policy, s) for s in (11, 22)])
        mcts_score = np.mean([play(mcts_policy, s) for s in (11, 22)])
        assert mcts_score > random_score


class TestWaves:
    """Wave-parallel mechanics: size clamp, duplicate canonicalization,
    wasted-slot accounting, and exact PUCT at wave_size=1."""

    def test_wave_size_clamped_to_divisor(
        self, mcts_world, tiny_mcts_config
    ):
        env, fe, net, _ = mcts_world
        cfg = tiny_mcts_config.model_copy(
            update={"max_simulations": 10, "mcts_batch_size": 4}
        )
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        assert mcts.wave_size == 2 and mcts.num_waves == 5
        cfg = tiny_mcts_config.model_copy(
            update={"max_simulations": 8, "mcts_batch_size": 7}
        )
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        assert mcts.wave_size == 4 and mcts.num_waves == 2

    def test_wave_duplicates_share_one_child_slot(self, mcts_world):
        """After one wave: distinct edges own distinct child slots, and
        the number of live (non-orphan) slots matches wasted_slots."""
        env, fe, net, mcts = mcts_world
        B = 4
        roots = env.reset_batch(jax.random.split(jax.random.PRNGKey(5), B))
        rng = jax.random.PRNGKey(6)
        tree = mcts._init_tree(net.variables, roots, rng)
        tree, wasted, base = mcts._wave(
            net.variables,
            B,
            (tree, jnp.zeros((B,), jnp.int32), jnp.int32(1)),
            jax.random.fold_in(rng, 0),
        )
        assert int(base) == 1 + mcts.wave_size
        children = np.asarray(tree.children)
        wasted = np.asarray(wasted)
        for b in range(B):
            kids = children[b][children[b] >= 0]
            # No slot is shared across different edges.
            assert len(kids) == len(set(kids.tolist()))
            # Live slots + orphans tile the wave exactly.
            assert len(kids) == mcts.wave_size - int(wasted[b])
            assert 0 <= wasted[b] < mcts.wave_size

    def test_wasted_slots_bounded_full_search(
        self, mcts_world, tiny_mcts_config
    ):
        env, _, net, mcts = mcts_world
        roots = env.reset_batch(jax.random.split(jax.random.PRNGKey(9), 8))
        out = mcts.search(net.variables, roots, jax.random.PRNGKey(10))
        wasted = np.asarray(out.wasted_slots)
        assert np.all(wasted >= 0)
        assert np.all(wasted <= tiny_mcts_config.max_simulations)

    def test_wave_size_one_is_noise_free(self, mcts_world, tiny_mcts_config):
        """W=1 must reproduce exact sequential PUCT: identical visit
        counts for different wave RNG streams (no Gumbel perturbation)."""
        env, fe, net, _ = mcts_world
        cfg = tiny_mcts_config.model_copy(
            update={"mcts_batch_size": 1, "dirichlet_epsilon": 0.0}
        )
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        assert mcts.wave_size == 1
        roots = env.reset_batch(jax.random.split(jax.random.PRNGKey(3), 4))
        o1 = mcts.search(net.variables, roots, jax.random.PRNGKey(1))
        o2 = mcts.search(net.variables, roots, jax.random.PRNGKey(2))
        np.testing.assert_array_equal(
            np.asarray(o1.visit_counts), np.asarray(o2.visit_counts)
        )
