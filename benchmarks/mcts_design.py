"""Measure the MCTS design bets.

Three deliberate departures from the reference's C++ search are
quantified here on the tiny board (fast enough for CPU; the relative
signals, not absolute scores, are what the bets are about):

(a) **No subtree reuse** (reference re-roots the previous tree each
    move, `alphatriangle/rl/self_play/worker.py:273-280`; we re-search
    from scratch with fresh root priors, `mcts/search.py` module doc).
    Measured as the score-vs-simulation-budget curve: the value of
    reuse is bounded by the marginal value of extra simulations, so a
    flat curve past the operating point (64 sims) means reuse would buy
    little; a steep curve means it matters.

(b) **Root-value bootstrap** (we bootstrap the n-step window with the
    search's backed-up root value; the reference queries the raw
    network, `worker.py:418`). Measured as MSE of each predictor
    against the realized discounted return-to-go of the played games.

(c) **Orphan node slots** (duplicate edges inside a wave burn a slot,
    `mcts/search.py` `wasted_slots`). Measured as the wasted-simulation
    fraction per wave size at the 64-sim budget.

Usage:  JAX_PLATFORMS=cpu python benchmarks/mcts_design.py
Writes benchmarks/mcts_design_results.json and prints a summary; the
prose writeup lives in docs/MCTS_DESIGN.md.
"""

import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from alphatriangle_tpu.config import (
    AlphaTriangleMCTSConfig,
    EnvConfig,
    ModelConfig,
    expected_other_features_dim,
)
from alphatriangle_tpu.env.engine import TriangleEnv
from alphatriangle_tpu.features.core import get_feature_extractor
from alphatriangle_tpu.mcts import BatchedMCTS
from alphatriangle_tpu.nn.network import NeuralNetwork

B = 64  # games per condition
MAX_MOVES = 60
SEEDS = (0, 1)


def tiny_world():
    env_cfg = EnvConfig(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[(0, 4), (0, 4), (0, 4)],
        NUM_SHAPE_SLOTS=1,
    )
    model_cfg = ModelConfig(
        GRID_INPUT_CHANNELS=1,
        CONV_FILTERS=[8],
        CONV_KERNEL_SIZES=[3],
        CONV_STRIDES=[1],
        NUM_RESIDUAL_BLOCKS=1,
        RESIDUAL_BLOCK_FILTERS=8,
        USE_TRANSFORMER=False,
        FC_DIMS_SHARED=[16],
        POLICY_HEAD_DIMS=[16],
        VALUE_HEAD_DIMS=[16],
        NUM_VALUE_ATOMS=21,
        OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(env_cfg),
    )
    env = TriangleEnv(env_cfg)
    fe = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    return env, fe, net, model_cfg


def rollout(env, fe, net, mcts, seed, record_values=False, b=B, max_moves=MAX_MOVES):
    """Play B games to completion with greedy-from-visits moves.

    Returns (mean_score, wasted_fraction, value_records) where
    value_records rows are (root_value, raw_value, reward, done) per
    (move, game) for the bootstrap comparison.
    """
    states = env.reset_batch(jax.random.split(jax.random.PRNGKey(seed), b))
    total_sims = 0
    total_wasted = 0
    recs = []
    for move in range(max_moves):
        done = np.asarray(states.done)
        if done.all():
            break
        out = mcts.search(
            net.variables, states, jax.random.PRNGKey(seed * 1000 + move)
        )
        counts = np.asarray(out.visit_counts)
        live = ~done
        total_sims += int(live.sum()) * mcts.config.max_simulations
        total_wasted += int(np.asarray(out.wasted_slots)[live].sum())
        actions = np.where(counts.sum(axis=1) > 0, counts.argmax(axis=1), 0)
        if record_values:
            _, raw_values, _ = mcts._evaluate(net.variables, states)
            root_v = np.asarray(out.root_value)
            raw_v = np.asarray(raw_values)
        states, rewards, _ = env.step_batch(
            states, jnp.asarray(actions, dtype=jnp.int32)
        )
        if record_values:
            recs.append(
                np.stack(
                    [root_v, raw_v, np.asarray(rewards), live.astype(float)],
                    axis=1,
                )
            )
    scores = float(np.asarray(states.score).mean())
    wasted_frac = total_wasted / max(total_sims, 1)
    return scores, wasted_frac, recs


def bootstrap_mse(recs):
    """MSE of root-value vs raw-value predictions of return-to-go."""
    arr = np.stack(recs)  # (T, B, 4): root_v, raw_v, reward, live
    t_len = arr.shape[0]
    g = np.zeros(arr.shape[1])
    returns = np.zeros((t_len, arr.shape[1]))
    for t in range(t_len - 1, -1, -1):
        g = arr[t, :, 2] + g  # discount=1.0 in these runs
        returns[t] = g
    live = arr[:, :, 3] > 0
    root_err = ((arr[:, :, 0] - returns) ** 2)[live]
    raw_err = ((arr[:, :, 1] - returns) ** 2)[live]
    return float(root_err.mean()), float(raw_err.mean()), int(live.sum())


def main() -> None:
    env, fe, net, model_cfg = tiny_world()
    results: dict = {"board": "3x4/1-slot", "games_per_condition": B * len(SEEDS)}

    # (a) score vs simulation budget (no-reuse bet).
    curve = {}
    for sims in (8, 16, 32, 64, 128):
        cfg = AlphaTriangleMCTSConfig(
            max_simulations=sims, max_depth=8, mcts_batch_size=32
        )
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        t0 = time.time()
        scores = [rollout(env, fe, net, mcts, s)[0] for s in SEEDS]
        curve[sims] = {
            "mean_score": round(float(np.mean(scores)), 3),
            "per_seed": [round(s, 3) for s in scores],
            "seconds": round(time.time() - t0, 1),
        }
        print(f"(a) sims={sims}: {curve[sims]}", flush=True)
    results["score_vs_sims"] = curve

    # (b) bootstrap quality: root value vs raw network value.
    cfg = AlphaTriangleMCTSConfig(
        max_simulations=64, max_depth=8, mcts_batch_size=32
    )
    mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
    root_mses, raw_mses = [], []
    for s in SEEDS:
        _, _, recs = rollout(env, fe, net, mcts, 100 + s, record_values=True)
        root_mse, raw_mse, n = bootstrap_mse(recs)
        root_mses.append(root_mse)
        raw_mses.append(raw_mse)
        print(f"(b) seed={s}: root_mse={root_mse:.3f} raw_mse={raw_mse:.3f} n={n}", flush=True)
    results["bootstrap_mse"] = {
        "root_value": round(float(np.mean(root_mses)), 3),
        "raw_network": round(float(np.mean(raw_mses)), 3),
    }

    # (c) wasted-slot fraction by wave size at the 64-sim budget.
    # NOTE: on the tiny board the reachable tree under a root often has
    # fewer nodes than the simulation budget, so most sims necessarily
    # revisit (tree exhausted) — the flagship section below is the
    # honest operating-point number.
    waste = {}
    for wave in (1, 8, 16, 32, 64):
        cfg = AlphaTriangleMCTSConfig(
            max_simulations=64, max_depth=8, mcts_batch_size=wave
        )
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        fracs = [rollout(env, fe, net, mcts, 200 + s)[1] for s in SEEDS]
        waste[wave] = round(float(np.mean(fracs)), 4)
        print(f"(c) wave={wave}: wasted_frac={waste[wave]}", flush=True)
    results["wasted_slot_fraction_by_wave_tiny"] = waste

    # (c') flagship board (8x15, 3 slots, action_dim 360): the real
    # operating point. Smaller B and a move cap keep CPU time sane.
    if os.environ.get("DESIGN_FLAGSHIP", "1") == "1":
        f_env_cfg = EnvConfig()
        f_model_cfg = ModelConfig(
            GRID_INPUT_CHANNELS=1,
            CONV_FILTERS=[8],
            CONV_KERNEL_SIZES=[3],
            CONV_STRIDES=[1],
            NUM_RESIDUAL_BLOCKS=1,
            RESIDUAL_BLOCK_FILTERS=8,
            USE_TRANSFORMER=False,
            FC_DIMS_SHARED=[16],
            POLICY_HEAD_DIMS=[16],
            VALUE_HEAD_DIMS=[16],
            NUM_VALUE_ATOMS=21,
            OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(
                f_env_cfg
            ),
        )
        f_env = TriangleEnv(f_env_cfg)
        f_fe = get_feature_extractor(f_env, f_model_cfg)
        f_net = NeuralNetwork(f_model_cfg, f_env_cfg, seed=0)
        fwaste = {}
        for wave in (1, 32):
            cfg = AlphaTriangleMCTSConfig(
                max_simulations=64, max_depth=8, mcts_batch_size=wave
            )
            mcts = BatchedMCTS(f_env, f_fe, f_net.model, cfg, f_net.support)
            t0 = time.time()
            _, frac, _ = rollout(
                f_env, f_fe, f_net, mcts, 300, b=8, max_moves=12
            )
            fwaste[wave] = {
                "wasted_frac": round(frac, 4),
                "seconds": round(time.time() - t0, 1),
            }
            print(f"(c') flagship wave={wave}: {fwaste[wave]}", flush=True)
        results["wasted_slot_fraction_by_wave_flagship"] = fwaste

        # (c'') wave_noise_scale sweep at wave=32: the knob that trades
        # descent diversity (fewer duplicate edges) against PUCT
        # fidelity (noise perturbs the argmax).
        nsweep = {}
        for noise in (0.0, 0.1, 0.25, 0.5, 1.0):
            cfg = AlphaTriangleMCTSConfig(
                max_simulations=64,
                max_depth=8,
                mcts_batch_size=32,
                wave_noise_scale=noise,
            )
            mcts = BatchedMCTS(f_env, f_fe, f_net.model, cfg, f_net.support)
            score, frac, _ = rollout(
                f_env, f_fe, f_net, mcts, 400, b=8, max_moves=12
            )
            nsweep[str(noise)] = {
                "wasted_frac": round(frac, 4),
                "mean_score_12_moves": round(score, 2),
            }
            print(f"(c'') noise={noise}: {nsweep[str(noise)]}", flush=True)
        results["flagship_noise_sweep_wave32"] = nsweep

    out_path = Path(__file__).parent / "mcts_design_results.json"
    out_path.write_text(json.dumps(results, indent=2))
    print(json.dumps(results))


if __name__ == "__main__":
    main()
