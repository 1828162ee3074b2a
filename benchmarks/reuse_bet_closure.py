"""Close the subtree-reuse bet at flagship scale (round 5).

`docs/MCTS_DESIGN.md` §a dropped the reference's subtree reuse
(`alphatriangle/rl/self_play/worker.py:273-280`) on a measured
argument: the value of reuse is bounded by the marginal value of extra
simulations, and the score-vs-sims curve was flat past the 64-sim
operating point. That measurement was CPU, tiny-board, UNTRAINED net —
and the doc's own criterion says to revisit if a trained net steepens
the curve. This harness reruns the curve with a TRAINED checkpoint on
the run's own (flagship) board.

Reading the result: reuse can at best make an S-sim search as strong
as an (S + carried) sim search. If score(128) ~ score(64) with the
trained net, reuse still buys nothing at the operating point and the
no-reuse design stands; a steep 64->128 slope reopens it.

Usage (healthy-chip window, after the training run):
    python benchmarks/reuse_bet_closure.py \
        --run-name tpu_flagship_r5 --root-dir /tmp/tpu_r5_train
Writes benchmarks/reuse_bet_results.json.
"""

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from alphatriangle_tpu.utils.helpers import enforce_platform  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-name", default=None)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--root-dir", default=None)
    ap.add_argument("--games", type=int, default=64)
    ap.add_argument("--max-moves", type=int, default=200)
    ap.add_argument("--sims", default="16,32,64,128")
    ap.add_argument("--seeds", default="0,1")
    ap.add_argument("--device", default=None)
    args = ap.parse_args()
    if not (args.run_name or args.checkpoint):
        ap.error("need --run-name or --checkpoint (a TRAINED net)")

    enforce_platform(args.device or "auto")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from alphatriangle_tpu.config.run_configs import (
        load_run_configs_or_default,
    )
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.mcts import BatchedMCTS
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import Trainer
    from alphatriangle_tpu.stats.persistence import CheckpointManager
    from alphatriangle_tpu.utils.helpers import (
        enable_persistent_compilation_cache,
    )

    enable_persistent_compilation_cache()

    # The run's OWN board/net configs (cli eval pattern).
    if args.run_name:
        persistence = PersistenceConfig(RUN_NAME=args.run_name)
        if args.root_dir:
            persistence = persistence.model_copy(
                update={"ROOT_DATA_DIR": args.root_dir}
            )
        cfg_dir = persistence.get_run_base_dir()
    else:
        cfg_dir = Path(args.checkpoint).resolve().parent.parent
        persistence = PersistenceConfig(RUN_NAME="reuse_bet")
    env_cfg, model_cfg = load_run_configs_or_default(cfg_dir)
    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    trainer = Trainer(net, TrainConfig(RUN_NAME="reuse_bet"))
    mgr = CheckpointManager(persistence)
    loaded = (
        mgr.restore_path(args.checkpoint, trainer.state)
        if args.checkpoint
        else mgr.restore(trainer.state)
    )
    if loaded.train_state is None:
        print("no checkpoint found — the bet needs a TRAINED net",
              file=sys.stderr)
        return 1
    trainer.set_state(loaded.train_state)
    trainer.sync_to_network()
    print(f"restored step {loaded.global_step} from {cfg_dir}", flush=True)

    def rollout(mcts, seed: int) -> float:
        """B games to completion, greedy-from-visits (exploit)."""
        states = env.reset_batch(
            jax.random.split(jax.random.PRNGKey(seed), args.games)
        )
        for move in range(args.max_moves):
            done = np.asarray(states.done)
            if done.all():
                break
            out = mcts.search(
                net.variables, states,
                jax.random.PRNGKey(seed * 1000 + move),
            )
            counts = np.asarray(out.visit_counts)
            actions = np.where(
                counts.sum(axis=1) > 0, counts.argmax(axis=1), 0
            )
            states, _, _ = env.step_batch(
                states, jnp.asarray(actions, dtype=jnp.int32)
            )
        return float(np.asarray(states.score).mean())

    seeds = [int(s) for s in args.seeds.split(",")]
    curve = {}
    for sims in (int(s) for s in args.sims.split(",")):
        cfg = AlphaTriangleMCTSConfig(
            max_simulations=sims,
            max_depth=8,
            mcts_batch_size=min(32, sims),
            dirichlet_epsilon=0.0,  # exploit: the strength probe
        )
        mcts = BatchedMCTS(env, extractor, net.model, cfg, net.support)
        t0 = time.time()
        scores = [rollout(mcts, s) for s in seeds]
        curve[sims] = {
            "mean_score": round(float(np.mean(scores)), 3),
            "per_seed": [round(s, 3) for s in scores],
            "seconds": round(time.time() - t0, 1),
        }
        print(f"sims={sims}: {curve[sims]}", flush=True)

    sims_sorted = sorted(curve)
    op = 64 if 64 in curve else sims_sorted[-2]
    top = sims_sorted[-1]
    gain_past_op = (
        curve[top]["mean_score"] - curve[op]["mean_score"]
        if top != op
        else 0.0
    )
    rel = gain_past_op / max(abs(curve[op]["mean_score"]), 1e-9)
    payload = {
        "board": f"{env_cfg.ROWS}x{env_cfg.COLS}",
        "checkpoint_step": loaded.global_step,
        "backend": jax.default_backend(),
        "games_per_condition": args.games * len(seeds),
        "max_moves": args.max_moves,
        "curve": curve,
        "gain_past_operating_point": round(gain_past_op, 3),
        "gain_relative": round(rel, 4),
        # MCTS_DESIGN.md §a's own criterion, applied to the trained net.
        "verdict": (
            "no-reuse design stands (curve flat past the operating "
            "point with a trained net)"
            if rel < 0.02
            else "REVISIT: trained net steepened the sims curve — "
            "subtree reuse could buy real strength"
        ),
    }
    out = REPO / "benchmarks" / "reuse_bet_results.json"
    out.write_text(json.dumps(payload, indent=2))
    print(json.dumps(payload))
    return 0


if __name__ == "__main__":
    sys.exit(main())
