"""Elo ladder over a run's checkpoints: paired round-robin arena.

Restores every checkpoint of a run (or an explicit list), plays each
pair head-to-head on the SAME paired hands (identical reset keys +
step-indexed shape draws, so hand luck cancels — the property the
`eval` command's arena also leans on), and fits Elo ratings to the
pairwise win rates by logistic regression (simple iterative update).

Usage:
  JAX_PLATFORMS=cpu python benchmarks/elo_ladder.py --run-name my_run \
      [--root-dir DIR] [--games 64] [--sims 32] [--max-moves 120]

Writes benchmarks/elo_ladder_<run>.json and prints the table.
"""

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from alphatriangle_tpu.utils.helpers import enforce_platform  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-name", required=True)
    ap.add_argument("--root-dir", default=None)
    ap.add_argument("--games", type=int, default=64)
    ap.add_argument("--sims", type=int, default=32)
    ap.add_argument("--max-moves", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--device", default=None, choices=["auto", "tpu", "cpu"]
    )
    ap.add_argument(
        "--max-checkpoints",
        type=int,
        default=6,
        help="Evenly subsample to at most this many rungs.",
    )
    args = ap.parse_args()
    enforce_platform(args.device or "auto")

    import jax

    from alphatriangle_tpu.utils.helpers import (  # noqa: E402
        enable_persistent_compilation_cache,
    )

    # Re-call with the resolved backend: the unpinned-auto case defers
    # (utils/helpers.py), and the ladder compiles the flagship search
    # programs repeatedly across rungs.
    enable_persistent_compilation_cache()

    import numpy as np

    from alphatriangle_tpu.arena import play_service
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.mcts import BatchedMCTS
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import Trainer
    from alphatriangle_tpu.stats.persistence import CheckpointManager

    persistence = PersistenceConfig(RUN_NAME=args.run_name)
    if args.root_dir:
        persistence = persistence.model_copy(
            update={"ROOT_DATA_DIR": args.root_dir}
        )

    # Rebuild the run's own board/net from its configs.json dump.
    from alphatriangle_tpu.config.run_configs import (
        load_run_configs_or_default,
    )

    env_cfg, model_cfg = load_run_configs_or_default(
        persistence.get_run_base_dir()
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=args.sims)
    train_cfg = TrainConfig(RUN_NAME=args.run_name)
    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    ckpt_dir = persistence.get_checkpoint_dir()
    mgr = CheckpointManager(persistence)
    steps = mgr.list_steps()
    if len(steps) < 2:
        raise SystemExit(f"Need >=2 checkpoints under {ckpt_dir}; found {steps}")
    if len(steps) > args.max_checkpoints:
        idx = np.linspace(0, len(steps) - 1, args.max_checkpoints)
        steps = [steps[int(i)] for i in idx]
    print(f"ladder rungs (steps): {steps}")

    # One net + trainer + ONE policy service for the whole ladder:
    # every rung is a hot weight reload into the same compiled
    # `serve/b<games>` search (the service reads net.variables at
    # dispatch time), so the heavy search program compiles once and
    # ladder traffic runs the same session API served "human" traffic
    # does (serving/service.py, docs/SERVING.md).
    from alphatriangle_tpu.serving import PolicyService

    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    trainer = Trainer(net, train_cfg)
    mcts = BatchedMCTS(env, extractor, net.model, mcts_cfg, net.support)
    service = PolicyService(
        env, extractor, net, mcts, slots=args.games
    )

    # Scores are deterministic per rung given the fixed keys, so the
    # full round-robin needs one playout per rung.
    scores = {}
    for step in steps:
        loaded = mgr.restore_path(
            str(ckpt_dir / f"step_{step:08d}"), trainer.state
        )
        assert loaded.train_state is not None, step
        trainer.set_state(loaded.train_state)
        trainer.sync_to_network()
        service.reload_weights()  # counted hot swap, zero recompiles
        scores[step], _, _ = play_service(
            service, args.games, args.max_moves, args.seed
        )

    # Win-rate matrix + Elo fit via the league subsystem's shared
    # rating math (league/pool.py) — the ladder is a thin client of it.
    from alphatriangle_tpu.league import fit_elo, pairwise_win_fraction

    n = len(steps)
    wins = np.zeros((n, n))
    # Clip away 0/1 winrates: the Bradley-Terry MLE is unbounded for a
    # never-lost pairing, so an unclipped fit would just ride the
    # iteration cap instead of the data.
    eps = 1.0 / (2.0 * args.games)
    for i, a in enumerate(steps):
        for j, b in enumerate(steps):
            if i == j:
                continue
            # paired=True: both rungs played the SAME hands, so the
            # element-wise comparison cancels hand luck.
            w = pairwise_win_fraction(scores[a], scores[b], paired=True)
            wins[i, j] = min(max(w, eps), 1.0 - eps)

    elo = fit_elo(wins)

    table = [
        {
            "step": steps[i],
            "elo": round(float(elo[i]), 1),
            "mean_score": round(float(scores[steps[i]].mean()), 3),
            "mean_winrate": round(
                float(wins[i].sum() / max(n - 1, 1)), 3
            ),
        }
        for i in range(n)
    ]
    table.sort(key=lambda r: -r["elo"])
    out = {
        "run": args.run_name,
        "games": args.games,
        "sims": args.sims,
        "ladder": table,
    }
    out_path = Path(__file__).parent / f"elo_ladder_{args.run_name}.json"
    out_path.write_text(json.dumps(out, indent=2))
    for row in table:
        print(row)


if __name__ == "__main__":
    main()
