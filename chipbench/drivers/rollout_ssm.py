"""The rollout driver for a net whose trunk is a hybrid state-space
stack (Mamba-2 mixers, routed experts in a latent, attention layers,
every layer one half: `chipbench/configs/nemotron-super-ep4.json`).

`rollout_trunk.Driver` with the three modules it names through its
globals exchanged, as `rollout_hybrid.py` does it: the stack's settings
and the plain net are `reference_nemotron_h`'s, the FLOP of an
evaluation `flops_nemotron_h`'s, and the routers' selection biases are
balanced under top 22 of 512 on that reference's activations
(`router_balance_ssm`). Its dispatch, its whole-period unit, its
release and its comparison (`rollout_trunk.compare_dispatch` with
`PlainSearch` given the plain net) are used as they are, and so is its
`calibrate`. One counter more comes out of the chunk's harvest:
`ssm_tokens`, the tokens the state-space layers' scan took, summed over
the window.

A program whose `TrunkConfig` does not know these layers cannot run the
cell: the driver says so and exits before anything is built.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops_nemotron_h, reference_nemotron_h, router_balance_ssm, weights
from . import rollout, rollout_trunk
from .rollout_trunk import PlainSearch, calibrate  # noqa: F401


def make_variables(configs: dict, cfg: dict, key):
    """`rollout_trunk.make_variables` with this stack's balancing."""
    variables = weights.make_variables(configs, key)
    jax.block_until_ready(variables)
    started = time.perf_counter()
    grid = router_balance_ssm.sample_boards(configs, jax.random.fold_in(key, 7))
    params = router_balance_ssm.balance(variables["params"], cfg, grid)
    jax.block_until_ready(params)
    return {**variables, "params": params}, time.perf_counter() - started


class Driver(rollout_trunk.Driver):
    def __init__(self, cell, configs, seed, spans):
        settings = reference_nemotron_h.trunk_settings(cell["config_file"])
        try:
            from alphatriangle_tpu.config import TrunkConfig

            trunk = TrunkConfig(**settings)
        except (ImportError, ValueError) as refusal:
            raise SystemExit(
                f"chipbench: {cell['name']} needs a program whose TrunkConfig "
                "(nn/trunk.py) has state_space layers, layers of one half and "
                "experts in a latent; this checkout's refuses the stack: "
                f"{refusal}"
            ) from None
        configs = {
            **configs, "model": configs["model"].model_copy(update={"TRUNK": trunk})
        }
        rollout.Driver.__init__(self, cell, configs, seed, spans)
        cfg = self.cfg
        self.plain = PlainSearch(
            lambda p, g, o, quant: reference_nemotron_h.forward(p, cfg, g, o, quant),
            cfg["model"],
            self.traffic["reference_block"],
        )
        self.whole_periods = True
        self.expert_tokens = 0
        self.routed = 0
        self.ssm_tokens = 0
        self.dispatches_before = 0

    def setup(self) -> None:
        """`rollout_trunk.Driver.setup` with this driver's weights."""
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
        self.ssm_tokens = 0

    def dispatch(self) -> int:
        work = super().dispatch()
        self.ssm_tokens += int(
            np.asarray(self.engine.last_trace["ssm_tokens"], np.int64).sum()
        )
        return work

    def counters(self) -> dict:
        roots = (self.dispatches - self.dispatches_before) * self.lanes * self.moves
        evaluations = self.simulations + roots
        here = int(np.sum(self.expert_tokens))
        return {
            "simulations": self.simulations,
            "forward_flops": flops_nemotron_h.forward_flops(
                self.cfg, here / max(evaluations, 1)
            ),
            "expert_tokens": np.asarray(self.expert_tokens).tolist(),
            "routed": self.routed,
            "ssm_tokens": self.ssm_tokens,
        }
