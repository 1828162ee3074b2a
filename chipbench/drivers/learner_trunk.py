"""The learner-from-ring driver for a net whose trunk is a routed decoder
stack that is TRAINED (`chipbench/configs/glm-flash-ep8.json`):
`learner.Driver`'s group, window, release and the five readings of its
comparison, with these things of its own.

- The program's `ModelConfig` gets its `TRUNK` group from the
  configuration's file (`reference_glm_moe.trunk_settings`, the same
  dict the reference reads). The weights are drawn on the device in
  float32; the routers' selection biases start balanced on a sample of
  the seeded ring's own boards (`router_balance_glm`), and from the
  first step on the program's rule moves them.
- What a learner chip of the deployment holds is the training state and
  the ring. The seeded weights go to the host once the trainer has its
  copy, and the wrapper's serving copy is dropped: self-play would run
  on other chips.
- The routed trunk's counters come off each group's fetch
  (`Trainer.last_counters`): the assignments each held expert computed,
  the loads of all experts, the tokens the trunk took. `step_flops` is
  the work a step really did: the fixed part plus one expert's SwiGLU
  for each assignment counted here, forward + backward.
- `check` follows the first step (the warm-up, the same object the
  window then drives) with the plain reference's step
  (`reference_glm_moe.train_step`) on the same rows: `learner.py`'s five
  readings on the trained leaves, and two of the routers'.
  `bias_rule_mismatch` counts the biases that are not, to the bit, the
  bias before + gamma x sign(mean - load) of the loads THE PROGRAM
  reported: the rule, exact. `load_gap` is the program's loads against
  the reference's, relative L1, worst layer: top-4 choices between
  scores that differ in the fourth decimal flip under bfloat16, so its
  limit comes from calibration.

A program whose `TrunkConfig` cannot describe the stack, or whose
learner does not report the routers' loads, cannot run the cell: the
driver says so and exits before anything is built.
"""

import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops_glm_moe, reference, reference_glm_moe as plain
from .. import router_balance_glm, rows, weights
from . import learner
from .learner import COMPARED, _mu

NUMBERS = (*COMPARED, "bias_rule_mismatch", "load_gap")


def _trained(tree):
    """A params tree on the host without the selection biases (None in
    their places): what the optimizer trains and `learner.py` compares."""
    return plain.without_biases(jax.device_get(tree))


class Driver(learner.Driver):
    def __init__(self, cell, configs, seed, spans):
        settings = plain.trunk_settings(cell["config_file"])
        try:
            from alphatriangle_tpu.config import TrunkConfig

            unknown = set(settings) - set(TrunkConfig.model_fields)
            if unknown:
                raise ValueError(f"TrunkConfig has no {sorted(unknown)}")
            trunk = TrunkConfig(**settings)
        except (ImportError, ValueError) as refusal:
            raise SystemExit(
                f"chipbench: {cell['name']} needs a program whose TrunkConfig "
                "(nn/trunk.py) has latent attention with a compressed query and "
                "a learner that takes its batch in blocks; this checkout's "
                f"refuses the stack: {refusal}"
            ) from None
        configs = {
            **configs, "model": configs["model"].model_copy(update={"TRUNK": trunk})
        }
        super().__init__(cell, configs, seed, spans)
        self.settings = settings
        self.reset_counters()

    def reset_counters(self) -> None:
        self.expert_tokens = 0  # (sparse layers, held), summed
        self.uneven = []  # a step's worst layer: busiest of all experts / mean
        self.trunk_tokens = 0
        self.routed = 0
        self.steps = 0

    # --- set-up -----------------------------------------------------------

    def setup(self) -> None:
        """`learner.Driver.setup` with this driver's weights."""
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer
        from alphatriangle_tpu.rl.trainer import Trainer

        env, model, train = (
            self.configs["env"], self.configs["model"], self.configs["train"]
        )
        self.k = train.FUSED_LEARNER_STEPS
        self.batch = train.BATCH_SIZE
        grid_shape = (model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS)
        other_dim = model.OTHER_NN_INPUT_FEATURES_DIM
        self.row_key = jax.random.fold_in(self.key, 2)

        variables = weights.make_variables(
            self.configs, jax.random.fold_in(self.key, 1)
        )
        jax.block_until_ready(variables)
        started = time.perf_counter()
        sample = router_balance_glm.sample_boards(
            self.cfg, self.row_key, self.traffic["balance_boards"]
        )
        params = router_balance_glm.balance(variables["params"], self.cfg, sample)
        jax.block_until_ready(params)
        print(
            f"chipbench: routers balanced in {time.perf_counter() - started:.1f} s "
            "of set-up",
            file=sys.stderr, flush=True,
        )
        variables = {**variables, "params": params}
        net = NeuralNetwork(model, env, variables=variables)
        try:
            self.trainer = Trainer(net, train)
        except ValueError as refusal:
            raise SystemExit(f"chipbench: {self.cell['name']}: {refusal}") from None
        if not hasattr(self.trainer, "last_counters"):
            raise SystemExit(
                f"chipbench: {self.cell['name']} needs a learner that reports "
                "the routers' loads (Trainer.last_counters); this checkout's "
                "does not."
            )
        # The trainer has its own copy. The seed's weights wait on the
        # host for the reference; a learner chip serves nothing.
        self.params0 = jax.device_get(params)
        net.variables = None
        del variables, params

        self.buffer = DeviceReplayBuffer(
            train, grid_shape, other_dim, env.action_dim, seed=self.seed
        )
        capacity = train.BUFFER_CAPACITY
        block = min(self.traffic["fill_block_rows"], capacity)
        if capacity % block or block % 2:
            raise ValueError(
                f"fill_block_rows {block} must be even and divide the "
                f"ring's {capacity} rows"
            )
        env_file = self.cfg["env"]

        @jax.jit
        def make_block(key, start):
            made = rows.make_rows(
                key,
                start + jnp.arange(block, dtype=jnp.int32),
                env_file,
                other_dim,
                env.action_dim,
                capacity,
            )
            made["mask"] = jnp.ones((block,), bool)
            half = block // 2
            return (
                {f: v[:half] for f, v in made.items()},
                {f: v[half:] for f, v in made.items()},
            )

        for start in range(0, capacity, block):
            mat, flush = make_block(self.row_key, jnp.int32(start))
            added = self.buffer.ingest_payload({"mat": mat, "flush": flush})
            if added != block:
                raise RuntimeError(
                    f"the ring took {added} of {block} seeded rows"
                )

        # The first group: warm-up, and the group `correct` follows.
        samples, outs = self._group()
        state = self.trainer.state
        counted = self.trainer.last_counters
        self.first = {
            "indices": np.stack([s["indices"] for s in samples]),
            "weights": np.stack([s["weights"] for s in samples]),
            "loss": np.asarray([m["total_loss"] for m, _ in outs]),
            "grad_norm": np.asarray([m["grad_norm"] for m, _ in outs]),
            "td": np.stack([td for _, td in outs]),
            "params": _trained(state.params),
            "mu": jax.device_get(_mu(state.opt_state)),
            "bias": plain.biases_of(jax.device_get(state.params), self.cfg),
            "loads": np.asarray(counted["expert_loads"], np.float32),
        }

    # --- the timed path ---------------------------------------------------

    def _group(self):
        samples, outs = super()._group()
        counted = self.trainer.last_counters
        loads = np.asarray(counted["expert_loads"], np.float64)  # (K, layers, E)
        self.expert_tokens = self.expert_tokens + np.asarray(
            counted["expert_tokens"], np.int64
        ).sum(axis=0)
        self.uneven += (loads.max(axis=-1) / loads.mean(axis=-1)).max(axis=-1).tolist()
        self.trunk_tokens += int(counted["trunk_tokens"])
        self.routed += int(counted["routed"])
        self.steps += len(loads)
        return samples, outs

    def start_window(self) -> None:
        self.reset_counters()

    def counters(self) -> dict:
        here = int(np.sum(self.expert_tokens))
        return {
            "steps_per_unit": self.k,
            "step_flops": flops_glm_moe.train_step_flops(
                self.cfg, self.batch, here / max(self.steps, 1)
            ),
            "expert_tokens": np.asarray(self.expert_tokens).tolist(),
            "load_max_over_mean": list(self.uneven),
            "routed": self.routed,
            "trunk_tokens": self.trunk_tokens,
        }

    # --- after the window -------------------------------------------------

    def reference_group(self, quant=None) -> dict:
        """The first group as the plain reference computes it, from the
        seed's weights and the rows the group's slot numbers name."""
        cfg = self.cfg
        indices = self.first["indices"]
        step_weights = np.ones(indices.shape, np.float32)  # as `learner.py` says
        env = cfg["env"]
        other_dim = cfg["model"]["OTHER_NN_INPUT_FEATURES_DIM"]
        make = jax.jit(
            lambda key, index: rows.make_rows(
                key, index, env, other_dim, cfg["action_dim"],
                cfg["train"]["BUFFER_CAPACITY"],
            )
        )
        params0 = jax.device_put(self.params0)
        state = plain.init_state(params0)
        loss, grad_norm, td, loads, first_moment = [], [], [], [], None
        for i in range(len(indices)):
            made = make(self.row_key, jnp.asarray(indices[i], jnp.int32))
            # The ring keeps the board as int8 and hands it back as float32.
            made["grid"] = made["grid"].astype(jnp.int8).astype(jnp.float32)
            made["weights"] = jnp.asarray(step_weights[i])
            state, total, norm, errors, counted = plain.train_step(
                state, cfg, made, self.traffic["reference_block"], quant
            )
            loss.append(total)
            grad_norm.append(norm)
            td.append(errors)
            loads.append(counted)
            if first_moment is None:
                first_moment = reference.leaf_norms(state[1])
        params = jax.device_get(state[0])
        return {
            "loss": np.asarray(jax.device_get(loss)),
            "grad_norm": np.asarray(jax.device_get(grad_norm)),
            "td": jax.device_get(td),
            "params": _trained(params),
            "mu": jax.device_get(state[1]),
            "params0": _trained(self.params0),
            "weights": step_weights,
            "first_moment": first_moment,
            "bias": plain.biases_of(params, cfg),
            "bias0": plain.biases_of(self.params0, cfg),
            "loads": np.asarray(jax.device_get(loads), np.float32),
        }

    def check(self) -> dict:
        read = compare(self.first, self.reference_group(), self.settings)
        return {name: read[name] for name in NUMBERS}


def compare(got: dict, ref: dict, settings: dict) -> dict:
    """`learner.compare_groups` on the trained leaves, and the routers'
    two readings (the module's docstring). Every bias after the group's
    steps has to be the starting bias moved once a step by the loads
    `got` itself reports."""
    read = learner.compare_groups(got, ref)
    gamma = np.float32(settings["router_bias_rate"])
    bias = ref["bias0"]
    for loads in got["loads"]:  # a step's (sparse layers, E)
        loads = loads.astype(np.float32)
        bias = bias + gamma * np.sign(loads.mean(axis=-1, keepdims=True) - loads)
    read["bias_rule_mismatch"] = float((got["bias"] != bias).sum())
    gaps = np.abs(got["loads"] - ref["loads"]).sum(axis=-1) / ref["loads"].sum(axis=-1)
    read["load_gap"] = float(gaps[0].max())  # step 1, the worst layer
    return read


def calibrate(driver, parts, with_detail=False) -> dict:
    """The readings of one seed: the program's, the control's (every
    product's operands rounded to fp8) and a second sound witness's
    (rounded to bfloat16), each against the one reference."""
    driver.setup()
    driver.release()
    ref = driver.reference_group()
    made = {
        "program": lambda: driver.first,
        "control": lambda: driver.reference_group(quant=reference.fp8),
        "bf16": lambda: driver.reference_group(quant=reference.bf16),
    }
    out = {}
    for part in parts:
        got = made[part]()
        out[part] = compare(got, ref, driver.settings)
        if with_detail:
            out[part + "_detail"] = learner.detail(got, ref)
        del got  # two float32 trees of the net: the host holds 40 GiB
    driver.first = None
    return out
