"""The learner-from-ring driver: what `TrainingLoop._run_training_steps`
does in device-replay mode, and nothing else of the loop.

One group is K x `buffer.sample(B)` on the host's SumTree, one dispatch
of `Trainer.train_steps_from` (the K fused steps gather their rows from
the HBM ring by slot number), one fetch, K x `update_priorities`.

Set-up builds the ring, fills it on the device from the seed through
the ring's own ingest program, builds the one `Trainer`, and drives it
through its first group. That group compiles or reloads the program, so
it is the warm-up, and it is the group `correct` is decided on: the
same object then goes to the window.
"""

import jax
import jax.numpy as jnp
import numpy as np

from .. import flops, reference, rows
from ..weights import make_variables
from ..spans import Spans


def _mu(opt_state):
    """Adam's first moment inside the optimizer chain's state."""
    found = [
        s
        for s in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda x: hasattr(x, "mu")
        )
        if hasattr(s, "mu")
    ]
    return found[0].mu


# Of `compare_groups`' readings, those that a control or a planted fault
# reads well above sound runs (PERF.md section 2 has every reading).
COMPARED = (
    "weight_mismatch", "grad_norm_gap", "td_gap", "td_gap_mean", "change_gap"
)


class Driver:
    unit_name = "steps"

    def __init__(self, cell: dict, configs: dict, seed: int, spans: Spans):
        self.cell = cell
        self.cfg = cell["config_file"]
        self.traffic = cell["traffic_file"]
        self.configs = configs
        self.seed = int(seed)
        self.spans = spans
        self.key = rows.seed_key(self.seed)
        self.first: dict | None = None
        self.failed = 0

    # --- set-up -----------------------------------------------------------

    def setup(self) -> None:
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

        variables = make_variables(
            self.configs, jax.random.fold_in(self.key, 1)
        )
        self.params0 = variables["params"]
        net = NeuralNetwork(model, env, variables=variables)
        self.trainer = Trainer(net, train)

        # The ring, filled on the device in blocks of seeded rows.
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
        self.row_key = jax.random.fold_in(self.key, 2)
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
        self.first = {
            "indices": np.stack([s["indices"] for s in samples]),
            "weights": np.stack([s["weights"] for s in samples]),
            "loss": np.asarray([m["total_loss"] for m, _ in outs]),
            "grad_norm": np.asarray([m["grad_norm"] for m, _ in outs]),
            "td": np.stack([td for _, td in outs]),
            "params": jax.device_get(state.params),
            "mu": jax.device_get(_mu(state.opt_state)),
        }

    # --- the timed path ---------------------------------------------------

    def _group(self):
        """One fused group, as `_run_training_steps` runs it."""
        trainer, buffer, spans = self.trainer, self.buffer, self.spans
        with spans.span("sample"):
            samples = [
                buffer.sample(
                    self.batch, current_train_step=trainer.global_step
                )
                for _ in range(self.k)
            ]
        with spans.span("dispatch"):
            handle = trainer.train_steps_from_begin(buffer, samples)
        with spans.span("fetch"):
            outs = trainer.train_steps_finish(handle)
        with spans.span("priorities"):
            for sample, (_, td) in zip(samples, outs):
                buffer.update_priorities(sample["indices"], td)
        if not all(np.isfinite(m["total_loss"]) for m, _ in outs):
            self.failed += 1
        return samples, outs

    def start_window(self) -> None:
        """Nothing of this driver's counters runs over from set-up."""

    def unit(self) -> int:
        """One whole dispatch of the window; returns the steps it ran."""
        self._group()
        return self.k

    def counters(self) -> dict:
        model = self.cfg["model"]
        return {
            "steps_per_unit": self.k,
            "step_flops": flops.train_step_flops(
                model, self.cfg["env"], self.cfg["action_dim"], self.batch
            ),
        }

    # --- after the window -------------------------------------------------

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        self.trainer = None
        self.buffer = None

    def reference_group(self, quant=None, half=False) -> dict:
        """The first group as the plain reference computes it, from the
        seed's weights and the rows the group's slot numbers name."""
        return reference_group(
            self.cfg,
            self.params0,
            self.row_key,
            self.first["indices"],
            quant=quant,
            half=half,
        )

    def check(self) -> dict:
        """The numbers `correct` compares, each against its limit."""
        read = compare_groups(self.first, self.reference_group())
        return {name: read[name] for name in COMPARED}


def reference_group(cfg, params0, row_key, indices, quant=None, half=False) -> dict:
    """K plain steps on the rows `indices` names. `quant` and `half`
    plant the control and the left-out half batch. The importance
    weights are the plain ones: every row of the filled ring has the
    priority a new row gets, so every sampling probability is 1 / N and
    every weight (N x probability)^-beta / the largest = 1."""
    weights = np.ones(np.shape(indices), np.float32)
    env = cfg["env"]
    other_dim = cfg["model"]["OTHER_NN_INPUT_FEATURES_DIM"]
    action_dim = cfg["action_dim"]

    # The key is an argument: as a constant it would make every seed
    # another program, and none would be found in the compile cache.
    @jax.jit
    def step(state, key, index, weight):
        made = rows.make_rows(
            key, index, env, other_dim, action_dim, cfg["train"]["BUFFER_CAPACITY"]
        )
        # The ring keeps the board as int8 and hands it back as float32.
        made["grid"] = made["grid"].astype(jnp.int8).astype(jnp.float32)
        made["weights"] = weight
        if half:
            made = {f: v[: v.shape[0] // 2] for f, v in made.items()}
        return reference.train_step(state, cfg, made, quant=quant)

    state = reference.init_state(params0, cfg)
    loss, grad_norm, td, first_moment, params1 = [], [], [], None, None
    for i in range(len(indices)):
        state, total, norm, errors = step(
            state,
            row_key,
            jnp.asarray(indices[i], jnp.int32),
            jnp.asarray(weights[i]),
        )
        loss.append(total)
        grad_norm.append(norm)
        td.append(errors)
        if first_moment is None:
            first_moment = reference.leaf_norms(state[1])
            params1 = jax.device_get(state[0])
    return {
        "loss": np.asarray(jax.device_get(loss)),
        "grad_norm": np.asarray(jax.device_get(grad_norm)),
        "td": jax.device_get(td),
        "params": jax.device_get(state[0]),
        "mu": jax.device_get(state[1]),
        "params0": jax.device_get(params0),
        "params1": params1,  # after step 1, for `detail`
        "weights": weights,
        # Leaf norms of Adam's moment after step 1: the first gradient
        # as the optimizer got it, times 1 - b1.
        "first_moment": first_moment,
    }


def _leaf_gaps(got, want) -> np.ndarray:
    """The gap between two trees' leaf norms, leaf by leaf, against the
    wanted leaf's norm or the median leaf's, whichever is larger."""
    got, want = reference.leaf_norms(got), reference.leaf_norms(want)
    return np.abs(got - want) / np.maximum(want, np.median(want))


def _minus(a, b):
    return jax.tree_util.tree_map(
        lambda x, y: np.asarray(x) - np.asarray(y), a, b
    )


def compare_groups(got: dict, ref: dict) -> dict:
    """`got` is the program's first group (or the control's, or a
    fault's, in its place); `ref` the reference's.

    - `weight_mismatch`: importance weights that are not the plain ones.
    - `loss_gap`: steps 1 to 3, the widest relative gap of the loss.
    - `grad_norm_gap`: step 1, the gradient's global norm before
      clipping, as the optimizer gets it.
    - `td_gap`, `td_gap_mean`: step 1, the gap of a row's TD error (the
      priority it becomes), against that error or the median row's:
      the widest row, and the mean over the rows.
    - `moment_gap`, `change_gap`: after the group's K steps, the median
      leaf's gap of Adam's first moment and of the parameters' change.
      The median and not the worst leaf: from step 2 on the two
      trajectories part by Adam's own doing (its first update is
      lr x sign(g), so rounding flips whole entries), and the worst of
      90 leaves then reads 0.1 to 0.3 on sound runs (PERF.md).

    `loss_gap` and `moment_gap` are read and not compared: on
    `moment_gap` neither the control nor a fault reads three times what
    sound runs do, and `loss_gap` read four times its usual on one
    sound seed of eighteen (PERF.md section 2).
    """
    n = min(3, len(ref["loss"]))
    td_got = np.asarray(got["td"][0])
    td_ref = np.asarray(ref["td"][0])[: len(td_got)]
    td_gaps = np.abs(td_got - td_ref) / np.maximum(
        np.abs(td_ref), np.median(np.abs(td_ref))
    )
    # A leaf whose gradient is nought to rounding in the reference (a
    # key's bias under softmax) moves under Adam by round-off alone.
    moved = ref["first_moment"] >= 1e-3 * np.median(ref["first_moment"])
    change = _leaf_gaps(
        _minus(got["params"], ref["params0"]),
        _minus(ref["params"], ref["params0"]),
    )
    return {
        # The importance weights the program sampled with, against the
        # plain ones (all 1), to float32 rounding: a count.
        "weight_mismatch": float(
            (np.abs(np.asarray(got["weights"]) - ref["weights"]) > 1e-6).sum()
        ),
        "loss_gap": float(
            np.max(np.abs(got["loss"][:n] - ref["loss"][:n]) / np.abs(ref["loss"][:n]))
        ),
        "grad_norm_gap": float(
            abs(got["grad_norm"][0] - ref["grad_norm"][0]) / ref["grad_norm"][0]
        ),
        "td_gap": float(np.max(td_gaps)),
        "td_gap_mean": float(np.mean(td_gaps)),
        "moment_gap": float(np.median(_leaf_gaps(got["mu"], ref["mu"]))),
        "change_gap": float(np.median(change[moved])),
    }


def detail(got: dict, ref: dict) -> dict:
    """For the look behind a reading: the gaps step by step, and the
    leaves with the widest gaps, by name."""
    names = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(ref["mu"])[0]
    ]
    sizes = [np.size(x) for x in jax.tree_util.tree_leaves(ref["mu"])]
    # as in `compare_groups`: leaves the reference's gradient leaves alone
    moved = ref["first_moment"] >= 1e-3 * np.median(ref["first_moment"])

    def leaves(a, b):
        a, b = reference.leaf_norms(a), reference.leaf_norms(b)
        gaps = np.where(moved, np.abs(a - b) / np.maximum(b, np.median(b)), 0.0)
        worst = np.argsort(gaps)[::-1][:4]
        return {
            "median_leaf_norm": float(np.median(b)),
            "worst_gap": float(gaps.max()),
            "worst": [
                [names[i], float(gaps[i]), float(a[i]), float(b[i])] for i in worst
            ],
        }

    n = min(len(got["loss"]), len(ref["loss"]))
    flipped = {}
    if got.get("params1") is not None:
        # Adam's first update is lr x sign(gradient) in every entry: the
        # share of entries that the two sides moved in opposite
        # directions at step 1, leaf by leaf.
        ours = jax.tree_util.tree_leaves(_minus(got["params1"], ref["params0"]))
        theirs = jax.tree_util.tree_leaves(_minus(ref["params1"], ref["params0"]))
        share = np.asarray(
            [float((np.sign(a) != np.sign(b)).mean()) for a, b in zip(ours, theirs)]
        )
        change = leaves(
            _minus(got["params"], ref["params0"]),
            _minus(ref["params"], ref["params0"]),
        )
        flipped = {
            "flipped_share_step1_median_leaf": float(np.median(share)),
            "flipped_share_step1_worst_change_leaves": [
                [name, float(share[names.index(name)]), int(sizes[names.index(name)])]
                for name, *_ in change["worst"]
            ],
        }
    return {
        **flipped,
        "loss_gap_by_step": (
            np.abs(got["loss"][:n] - ref["loss"][:n]) / np.abs(ref["loss"][:n])
        ).tolist(),
        "grad_norm_gap_by_step": (
            np.abs(got["grad_norm"][:n] - ref["grad_norm"][:n]) / ref["grad_norm"][:n]
        ).tolist(),
        "moment": leaves(got["mu"], ref["mu"]),
        "change": leaves(
            _minus(got["params"], ref["params0"]),
            _minus(ref["params"], ref["params0"]),
        ),
    }


def calibrate(driver, parts, with_detail=False) -> dict:
    """The readings of one seed: the program's, the control's and the
    planted fault's, each against the one reference."""
    driver.setup()
    driver.release()
    ref = driver.reference_group()
    made = {
        "program": lambda: driver.first,
        "control": lambda: driver.reference_group(quant=reference.fp8),
        "half": lambda: driver.reference_group(half=True),
        # A second sound witness: the plain reference with its matmul
        # operands rounded to the configuration's bfloat16.
        "bf16": lambda: driver.reference_group(quant=reference.bf16),
    }
    out = {}
    for part in parts:
        got = made[part]()
        out[part] = compare_groups(got, ref)
        if with_detail:
            out[part + "_detail"] = detail(got, ref)
    return out
