"""Learner: optimizer factories, C51 target projection, pjit train step.

Capability parity with the reference `Trainer`
(`alphatriangle/rl/core/trainer.py:48-310`): Adam/AdamW/SGD + Step/
Cosine LR schedules, dense policy targets, C51 two-hot projection of
scalar n-step returns, IS-weighted policy CE + value CE + entropy bonus,
global-norm gradient clipping, per-sample TD errors for PER.

TPU-native redesign:
- The train step is one **pure jitted function** over a named device
  mesh: model/optimizer state replicated, the batch sharded on the `dp`
  axis. Gradient all-reduce is not written anywhere — XLA inserts the
  ICI collectives because the loss reduces over a sharded axis (GSPMD).
  The reference's single-device `backward()` (`trainer.py:274-286`)
  becomes multi-chip for free.
- Optimizer/schedule are optax transforms; the optax schedule lives
  only inside the compiled programs. The `learning_rate` label of a
  finished step is recomputed on the host from the step number by the
  schedule's numpy twin (`make_host_lr_schedule`): no JAX call, not
  read from mutable optimizer state.
- The C51 projection of a *scalar* return is a two-hot scatter
  (`trainer.py:159-202` does the same dance with torch index math).
- A net whose trunk is a decoder stack (`ModelConfig.TRUNK`) is trained
  through the same programs with two things more. Where the trunk
  names `learner_block_boards` a step takes its batch a block of boards
  at a time: forward and backward of one block (each trunk layer
  recomputed under `ModelConfig.REMAT`), the gradients added in float32,
  then one clip, one optimizer update. And a router's selection bias is
  no parameter of the optimizer's: no gradient reaches it, it is left
  out of the clipped norm, the moments and the weight decay, and each
  step moves it by the sign of its expert's load error over the step's
  whole batch (`nn/trunk.py` `moved_router_biases`). The loads ride the
  group's one fetch (`Trainer.last_counters`). The blocked step is
  written for one device: it slices the batch it is handed.
"""

import logging
import time
from collections.abc import Callable
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compile_cache import config_digest, get_compile_cache
from ..config.mesh_config import MeshConfig
from ..config.train_config import TrainConfig
from ..nn.network import NeuralNetwork
from ..nn.trunk import block_size, counters_of, moved_router_biases, sparse_layers
from ..telemetry.device_stats import emit_beacon
from ..telemetry.flight import flight_span
from ..telemetry.tracer import default_tracer
from ..parallel.sharding import (
    batch_sharding,
    local_rows,
    replicated,
    shard_batch,
    state_shardings,
)
from ..utils.types import DenseBatch
from .device_buffer import read_rows, ring_read

logger = logging.getLogger(__name__)


# --- optimizer / schedule factories --------------------------------------


def _cosine_t_max(cfg: TrainConfig) -> int:
    return cfg.LR_SCHEDULER_T_MAX or (cfg.MAX_TRAINING_STEPS or 100_000)


def make_lr_schedule(cfg: TrainConfig) -> optax.Schedule:
    """LR schedule per `TrainConfig` (reference `trainer.py:66-102`)."""
    if cfg.LR_SCHEDULER_TYPE == "CosineAnnealingLR":
        return optax.cosine_decay_schedule(
            init_value=cfg.LEARNING_RATE,
            decay_steps=_cosine_t_max(cfg),
            alpha=cfg.LR_SCHEDULER_ETA_MIN / cfg.LEARNING_RATE,
        )
    if cfg.LR_SCHEDULER_TYPE == "StepLR":
        return optax.exponential_decay(
            init_value=cfg.LEARNING_RATE,
            transition_steps=cfg.LR_SCHEDULER_STEP_SIZE,
            decay_rate=cfg.LR_SCHEDULER_GAMMA,
            staircase=True,
        )
    return optax.constant_schedule(cfg.LEARNING_RATE)


def make_host_lr_schedule(cfg: TrainConfig) -> Callable[[Any], np.ndarray]:
    """`make_lr_schedule`'s host twin: step numbers -> float32 LRs.

    Plain numpy, vectorised over an array of steps (a scalar gives a
    0-d result), with optax's own float32 operation order so the two
    agree to rounding. It labels the metrics stream; the optimizer
    inside the compiled programs evaluates `make_lr_schedule`'s copy.
    """
    f32 = np.float32
    init = f32(cfg.LEARNING_RATE)
    if cfg.LR_SCHEDULER_TYPE == "CosineAnnealingLR":
        t_max = f32(_cosine_t_max(cfg))
        alpha = cfg.LR_SCHEDULER_ETA_MIN / cfg.LEARNING_RATE
        keep, floor = f32(1 - alpha), f32(alpha)

        def cosine(steps):
            count = np.minimum(np.asarray(steps, dtype=f32), t_max)
            decay = f32(0.5) * (f32(1) + np.cos(f32(np.pi) * count / t_max))
            return init * (keep * decay + floor)

        return cosine
    if cfg.LR_SCHEDULER_TYPE == "StepLR":
        size, gamma = cfg.LR_SCHEDULER_STEP_SIZE, f32(cfg.LR_SCHEDULER_GAMMA)

        def staircase(steps):
            stairs = (np.asarray(steps, dtype=np.int64) // size).astype(f32)
            return init * np.power(gamma, stairs)

        return staircase
    return lambda steps: np.full(np.shape(steps), init)


def make_optimizer(cfg: TrainConfig) -> optax.GradientTransformation:
    """Clip + optimizer + schedule chain (reference `trainer.py:48-64`)."""
    schedule = make_lr_schedule(cfg)
    if cfg.OPTIMIZER_TYPE == "AdamW":
        opt = optax.adamw(schedule, weight_decay=cfg.WEIGHT_DECAY)
    elif cfg.OPTIMIZER_TYPE == "Adam":
        # torch-style coupled L2: decay folds into the gradient before
        # the moment estimates (vs AdamW's decoupled decay).
        opt = optax.chain(
            optax.add_decayed_weights(cfg.WEIGHT_DECAY), optax.adam(schedule)
        )
    elif cfg.OPTIMIZER_TYPE == "SGD":
        opt = optax.chain(
            optax.add_decayed_weights(cfg.WEIGHT_DECAY), optax.sgd(schedule)
        )
    else:  # pragma: no cover - pydantic Literal prevents this
        raise ValueError(f"Unknown optimizer {cfg.OPTIMIZER_TYPE}")
    if cfg.GRADIENT_CLIP_VALUE is not None:
        return optax.chain(
            optax.clip_by_global_norm(cfg.GRADIENT_CLIP_VALUE), opt
        )
    return opt


def _is_router_bias(path) -> bool:
    return str(getattr(path[-1], "key", "")).endswith("router_bias")


def without_router_biases(
    optimizer: optax.GradientTransformation, params
) -> optax.GradientTransformation:
    """`optimizer` over every leaf of `params` but the routers'
    selection biases: those get no moment, no decay and no part in the
    clipped norm, and their update is whatever gradient comes (nought:
    a bias moves the choice alone). The optimizer itself where the
    tree has no such leaf."""
    trained = jax.tree_util.tree_map_with_path(
        lambda path, _: not _is_router_bias(path), params
    )
    if all(jax.tree_util.tree_leaves(trained)):
        return optimizer
    return optax.masked(optimizer, trained)


def refuse_untrainable(params, devices: int = 1) -> None:
    """A net whose training state cannot lie on the `devices` that share
    it is refused by its bytes, before anything is copied: `params` may
    be shapes alone. The state is the parameters, their gradients and
    two Adam moments, four times the parameters' bytes."""
    from ..nn.model import count_parameters
    from ..telemetry.memory import resolve_bytes_limit

    count = count_parameters(params)
    state_bytes = 4 * sum(
        int(np.prod(p.shape)) * jnp.dtype(p.dtype).itemsize
        for p in jax.tree_util.tree_leaves(params)
    )
    limit, _ = resolve_bytes_limit(None)
    if limit is not None and state_bytes > limit * devices:
        raise ValueError(
            f"Trainer: {count:,} parameters need {state_bytes:,} B of "
            "training state (parameters, gradients and two Adam moments) "
            f"and {devices} device(s) of {int(limit):,} B hold it: "
            "this net can be served (INFERENCE_PRECISION), not trained here."
        )


# --- C51 projection -------------------------------------------------------


def project_to_support(
    returns: jax.Array, num_atoms: int, v_min: float, v_max: float
) -> jax.Array:
    """(B,) scalar returns -> (B, num_atoms) two-hot target distribution.

    Categorical projection of a delta distribution onto the fixed atom
    support (reference `trainer.py:159-202`).
    """
    delta_z = (v_max - v_min) / (num_atoms - 1)
    b = (jnp.clip(returns, v_min, v_max) - v_min) / delta_z  # (B,) in [0, A-1]
    lower = jnp.floor(b).astype(jnp.int32)
    upper = jnp.ceil(b).astype(jnp.int32)
    exact = lower == upper
    w_lower = jnp.where(exact, 1.0, upper.astype(jnp.float32) - b)
    w_upper = jnp.where(exact, 0.0, b - lower.astype(jnp.float32))
    onehot_l = jax.nn.one_hot(lower, num_atoms, dtype=jnp.float32)
    onehot_u = jax.nn.one_hot(upper, num_atoms, dtype=jnp.float32)
    return onehot_l * w_lower[:, None] + onehot_u * w_upper[:, None]


# --- train state ----------------------------------------------------------


@struct.dataclass
class TrainState:
    """Replicated learner state (a pure pytree; checkpoints directly)."""

    params: Any
    batch_stats: Any  # {} unless NORM_TYPE == "batch"
    opt_state: Any
    step: jax.Array  # () int32
    rng: jax.Array  # dropout PRNG key


class Trainer:
    """Owns the jitted sharded train step bound to one network + mesh."""

    def __init__(
        self,
        nn: NeuralNetwork,
        train_config: TrainConfig,
        mesh: Mesh | None = None,
        mdl_axis: str | None = None,
    ):
        self.nn = nn
        self.config = train_config
        self.mesh = mesh or MeshConfig.single_device_mesh()
        # Data-parallel axis: the conventional name "dp" wins if present
        # (meshes may order axes arbitrarily); otherwise the first axis
        # (MeshConfig.DP_AXIS is configurable and always comes first).
        self.dp_axis = (
            "dp" if "dp" in self.mesh.axis_names else self.mesh.axis_names[0]
        )
        self.dp_size = self.mesh.shape[self.dp_axis]
        # Model (tensor-parallel) axis: transformer params shard over
        # it when it is wider than 1 (parallel/sharding.py Megatron
        # layout); 1-wide or absent means fully-replicated state (the
        # default — the flagship net is ~3M params). Only an axis
        # DISTINCT from dp qualifies: guessing (e.g. taking the second
        # axis of a custom-named mesh) could silently tensor-shard
        # params over a data or sequence axis. Callers with custom
        # axis names pass `mdl_axis` explicitly (setup.py forwards
        # MeshConfig.MDL_AXIS).
        if mdl_axis is None:
            mdl_axis = "mdl" if "mdl" in self.mesh.axis_names else None
        if (
            mdl_axis is not None
            and mdl_axis != self.dp_axis
            and mdl_axis in self.mesh.axis_names
        ):
            self.mdl_axis: str | None = mdl_axis
            self.tp_size = self.mesh.shape[mdl_axis]
        else:
            self.mdl_axis = None
            self.tp_size = 1
        self.model = nn.model
        # Host<->device transfer accounting (telemetry/perf.py reads
        # deltas per tick): h2d = batch staging uploads; d2h = blocking
        # result fetches (includes any wait for the step to finish —
        # the host-visible cost of the round trip, which is what the
        # utilization record is after). Single-writer (main/consumer
        # thread), so bare float accumulation is safe.
        self.transfer_h2d_seconds = 0.0
        self.transfer_d2h_seconds = 0.0
        # Learner program dispatches (telemetry: the loop's dispatches-
        # per-iteration gauge; one per step/group dispatch).
        self.dispatch_count = 0
        # Dispatch flight recorder (telemetry/flight.py), attached by
        # training/setup.py; None = no intent/seal records written.
        self.flight = None
        mc = nn.model_config
        self.num_atoms = mc.NUM_VALUE_ATOMS
        self.v_min, self.v_max = mc.VALUE_MIN, mc.VALUE_MAX
        self.schedule = make_lr_schedule(train_config)
        self.host_schedule = make_host_lr_schedule(train_config)
        # What of a decoder stack the step reads: whether routers load
        # experts (their counters come back, their biases are moved),
        # and how many boards a step takes at a time.
        trunk = mc.TRUNK
        self._routed = trunk is not None and bool(sparse_layers(trunk))
        self._block_boards = trunk.learner_block_boards if trunk else None
        self.last_counters: dict | None = None
        self.optimizer = without_router_biases(
            make_optimizer(train_config), nn.variables["params"]
        )

        refuse_untrainable(nn.variables["params"], self.tp_size)
        # Deep-copy the wrapper's variables: the jitted step donates its
        # input state, and a donated buffer aliased by `nn.variables`
        # would leave the eval wrapper holding deleted arrays.
        variables = jax.tree_util.tree_map(jnp.array, nn.variables)
        self.state = TrainState(
            params=variables["params"],
            batch_stats=variables.get("batch_stats", {}),
            opt_state=self.optimizer.init(variables["params"]),
            step=jnp.int32(0),
            rng=jax.random.PRNGKey(train_config.RANDOM_SEED),
        )

        rep = replicated(self.mesh)
        state_shard = state_shardings(
            self.mesh, self.state, mdl_axis=self.mdl_axis
        )
        self._state_shard = state_shard
        bshard = batch_sharding(self.mesh, self.dp_axis)
        batch_shards: dict[str, Any] = {
            "grid": bshard,
            "other_features": bshard,
            "policy_target": bshard,
            "value_target": bshard,
            "weights": bshard,
            "policy_weight": bshard,
        }
        # Learner programs ride the AOT compile cache (compile_cache.py):
        # a warm cache (cli warm / a prior same-shape run) deserializes
        # the serialized executable instead of recompiling. The digest
        # keys the program shapers invisible in input avals: optimizer/
        # schedule/loss config, net architecture, board geometry.
        cache = get_compile_cache()
        from ..telemetry.device_stats import beacon_signature, beacons_armed

        self._cache_extra = (
            config_digest(train_config, nn.model_config, nn.env_config)
            + f"|att{int(getattr(nn.model, 'attention_fn', None) is not None)}"
            # Beacon-armed learner programs embed host callbacks in the
            # scan body — distinct executables, never serialized (the
            # wrap sites pass serialize=False under arming).
            + beacon_signature()
        )
        # cpu_aot=False on every learner program: XLA:CPU DESERIALIZED
        # executables of this program family run without error but
        # return the donated train state UNCHANGED — params silently
        # stop updating. Reproduced deterministically (fresh compile
        # updates params; the same process or a later one reloading the
        # serialized artifact does not), while the rollout-chunk
        # programs reload correctly. On CPU these programs therefore
        # always compile fresh; accelerator backends keep the full AOT
        # cache behavior.
        self._step_fn = cache.wrap(
            "learner_step",
            jax.jit(
                self._train_step_impl,
                in_shardings=(state_shard, batch_shards),
                out_shardings=(state_shard, rep, bshard),
                donate_argnums=(0,),
            ),
            extra=self._cache_extra,
            cpu_aot=False,
            serialize=not beacons_armed(),
        )
        # Fused multi-step: batches stacked on a new leading K axis, dp
        # sharding on axis 1; one compiled program per distinct K.
        stacked_shard = NamedSharding(
            self.mesh, P(None, self.dp_axis)
        )
        stacked_shards = {k: stacked_shard for k in batch_shards}
        self._multi_step_fn = cache.wrap(
            "learner_fused_steps",
            jax.jit(
                self._train_steps_impl,
                in_shardings=(state_shard, stacked_shards),
                out_shardings=(state_shard, rep, stacked_shard),
                donate_argnums=(0,),
            ),
            extra=self._cache_extra,
            cpu_aot=False,
            serialize=not beacons_armed(),
        )
        self._stacked_shard = stacked_shard
        # Device-buffer path (rl/device_buffer.py): batches are gathered
        # ON DEVICE from the replay ring by sampled indices — the fused
        # group's host->device traffic shrinks from K full batches to
        # K*B int32 indices. One compiled program per distinct K (the
        # cache wrapper keys executables per input signature, so the
        # distinct-K programs each get their own AOT cache entry).
        def learner_fused_from_ring(state, storage, idx, weights):
            # Named as the cache names it: the HLO module's name is what
            # a device trace calls the program.
            return self._train_steps_from_impl(state, storage, idx, weights)

        self._from_fn = cache.wrap(
            "learner_fused_from_ring",
            jax.jit(learner_fused_from_ring, donate_argnums=(0,)),
            extra=self._cache_extra,
            cpu_aot=False,
            serialize=not beacons_armed(),
        )
        # dp-sharded ring variant (rl/sharded_device_buffer.py): built
        # lazily on first use, cached per shard geometry — the program
        # closes over (stride, dp_axis), and a geometry change with a
        # stale program would gather silently-wrong rows (JAX clamps
        # out-of-range gather indices rather than erroring).
        self._from_sharded_fns: dict[tuple, Any] = {}
        # Keep state resident on the mesh (replicated, or TP-sharded
        # over the mdl axis when it is wider than 1).
        self.state = jax.device_put(self.state, state_shard)
        # Host mirror of state.step: global_step / LR lookups must not
        # block on a device fetch (each fetch is a full round trip).
        self._host_step = 0

    # --- pure core --------------------------------------------------------

    @jax.named_scope("learner/forward_loss")
    def _loss_fn(self, params, batch_stats, rng, batch: DenseBatch):
        cfg = self.config
        variables = {"params": params}
        mutable: list[str] | bool = False
        if batch_stats:
            variables["batch_stats"] = batch_stats
            mutable = ["batch_stats"]
        if self._routed:
            mutable = [*(mutable or []), "counters"]
        out = self.model.apply(
            variables,
            batch["grid"],
            batch["other_features"],
            train=True,
            rngs={"dropout": rng},
            mutable=mutable,
        )
        if mutable:
            (policy_logits, value_logits), updates = out
            new_batch_stats = updates.get("batch_stats", {})
        else:
            policy_logits, value_logits = out
            new_batch_stats = batch_stats

        log_policy = jax.nn.log_softmax(policy_logits, axis=-1)
        policy_ce = -(batch["policy_target"] * log_policy).sum(axis=-1)  # (B,)
        # Playout-cap randomization: rows from fast searches carry
        # policy_weight 0 — their visit counts are too noisy to train
        # the policy on; they still train the value head below.
        pw = batch["policy_weight"]
        policy_ce = pw * policy_ce

        # The value cross-entropy doubles as the per-row TD error PER
        # gets back, hence the phase's name.
        with jax.named_scope("learner/td"):
            target_dist = project_to_support(
                batch["value_target"], self.num_atoms, self.v_min, self.v_max
            )
            log_value = jax.nn.log_softmax(value_logits, axis=-1)
            value_ce = -(target_dist * log_value).sum(axis=-1)  # (B,)

        probs = jnp.exp(log_policy)
        # Entropy regularizes the policy, so it follows the policy mask.
        # The LOSS term averages over all B rows — the same denominator
        # as the masked policy CE — so the entropy-to-policy-gradient
        # ratio is invariant to the PCR full-search fraction. The
        # REPORTED entropy averages over policy-trainable rows only
        # (interpretable as nats/decision regardless of masking).
        entropy_rows = -(probs * log_policy).sum(axis=-1)  # (B,)
        entropy_term = (pw * entropy_rows).mean()
        entropy_sum, policy_rows = (pw * entropy_rows).sum(), pw.sum()
        entropy_metric = entropy_sum / jnp.maximum(policy_rows, 1.0)

        w = batch["weights"]
        per_sample = (
            cfg.POLICY_LOSS_WEIGHT * policy_ce
            + cfg.VALUE_LOSS_WEIGHT * value_ce
        )
        # Entropy regularization uses the UNWEIGHTED (by IS weight) mean
        # — the reference is explicit about this ("Use mean entropy, not
        # weighted", `trainer.py:253-256`); IS weights must not modulate
        # the regularizer's strength per sample.
        total = (w * per_sample).mean() - cfg.ENTROPY_BONUS_WEIGHT * entropy_term
        aux = {
            "total_loss": total,
            "policy_loss": (w * policy_ce).mean(),
            "value_loss": (w * value_ce).mean(),
            "entropy": entropy_metric,
            "td_errors": value_ce,
            "batch_stats": new_batch_stats,
        }
        if self._routed:
            counted = counters_of(updates)
            aux["expert_loads"] = counted["expert_loads"]
            aux["expert_tokens"] = counted["expert_tokens"]
        if self._block_boards is not None:
            # What the blocks of one step add up to the step's entropy.
            aux["entropy_sum"], aux["policy_rows"] = entropy_sum, policy_rows
        return total, aux

    def _blocked_grads(self, state: TrainState, step_rng, batch, size: int):
        """`jax.grad` of `_loss_fn` over `batch` taken `size` rows at a
        time, in equal blocks: one block's forward and backward, then
        the next, the gradients summed in float32 and divided by the
        number of blocks (the loss is a mean over rows, so the mean of
        the blocks' gradients is the batch's). `aux` as `_loss_fn`
        gives it for the whole batch: the losses the blocks' means, the
        TD errors row by row in order, the experts' counters summed."""
        means = ("total_loss", "policy_loss", "value_loss")
        sums = ("entropy_sum", "policy_rows", "expert_loads", "expert_tokens")
        blocks = batch["value_target"].shape[0] // size

        def block_at(j):
            return jax.tree_util.tree_map(
                lambda x: jax.lax.dynamic_slice_in_dim(x, j * size, size), batch
            )

        def one(carry, j):
            grads_sum, totals, batch_stats = carry
            with jax.named_scope("learner/block"):
                grads, aux = jax.grad(
                    lambda p: self._loss_fn(
                        p, batch_stats, jax.random.fold_in(step_rng, j), block_at(j)
                    ),
                    has_aux=True,
                )(state.params)
            with jax.named_scope("learner/accumulate"):
                grads_sum = jax.tree_util.tree_map(
                    lambda total, g: total + g.astype(jnp.float32), grads_sum, grads
                )
                totals = {
                    name: totals[name] + aux[name] for name in totals
                }
            return (grads_sum, totals, aux["batch_stats"]), aux["td_errors"]

        shapes = jax.eval_shape(
            lambda: self._loss_fn(
                state.params, state.batch_stats, step_rng, block_at(0)
            )[1]
        )
        start = (
            jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            ),
            {
                name: jnp.zeros(shapes[name].shape, shapes[name].dtype)
                for name in means + sums
                if name in shapes
            },
            state.batch_stats,
        )
        (grads_sum, totals, batch_stats), td = jax.lax.scan(
            one,
            start,
            jnp.arange(blocks),
            unroll=True if jax.default_backend() == "cpu" else 1,
        )
        with jax.named_scope("learner/accumulate"):
            grads = jax.tree_util.tree_map(
                lambda total, p: (total / blocks).astype(p.dtype),
                grads_sum,
                state.params,
            )
        aux = {
            **{name: totals[name] / blocks for name in means},
            **{name: totals[name] for name in sums if name in totals},
            "td_errors": td.reshape(-1),
            "batch_stats": batch_stats,
        }
        aux["entropy"] = aux["entropy_sum"] / jnp.maximum(aux["policy_rows"], 1.0)
        return grads, aux

    def _train_step_impl(self, state: TrainState, batch: DenseBatch):
        rng, step_rng = jax.random.split(state.rng)
        rows = batch["value_target"].shape[0]
        size = block_size(rows, self._block_boards)
        if size < rows:
            grads, aux = self._blocked_grads(state, step_rng, batch, size)
        else:
            grads, aux = jax.grad(
                lambda p: self._loss_fn(p, state.batch_stats, step_rng, batch),
                has_aux=True,
            )(state.params)
        with jax.named_scope("learner/optimizer"):
            updates, opt_state = self.optimizer.update(
                grads, state.opt_state, state.params
            )
            params = optax.apply_updates(state.params, updates)
            grad_norm = optax.global_norm(grads)
            update_norm = optax.global_norm(updates)
        if self._routed:
            with jax.named_scope("learner/router_bias"):
                params = {
                    **params,
                    "DecoderTrunk_0": moved_router_biases(
                        params["DecoderTrunk_0"],
                        aux["expert_loads"],
                        self.nn.model_config.TRUNK,
                    ),
                }
        new_state = TrainState(
            params=params,
            batch_stats=aux["batch_stats"],
            opt_state=opt_state,
            step=state.step + 1,
            rng=rng,
        )
        metrics = {
            "total_loss": aux["total_loss"],
            "policy_loss": aux["policy_loss"],
            "value_loss": aux["value_loss"],
            "entropy": aux["entropy"],
            "grad_norm": grad_norm,
            # Post-transform step size: grad_norm tells you what the
            # loss surface did, update_norm what the optimizer actually
            # applied — the pair separates "gradient explosion" from
            # "adaptive-moment blowup" per fused step.
            "update_norm": update_norm,
        }
        if self._routed:
            # Counters, not metrics: `group_results` takes them off the
            # group's fetch into `last_counters`.
            metrics["expert_loads"] = aux["expert_loads"]
            metrics["expert_tokens"] = aux["expert_tokens"]
        return new_state, metrics, aux["td_errors"]

    def _train_steps_impl(self, state: TrainState, stacked: DenseBatch):
        """K fused SGD steps: a lax.scan over the leading batch axis.

        Produces bit-identical results to K sequential `_train_step_impl`
        calls on the same batches (same state threading, same RNG split
        sequence) — only the host round trips collapse to one.

        This body is also the learner phase of the megastep program
        families (rl/megastep.py). Under a dp-sharded stacked batch
        (axis 1) with replicated params, XLA inserts the gradient
        all-reduce over dp from the shardings — the "psum axis" of the
        sharded megastep, with nothing spelled by hand (module
        docstring) — so the updated params stay bit-identical on every
        shard.

        The scan is fully unrolled on the CPU backend: XLA-CPU runs ops
        inside a While loop single-threaded, which makes a rolled scan
        ~15x slower per step than the identical unrolled program
        (measured; TPU has no such penalty, and rolled keeps compile
        time flat in K there).
        """

        def body(st, batch):
            emit_beacon("learner_step", st.step)
            new_st, metrics, td = self._train_step_impl(st, batch)
            return new_st, (metrics, td)

        state, (metrics_k, td_k) = jax.lax.scan(
            body,
            state,
            stacked,
            unroll=True if jax.default_backend() == "cpu" else 1,
        )
        return state, metrics_k, td_k

    @staticmethod
    @jax.named_scope("learner/gather")
    def _stacked_rows_batch(rows, weights) -> DenseBatch:
        """(K, B, ...) ring rows -> the stacked DenseBatch the fused
        steps consume. The grid int8->float32 cast reproduces the host
        ring's storage round trip exactly. Shared by every gathered-
        from-ring program: `_train_steps_from_impl`, the sharded-ring
        gather below, and the megastep program families that embed the
        fused steps (rl/megastep.py)."""
        return {
            "grid": rows["grid"].astype(jnp.float32),
            "other_features": rows["other_features"],
            "policy_target": rows["policy_target"],
            "value_target": rows["value_target"],
            "policy_weight": rows["policy_weight"],
            "weights": weights,
        }

    def _get_from_sharded_fn(self, buffer):
        """Jitted fused-steps program for the dp-SHARDED replay ring:
        each device gathers its B/dp batch rows from its LOCAL ring
        shard (shard_map, no collectives), then runs the dp-sharded
        fused train step. Index upload stays K*B int32 — the sharded
        ring keeps the index-only-upload property per device."""
        key = (buffer.stride, buffer.dp_axis)
        if key not in self._from_sharded_fns:
            stride = buffer.stride
            dp_axis = buffer.dp_axis

            @jax.named_scope("learner/gather")
            def gather_local(storage_local, idx_local):
                base = jax.lax.axis_index(dp_axis) * stride
                local = idx_local - base  # global encoding -> local slot
                return read_rows(storage_local, local)

            gather = jax.shard_map(
                gather_local,
                mesh=self.mesh,
                in_specs=(P(dp_axis), P(None, dp_axis)),
                out_specs=P(None, dp_axis),
                check_vma=False,
            )

            def impl(state, storage, idx, weights):
                g = gather(storage, idx)
                stacked = self._stacked_rows_batch(
                    g,
                    jax.lax.with_sharding_constraint(
                        weights, self._stacked_shard
                    ),
                )
                return self._train_steps_impl(state, stacked)

            from ..telemetry.device_stats import beacons_armed

            self._from_sharded_fns[key] = get_compile_cache().wrap(
                f"learner_fused_from_sharded_ring/s{stride}_{dp_axis}",
                jax.jit(impl, donate_argnums=(0,)),
                extra=self._cache_extra,
                cpu_aot=False,
                serialize=not beacons_armed(),
            )
        return self._from_sharded_fns[key]

    def _train_steps_from_impl(self, state: TrainState, storage, idx, weights):
        """K fused steps whose batches are gathered from the device
        replay ring: `idx` is (K, B) int32 slot indices, `weights` the
        matching (K, B) IS weights. Bit-identical to `_train_steps_impl`
        on the same rows."""
        with jax.named_scope("learner/gather"):
            rows = read_rows(storage, idx)
        return self._train_steps_impl(
            state, self._stacked_rows_batch(rows, weights)
        )

    # --- host API ---------------------------------------------------------

    def _take_counters(self, host_metrics: dict, rows: int) -> dict:
        """`host_metrics` without the routed trunk's counters, which go
        to `last_counters`: of the group just fetched, per step, the
        assignments the routers made to each expert (`expert_loads`:
        steps x sparse layers x `num_experts`) and those the held
        experts computed (`expert_tokens`: steps x sparse layers x
        held), and over the group all the assignments routed anywhere
        (`routed`) and the tokens x layers the trunk took
        (`trunk_tokens`). A net without routers has none."""
        if not self._routed:
            return host_metrics
        host_metrics = dict(host_metrics)
        loads = np.asarray(host_metrics.pop("expert_loads"))
        tokens = np.asarray(host_metrics.pop("expert_tokens"))
        if loads.ndim == 2:  # one step, not stacked
            loads, tokens = loads[None], tokens[None]
        trunk = self.nn.model_config.TRUNK
        # Every router chooses `num_experts_per_tok` for each token.
        seen = int(loads[0, 0].sum()) // trunk.num_experts_per_tok // rows
        self.last_counters = {
            "expert_loads": loads,
            "expert_tokens": tokens,
            "routed": int(loads.sum()),
            "trunk_tokens": len(loads) * rows * seen * len(trunk.layer_types),
        }
        return host_metrics

    @staticmethod
    def _with_policy_weight(batch: dict, n: int) -> dict:
        """Default the PCR policy-loss mask to ones when absent, so
        callers that predate playout-cap randomization stay valid."""
        if "policy_weight" not in batch:
            batch["policy_weight"] = np.ones(n, dtype=np.float32)
        return batch

    def _check_local_batch(self, n: int) -> None:
        # Multi-process: `batch` is this host's share; it must tile this
        # host's slice of the dp axis (shard_batch assembles the global
        # array in process order).
        local_dp = max(1, self.dp_size // jax.process_count())
        if n % local_dp != 0:
            raise ValueError(
                f"Local batch size {n} not divisible by the local dp "
                f"extent {local_dp} (global dp={self.dp_size})."
            )

    def train_step(
        self, batch: DenseBatch
    ) -> tuple[dict[str, float], np.ndarray] | None:
        """One SGD step. Returns (metrics, per-sample TD errors) or None
        on an empty batch (reference `trainer.py:204-310` contract)."""
        # Static shape read — np.asarray here would fetch the whole
        # array from the device just to look at its metadata.
        n = int(batch["value_target"].shape[0])
        if n == 0:
            return None
        self._check_local_batch(n)
        batch = self._with_policy_weight(dict(batch), n)
        t0 = time.perf_counter()
        device_batch = shard_batch(self.mesh, batch, self.dp_axis)
        self.transfer_h2d_seconds += time.perf_counter() - t0
        with flight_span(
            self.flight, "learner", "learner_step", avals=f"B{n}"
        ):
            self.state, metrics, td = self._step_fn(self.state, device_batch)
            self.dispatch_count += 1
            # ONE blocking transfer for everything this step produced
            # (fetching each metric separately costs a round trip apiece).
            t0 = time.perf_counter()
            host_metrics, td_host = jax.device_get(  # graftlint: allow(host-sync-in-hot-path) the one blocking fetch per step
                (metrics, td if jax.process_count() == 1 else None)
            )
            self.transfer_d2h_seconds += time.perf_counter() - t0
        if td_host is None:
            td_host = local_rows(td)
        self._host_step += 1
        host_metrics = self._take_counters(host_metrics, n)
        host_metrics = {k: float(v) for k, v in host_metrics.items()}
        host_metrics["learning_rate"] = self.get_current_lr()
        # PER bookkeeping is host-local: return only this host's rows.
        return host_metrics, np.asarray(td_host)

    def train_steps(
        self, batches: "list[DenseBatch]"
    ) -> list[tuple[dict[str, float], np.ndarray]]:
        """K SGD steps in ONE device dispatch (`FUSED_LEARNER_STEPS`).

        Equivalent to K sequential `train_step` calls on the same
        batches, but with a single host→device transfer and a single
        device→host fetch for the whole group. Returns the per-step
        (metrics, local TD errors) list, in execution order.
        """
        handle = self.train_steps_begin(batches)
        if handle is None:
            return []
        return self.train_steps_finish(handle)

    def train_steps_begin(
        self, batches: "list[DenseBatch]"
    ) -> dict | None:
        """Stage + dispatch a fused group WITHOUT fetching results.

        The dispatch is asynchronous: this returns as soon as the
        host→device transfer is enqueued, so a caller can overlap the
        group's device execution with host work (PER sampling, harvest
        folding) and with *staging the next group* — the double-buffered
        pipeline the overlapped training loop runs. Fetch the results
        later with `train_steps_finish`; `self.state` is already the
        group-end state (as a device future), so `sync_to_network` and
        checkpointing may run before the fetch.

        Returns an opaque handle, or None when `batches` is empty or
        the batch is degenerate (same skip contract as `train_step`).
        """
        if not batches:
            return None
        n = int(batches[0]["value_target"].shape[0])
        if n == 0:  # same skip contract as train_step
            return None
        self._check_local_batch(n)
        batches = [self._with_policy_weight(dict(b), n) for b in batches]
        with default_tracer().span("learner.dispatch", k=len(batches)):
            if len(batches) == 1:
                # Single-step groups reuse the per-step program (a fused
                # K=1 program would recompile for nothing).
                t0 = time.perf_counter()
                device_batch = shard_batch(self.mesh, batches[0], self.dp_axis)
                self.transfer_h2d_seconds += time.perf_counter() - t0
                span = (
                    self.flight.begin("learner", "learner_step", avals=f"B{n}")
                    if self.flight is not None
                    else None
                )
                self.state, metrics, td = self._step_fn(self.state, device_batch)
                self.dispatch_count += 1
                handle: dict = {"k": 1, "metrics": metrics, "td": td}
            else:
                t0 = time.perf_counter()
                stacked_host = {
                    key: np.stack([np.asarray(b[key]) for b in batches])
                    for key in batches[0]
                }
                if jax.process_count() > 1:
                    stacked = jax.tree_util.tree_map(
                        lambda x: jax.make_array_from_process_local_data(
                            self._stacked_shard, x
                        ),
                        stacked_host,
                    )
                else:
                    stacked = jax.tree_util.tree_map(
                        lambda x: jax.device_put(x, self._stacked_shard),
                        stacked_host,
                    )
                self.transfer_h2d_seconds += time.perf_counter() - t0
                span = (
                    self.flight.begin(
                        "learner",
                        "learner_fused_steps",
                        avals=f"K{len(batches)}xB{n}",
                    )
                    if self.flight is not None
                    else None
                )
                self.state, metrics_k, td_k = self._multi_step_fn(
                    self.state, stacked
                )
                self.dispatch_count += 1
                handle = {"k": len(batches), "metrics": metrics_k, "td": td_k}
        # The group stays in flight until train_steps_finish fetches;
        # the seal there gives the dispatch->fetch wall for the record.
        handle["flight"] = span
        # The dispatch semantically runs the steps; advance the host
        # mirror now so LR lookups / buffer sampling for the NEXT group
        # see the post-group step while this group still executes.
        handle["start_step"] = self._host_step
        self._host_step += handle["k"]
        return handle

    def train_steps_from(
        self, buffer, samples: "list[dict]"
    ) -> list[tuple[dict[str, float], np.ndarray]]:
        """K fused steps sampled from a `DeviceReplayBuffer`: upload
        only indices + IS weights; rows are gathered on device."""
        handle = self.train_steps_from_begin(buffer, samples)
        if handle is None:
            return []
        return self.train_steps_finish(handle)

    def train_steps_from_begin(
        self, buffer, samples: "list[dict]"
    ) -> dict | None:
        """Pipelined dispatch of a device-gathered fused group.

        `samples` are `DeviceReplayBuffer.sample` /
        `ShardedDeviceReplayBuffer.sample` outputs ({"indices",
        "weights"}); the sharded ring routes through a per-device
        local gather. Single-process only (gated in training/setup.py).
        Same handle/fetch contract as `train_steps_begin`/
        `train_steps_finish`.
        """
        if not samples:
            return None
        with default_tracer().span(
            "learner.dispatch",
            k=len(samples),
            ring_read=ring_read(buffer.storage),
        ):
            idx = np.stack(
                [np.asarray(s["indices"], dtype=np.int32) for s in samples]
            )
            weights = np.stack(
                [np.asarray(s["weights"], dtype=np.float32) for s in samples]
            )
            sharded = getattr(buffer, "is_sharded", False)
            from_fn = (
                self._get_from_sharded_fn(buffer) if sharded else self._from_fn
            )
            program = (
                "learner_fused_from_sharded_ring"
                if sharded
                else "learner_fused_from_ring"
            )
            span = (
                self.flight.begin(
                    "learner", program, avals=f"K{len(samples)}"
                )
                if self.flight is not None
                else None
            )
            self.state, metrics_k, td_k = from_fn(
                self.state, buffer.storage, idx, weights
            )
            self.dispatch_count += 1
        handle = {
            "k": len(samples),
            "metrics": metrics_k,
            "td": td_k,
            # The scan stacks outputs even at K=1; tells finish so.
            "stacked": True,
            "flight": span,
            "start_step": self._host_step,
        }
        self._host_step += len(samples)
        return handle

    def train_steps_finish(
        self, handle: dict
    ) -> list[tuple[dict[str, float], np.ndarray]]:
        """Blocking fetch of a `train_steps_begin` group's results.

        ONE device→host transfer for the whole group. Returns the
        per-step (metrics, local TD errors) list, in execution order.
        """
        k = handle["k"]
        tracer = default_tracer()
        metrics_k, td_k = handle["metrics"], handle["td"]
        t0 = time.perf_counter()
        with tracer.span("learner.wait", k=k):
            host_metrics_k, td_host = jax.device_get(  # graftlint: allow(host-sync-in-hot-path) the one blocking fetch per fused group
                (metrics_k, td_k if jax.process_count() == 1 else None)
            )
        self.transfer_d2h_seconds += time.perf_counter() - t0
        # Everything from here on runs after the device has finished.
        with tracer.span("learner.results", k=k):
            span = handle.pop("flight", None)
            if span is not None:
                span.seal()
            if td_host is None:
                td_host = local_rows(
                    td_k, axis=1 if (k > 1 or handle.get("stacked")) else 0
                )
            td_host = np.asarray(td_host)
            if k == 1 and not handle.get("stacked"):
                host_metrics_k = {
                    key: np.asarray(v)[None]
                    for key, v in host_metrics_k.items()
                }
                td_host = td_host[None]
            return self.group_results(
                handle["start_step"], host_metrics_k, td_host
            )

    def group_results(
        self, start_step: int, metrics_k: dict, td_k: np.ndarray
    ) -> list[tuple[dict[str, float], np.ndarray]]:
        """Per-step (metrics, TD errors) of a fetched group whose first
        step is `start_step + 1`, from host arrays with a leading step
        axis. Host only: nothing here may dispatch to the device (the
        device idles until the next group is sampled and dispatched)."""
        k = len(td_k)
        metrics_k = self._take_counters(metrics_k, np.shape(td_k)[-1])
        lrs = self.host_schedule(start_step + 1 + np.arange(k)).tolist()
        results = []
        for i in range(k):
            m = {key: float(v[i]) for key, v in metrics_k.items()}
            m["learning_rate"] = lrs[i]
            results.append((m, td_k[i]))
        return results

    # --- AOT warming (compile_cache.py; cli warm) -------------------------

    @property
    def aot_enabled(self) -> bool:
        """Whether the learner programs use the AOT artifact path on
        this backend (False on CPU — see the cpu_aot note at the wrap
        sites; `warm.py` reports those programs as skipped)."""
        return self._step_fn.aot_active

    def _zero_batch(self, n: int) -> DenseBatch:
        """A dense batch of the training shapes, all zeros — enough to
        lower the learner programs without touching a replay buffer."""
        mc, ec = self.nn.model_config, self.nn.env_config
        return {
            "grid": np.zeros(
                (n, mc.GRID_INPUT_CHANNELS, ec.ROWS, ec.COLS), np.float32
            ),
            "other_features": np.zeros(
                (n, mc.OTHER_NN_INPUT_FEATURES_DIM), np.float32
            ),
            "policy_target": np.full(
                (n, ec.action_dim), 1.0 / ec.action_dim, np.float32
            ),
            "value_target": np.zeros(n, np.float32),
            "weights": np.ones(n, np.float32),
            "policy_weight": np.ones(n, np.float32),
        }

    def warm_step(self, batch_size: int | None = None) -> bool:
        """AOT-precompile the per-step learner program (no execution,
        no state donation). True when an AOT executable is ready."""
        b = batch_size or self.config.BATCH_SIZE
        device_batch = shard_batch(
            self.mesh, self._zero_batch(b), self.dp_axis
        )
        return self._step_fn.warm(self.state, device_batch)

    def warm_steps(self, k: int, batch_size: int | None = None) -> bool:
        """AOT-precompile the K-fused learner program (one entry per
        distinct K, matching `train_steps`' per-K jit specialization)."""
        b = batch_size or self.config.BATCH_SIZE
        batch = self._zero_batch(b)
        stacked_host = {key: np.stack([batch[key]] * k) for key in batch}
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._stacked_shard), stacked_host
        )
        return self._multi_step_fn.warm(self.state, stacked)

    def warm_steps_from(
        self, buffer, k: int, batch_size: int | None = None
    ) -> bool:
        """AOT-precompile the device-replay fused program against a
        real ring's storage (shapes + shardings must match dispatch)."""
        b = batch_size or self.config.BATCH_SIZE
        idx = np.zeros((k, b), np.int32)
        weights = np.ones((k, b), np.float32)
        from_fn = (
            self._get_from_sharded_fn(buffer)
            if getattr(buffer, "is_sharded", False)
            else self._from_fn
        )
        return from_fn.warm(self.state, buffer.storage, idx, weights)

    # --- memory attribution (telemetry/memory.py; cli fit) ----------------

    def analyze_step(self, batch_size: int | None = None) -> "dict | None":
        """Memory record of the per-step learner program (AOT-lowered,
        never executed — works on CPU despite the cpu_aot bypass).
        The learner family's `cost_analysis()` record + `.cost.json`
        sidecar ride the same compile (telemetry/roofline.py), which
        is what gives `cli roofline` FLOP coverage of a family whose
        executable never enters the AOT artifact path on CPU."""
        b = batch_size or self.config.BATCH_SIZE
        device_batch = shard_batch(
            self.mesh, self._zero_batch(b), self.dp_axis
        )
        return self._step_fn.analyze(self.state, device_batch)

    def analyze_steps(
        self, k: int, batch_size: int | None = None
    ) -> "dict | None":
        """Memory record of the K-fused learner program."""
        b = batch_size or self.config.BATCH_SIZE
        batch = self._zero_batch(b)
        stacked_host = {key: np.stack([batch[key]] * k) for key in batch}
        stacked = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, self._stacked_shard), stacked_host
        )
        return self._multi_step_fn.analyze(self.state, stacked)

    def analyze_steps_from(
        self, buffer, k: int, batch_size: int | None = None
    ) -> "dict | None":
        """Memory record of the device-replay fused gather program
        (needs a real ring — its storage IS an argument)."""
        b = batch_size or self.config.BATCH_SIZE
        idx = np.zeros((k, b), np.int32)
        weights = np.ones((k, b), np.float32)
        from_fn = (
            self._get_from_sharded_fn(buffer)
            if getattr(buffer, "is_sharded", False)
            else self._from_fn
        )
        return from_fn.analyze(self.state, buffer.storage, idx, weights)

    @property
    def global_step(self) -> int:
        return self._host_step

    def get_current_lr(self) -> float:
        """LR at the current step (reference `trainer.py:312-323`)."""
        return float(self.host_schedule(self.global_step))

    def get_variables(self) -> dict:
        """Current model variables (for pushing into the eval wrapper)."""
        variables = {"params": self.state.params}
        if self.state.batch_stats:
            variables["batch_stats"] = self.state.batch_stats
        return variables

    def sync_to_network(self) -> int:
        """Install learner params into the `NeuralNetwork`; returns the
        bumped weights version (the TPU replacement for the reference's
        Ray weight broadcast, `worker_manager.py:169-209`).

        Hands the wrapper a device-side copy: the live state buffers get
        donated by the next train step. Tensor-sharded params are
        gathered first — the eval wrapper serves the single-device
        self-play path, which wants whole tensors."""
        variables = self.get_variables()
        if self.tp_size > 1:
            # On-device all-gather (ICI) first: after it every host
            # holds full replicas with no host round trip. Then hand
            # the eval wrapper each tensor's LOCAL replica (a
            # single-device array) — a multi-host replicated array
            # cannot be device_put to one device directly (it spans
            # non-addressable devices), but its first addressable
            # shard IS the whole tensor, already resident locally.
            variables = jax.device_put(variables, replicated(self.mesh))
            # jnp.array COPIES the local replica: for leaves that were
            # already replicated the device_put above is a no-op, and
            # handing the wrapper the raw shard would alias live state
            # buffers that the next train step donates.
            variables = jax.tree_util.tree_map(
                lambda x: jnp.array(x.addressable_shards[0].data), variables
            )
        else:
            variables = jax.tree_util.tree_map(jnp.array, variables)
        self.nn.set_weights(variables)
        return self.nn.weights_version

    def set_state(self, state: TrainState) -> None:
        """Install a restored TrainState (checkpoint resume path).

        Deep-copies: device_put is a no-op for already-replicated
        arrays, and an aliased caller pytree would be deleted by the
        next step's donation."""
        state = jax.tree_util.tree_map(jnp.array, state)
        self.state = jax.device_put(state, self._state_shard)
        self._host_step = int(self.state.step)  # one fetch, resume-only
