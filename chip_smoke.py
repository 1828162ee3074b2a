#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

`python chip_smoke.py` drives the main path once on one TPU chip, at
the full width of preset 3 (config/presets.py: 8x15 board, conv +
residual + 4-layer transformer, C51 head, 64 simulations, Gumbel root +
playout-cap randomisation, 512 self-play lanes, learner batch 256, the
250k-row replay ring). Only step counts are small. The phases:

- train-sync     `run_training` in the default loop mode, a few learner
                 steps. Run TWICE, each in its own process: the second
                 must find the first one's compiled programs in the
                 cache and still move the params.
- train-megastep `run_training` with the fused megastep: one dispatch
                 per iteration, params move, loss finite.
- serve          `cli serve` answers a few dozen move requests over a
                 handful of sessions from the checkpoint train-sync
                 saved.
- kernels        each Pallas kernel of ops/, compiled (not interpreted)
                 at the shapes the phases above use, against its XLA
                 lowering.
- native-engine  the C++ host engine, built fresh from engine.cpp,
                 against the JAX engine.

`python chip_smoke.py --chips 4` runs instead, and only, the dp=4
sharded megastep, its dp=1 comparison on one of the four chips, and the
resume of the dp=1 run's checkpoint on the dp=4 mesh.

Every phase prints one JSON object on its own line. The last line of
standard output is `{"ok": true, "device": {...}}` with the device as
JAX reports it, and the exit code is 0, only if every phase passed on a
TPU. A chip belongs to one process at a time, so this parent never
imports JAX: it runs the phases in child processes, one after another.

Run directories go under `runs/chip_smoke/` (emptied first), a copy of
the phase lines to `chiprun_out/chip_smoke.jsonl`, compiled programs to
the compile cache (`JAX_COMPILATION_CACHE_DIR`, else `.cache/jax`).
"""

import argparse
import contextlib
import functools
import gc
import io
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
RUNS = REPO / "runs" / "chip_smoke"
REPORT = REPO / "chiprun_out" / "chip_smoke.jsonl"
NATIVE_DIR = REPO / "alphatriangle_tpu" / "env" / "native"

# Children are killed when the whole run has used this much. The
# driver allows the one-chip run 1200 s, compilation included; the
# four-chip run is the builder's own and compiles three programs more.
BUDGET_SECONDS = {1: 1150.0, 4: 2400.0}

PRESET = 3
SEED = 0

# Which phases each child process runs, in order. train-sync comes
# first in its process both times, so the two are compared like for
# like; the second process then goes on to the other phases (a process
# takes a quarter of a minute to reach the chip).
CHILDREN = {
    1: (
        ("cold", ("train-sync",)),
        (
            "warm",
            ("train-sync", "train-megastep", "serve", "kernels", "native-engine"),
        ),
    ),
    4: (("dp", ("dp-megastep",)),),
}


class SmokeFailure(Exception):
    """A phase ran and what came out is wrong."""


def _check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# --- what the phases run at ----------------------------------------------


def flagship_configs() -> dict:
    """Preset 3 as published: nothing of its width is touched here."""
    from alphatriangle_tpu.config.presets import baseline_preset

    return baseline_preset(PRESET)


def _train_config(base, **overrides):
    """`base` with `overrides`, rebuilt through the constructor so the
    validators run (cli.merge_train_overrides does the same)."""
    from alphatriangle_tpu.config import TrainConfig

    return TrainConfig(
        **{**base.model_dump(), "AUTO_RESUME_LATEST": False, **overrides}
    )


# The recurrence of a linear-attention mixer as the benchmark's
# `ling-flash-ep4` runs it (no preset has such a layer): a block of 64
# boards, 32 heads of 128, the 252 cells of its board, chunks of 64.
RECURRENCE = {"boards": 64, "heads": 32, "tokens": 252, "head_dim": 128, "chunk": 64}
# The recurrence of a gated-delta-net mixer as `gigachat35-ep16` runs it:
# a block of 64 boards, 32 key heads of 128 each read by 2 value heads of
# 128, the 252 cells of its board, chunks of 64.
GDN_RECURRENCE = {
    "boards": 64, "key_heads": 32, "group": 2, "tokens": 252, "head_dim": 128,
    "chunk": 64,
}


# A round of the routed experts as `ling-flash-ep4` runs it: a block of
# 64 boards routes 16,128 tokens to 8 of 512 experts, 128 of them held;
# the round's buffer is twice what even routing brings, 64,512 rows of
# 2,560, and SwiGLUs of 768.
EXPERTS = {"rows": 64512, "d": 2560, "inter": 768, "held": 128, "limit": None}


def kernel_shapes(
    cfgs: dict,
    recurrence: "dict | None" = None,
    gdn: "dict | None" = None,
    experts: "dict | None" = None,
) -> dict:
    """The operand shapes the kernels see under `cfgs`: B games, a tree
    of N nodes x A actions searched W leaves at a time to depth D, an
    encoder layer over the leaf wave of a fast search (of a full one
    where the playout cap is off): `leaves` boards of `tokens` cells,
    `dim` wide; and `recurrence`, `gdn` and `experts`, which no preset
    sizes (`RECURRENCE`, `GDN_RECURRENCE`, `EXPERTS`)."""
    from alphatriangle_tpu.mcts.search import tree_geometry

    mcts, train, model = cfgs["mcts"], cfgs["train"], cfgs["model"]
    nodes, wave = tree_geometry(mcts)
    _, fast_wave = tree_geometry(
        mcts.model_copy(
            update={
                "max_simulations": mcts.fast_simulations or mcts.max_simulations,
                "fast_simulations": None,
            }
        )
    )
    return {
        "batch": train.SELF_PLAY_BATCH_SIZE,
        "nodes": nodes,
        "wave": wave,
        "actions": cfgs["env"].action_dim,
        "depth": mcts.max_depth,
        "leaves": train.SELF_PLAY_BATCH_SIZE * fast_wave,
        "tokens": cfgs["env"].ROWS * cfgs["env"].COLS,
        "dim": model.TRANSFORMER_DIM,
        "heads": model.TRANSFORMER_HEADS,
        "mlp_dim": model.TRANSFORMER_FC_DIM,
        "activation": model.ACTIVATION_FUNCTION,
        "compute_dtype": model.COMPUTE_DTYPE,
        "recurrence": dict(recurrence or RECURRENCE),
        "gdn": dict(gdn or GDN_RECURRENCE),
        "experts": dict(experts or EXPERTS),
    }


def kernel_cases(shapes: dict) -> list[dict]:
    """One case for each of five Pallas kernels of ops/: `run(mode,
    *operands)` calls it or the lowering named by `mode`, `operands(key)`
    makes seeded inputs at `shapes` (valid node/action indices), `xla`
    names the reference lowering (docs/KERNELS.md). `backup_update`
    matches its XLA chain but for the order of duplicate sums
    (`rounding`); `encoder_layer` rounds where Flax's layer rounds
    (`tolerance`) and is `timed` beside it; `delta_rule` and
    `gated_delta_net` are held to the token-by-token recurrence, and
    the chunked form each replaces on a TPU (`beside`) is held to it
    and timed too; `grouped_experts` is held to and timed beside the
    `ragged_dot` chain it replaces."""
    import jax
    import jax.numpy as jnp

    from alphatriangle_tpu.nn import linear_attention
    from alphatriangle_tpu.nn.model import _ACTIVATIONS, TransformerEncoderLayer
    from alphatriangle_tpu.nn.trunk import ragged_experts
    from alphatriangle_tpu.ops import backup_update
    from alphatriangle_tpu.ops.delta_rule import gated_delta_rule
    from alphatriangle_tpu.ops.encoder_layer import encoder_layer
    from alphatriangle_tpu.ops.gated_delta_rule import gated_delta_net
    from alphatriangle_tpu.ops.grouped_experts import grouped_experts

    b, n, w = shapes["batch"], shapes["nodes"], shapes["wave"]
    a, d = shapes["actions"], shapes["depth"]

    def backup_operands(key):
        ks = jax.random.split(key, 12)
        planes = (
            # Visit counts are whole numbers, as in a search: their sums
            # are exact in any order.
            jnp.floor(jax.random.uniform(ks[0], (b, n, a)) * 8.0),
            jax.random.normal(ks[1], (b, n, a)),
            jnp.full((b, n, a), -1.0),
            jax.random.normal(ks[2], (b, n, a)),
        )
        return planes + (
            jax.random.randint(ks[4], (b, w), 0, n),
            # One action per wave member: two members that insert at
            # the same edge carry the same child and reward in a real
            # wave, so which write lands last cannot matter there; XLA
            # leaves it undefined, and random values would make it show.
            (jax.random.randint(ks[5], (b, 1), 0, a) + jnp.arange(w)) % a,
            jnp.where(
                jax.random.bernoulli(ks[6], 0.5, (b, w)),
                jax.random.randint(ks[7], (b, w), 1, n).astype(jnp.float32),
                -1.0,
            ),
            jax.random.normal(ks[8], (b, w)),
            # A narrow range, so paths share edges and the update order
            # on duplicates is exercised.
            jax.random.randint(ks[9], (b, w, d), -1, min(n, 8)),
            jax.random.randint(ks[10], (b, w, d), -1, min(a, 8)),
            jax.random.bernoulli(ks[11], 0.7, (b, w, d)),
            jax.random.normal(ks[3], (b, w, d)),
        )

    act = _ACTIVATIONS[shapes["activation"]]
    layer = TransformerEncoderLayer(
        shapes["dim"], shapes["heads"], shapes["mlp_dim"], act,
        jnp.dtype(shapes["compute_dtype"]),
    )

    def layer_operands(key):
        ks = jax.random.split(key, 3)
        tokens = jax.random.normal(
            ks[0], (shapes["leaves"], shapes["tokens"], shapes["dim"]), layer.dtype
        )
        # Off their initial values: biases start at 0 and scales at 1,
        # where a kernel that dropped them would still agree.
        params = layer.init(ks[1], tokens[:1], False)["params"]
        leaves, tree = jax.tree_util.tree_flatten(params)
        moved = [
            leaf + 0.02 * jax.random.normal(k, leaf.shape)
            for leaf, k in zip(leaves, jax.random.split(ks[2], len(leaves)))
        ]
        return tokens, jax.tree_util.tree_unflatten(tree, moved)

    def run_layer(mode, tokens, params):
        if mode == "flax":
            return layer.apply({"params": params}, tokens, False)
        return encoder_layer(
            tokens, params, heads=shapes["heads"], act=act,
            interpret=jax.default_backend() != "tpu",
        )

    rec = shapes["recurrence"]
    rec_dtype = jnp.dtype(shapes["compute_dtype"])

    def recurrence_operands(key):
        """q, k, v, g, beta as a mixer hands them over: unit keys,
        queries over sqrt(head_dim), v in the compute type, g over
        (-5, 0), beta over (0, 1)."""
        ks = jax.random.split(key, 5)
        shape = (rec["boards"], rec["tokens"], rec["heads"], rec["head_dim"])
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, axis=-1, keepdims=True)
        )
        return (
            unit(jax.random.normal(ks[0], shape)) * rec["head_dim"] ** -0.5,
            unit(jax.random.normal(ks[1], shape)),
            jax.random.normal(ks[2], shape).astype(rec_dtype),
            -5.0 * jax.nn.sigmoid(jax.random.normal(ks[3], shape)),
            jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3])),
        )

    def run_recurrence(mode, q, k, v, g, beta):
        """o (boards, tokens, heads x head_dim) float32, by the kernel,
        the chunked form or the recurrence itself."""
        boards, tokens, heads, hd = q.shape
        if mode == "pallas":
            return gated_delta_rule(
                *(y.reshape(boards, tokens, heads * hd) for y in (q, k, v, g)),
                beta, heads=heads, chunk=rec["chunk"], lower_bound=-5.0,
                dtype=rec_dtype, interpret=jax.default_backend() != "tpu",
            )

        def heads_first(y):
            y = jnp.moveaxis(y, 2, 1)
            return y.reshape(boards * heads, tokens, *y.shape[3:])

        operands = [heads_first(y) for y in (q, k, v, g, beta)]
        if mode == "chunked":
            o = linear_attention.chunked(*operands, rec["chunk"], -5.0, rec_dtype)
        else:
            o = linear_attention.recurrent(*operands)
        o = jnp.moveaxis(o.reshape(boards, heads, tokens, hd), 1, 2)
        return o.reshape(boards, tokens, heads * hd)

    gdn = shapes["gdn"]
    key_heads, group, gdn_dim = gdn["key_heads"], gdn["group"], gdn["head_dim"]
    value_heads = key_heads * group

    def gdn_operands(key):
        """q, k, [q | k | v], g, beta as the mixer hands them over: unit
        keys, queries over sqrt(head_dim), qkv in the compute type, g =
        -A softplus(.) with A over (1, 16), beta over (0, 1)."""
        ks = jax.random.split(key, 6)
        b, s = gdn["boards"], gdn["tokens"]
        unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, axis=-1, keepdims=True)
        )
        q = unit(jax.random.normal(ks[0], (b, s, key_heads, gdn_dim))) * gdn_dim**-0.5
        k = unit(jax.random.normal(ks[1], (b, s, key_heads, gdn_dim)))
        q, k = q.reshape(b, s, -1), k.reshape(b, s, -1)
        v = jax.random.normal(ks[2], (b, s, value_heads * gdn_dim))
        rate = jax.random.uniform(ks[3], (value_heads,), minval=1.0, maxval=16.0)
        return (
            q, k, jnp.concatenate([q, k, v], axis=-1).astype(rec_dtype),
            -rate * jax.nn.softplus(jax.random.normal(ks[4], (b, s, value_heads))),
            jax.nn.sigmoid(jax.random.normal(ks[5], (b, s, value_heads))),
        )

    def run_gdn(mode, q, k, qkv, g, beta):
        """o (boards, tokens, value heads x head_dim) float32, by the
        kernel, the chunked form or the recurrence itself."""
        b, s, keys = q.shape
        if mode == "pallas":
            return gated_delta_net(
                q, k, qkv, g, beta, key_heads=key_heads, value_dim=gdn_dim,
                chunk=gdn["chunk"], dtype=rec_dtype, v_first=2 * keys,
                interpret=jax.default_backend() != "tpu",
            )
        v = qkv[..., 2 * keys :]
        if mode == "chunked":

            def heads_first(y):
                y = jnp.moveaxis(y.reshape(b, s, key_heads, *y.shape[3:]), 2, 1)
                return y.reshape(b * key_heads, s, *y.shape[3:])

            o = linear_attention.chunked_scalar(
                heads_first(q.reshape(b, s, key_heads, gdn_dim)),
                heads_first(k.reshape(b, s, key_heads, gdn_dim)),
                heads_first(v.reshape(b, s, key_heads, group, gdn_dim)),
                heads_first(g.reshape(b, s, key_heads, group)),
                heads_first(beta.reshape(b, s, key_heads, group)),
                gdn["chunk"], rec_dtype,
            )
            o = jnp.moveaxis(o.reshape(b, key_heads, s, group, gdn_dim), 1, 2)
            return o.reshape(b, s, value_heads * gdn_dim)

        def by_value_head(y):  # (b, s, value heads, ...) -> (b x value heads, s, ...)
            return jnp.moveaxis(y, 2, 1).reshape(b * value_heads, s, *y.shape[3:])

        def repeated(y):  # a key head's rows for each of its value heads
            return jnp.repeat(
                y.reshape(b, s, key_heads, gdn_dim).astype(rec_dtype), group, axis=2
            )

        o = linear_attention.recurrent(
            by_value_head(repeated(q)), by_value_head(repeated(k)),
            by_value_head(v.reshape(b, s, value_heads, gdn_dim)),
            by_value_head(
                jnp.broadcast_to(g[..., None], (b, s, value_heads, gdn_dim))
            ),
            by_value_head(beta),
        )
        return jnp.moveaxis(o.reshape(b, value_heads, s, gdn_dim), 1, 2).reshape(
            b, s, value_heads * gdn_dim
        )

    experts = shapes["experts"]

    def experts_operands(key):
        """A round's rows, half of them assigned, spread over the held
        experts at random; unit-normal rows, weights N(0, 1 / fan_in),
        all bfloat16 as the trunk cells state them."""
        ks = jax.random.split(key, 5)
        rows, width, inter, held = (
            experts["rows"], experts["d"], experts["inter"], experts["held"]
        )
        picks = jax.random.randint(ks[0], (rows // 2,), 0, held)

        def weight(k, fan_in, shape):
            return (jax.random.normal(k, shape) * fan_in**-0.5).astype(jnp.bfloat16)

        return (
            jax.random.normal(ks[1], (rows, width)).astype(jnp.bfloat16),
            jnp.zeros((held,), jnp.int32).at[picks].add(1),
            weight(ks[2], width, (held, width, inter)),
            weight(ks[3], inter, (held, inter, width)),
            weight(ks[4], width, (held, width, inter)),
        )

    def run_experts(mode, x, sizes, up, down, gate):
        """The round's (rows, d) out, by the kernels or the ragged chain."""
        if mode == "pallas":
            return grouped_experts(
                x, sizes, up, down, gate, limit=experts["limit"], dtype=jnp.bfloat16,
                interpret=jax.default_backend() != "tpu",
            )
        return ragged_experts(
            x, sizes, up, down, gate, limit=experts["limit"], dtype=jnp.bfloat16
        )

    return [
        {
            "name": "backup_update",
            "xla": "xla",
            "run": lambda mode, *ops: backup_update(*ops, mode=mode),
            "operands": backup_operands,
            # Output 1, e_value, sums returns over paths that share
            # edges. The kernel adds them member by member; in what
            # order the TPU's scatter-add takes duplicates is XLA's
            # affair, so the sums agree to f32 rounding, not to the bit.
            "rounding": {1},
        },
        {
            "name": "encoder_layer",
            "xla": "flax",
            "run": run_layer,
            "operands": layer_operands,
            # Unit normal tokens: the output is the residual stream,
            # values up to 8, rounded to the compute type (bfloat16:
            # steps of 2^-5 there); Flax rounds it at both adds, every
            # product's output and its softmax, the kernel once.
            "tolerance": 0.13,
            "timed": True,
        },
        {
            "name": "delta_rule",
            "xla": "recurrent",
            "run": run_recurrence,
            "operands": recurrence_operands,
            # Outputs up to 0.1; bfloat16 operands move one by up to
            # 0.002 on either path (PERF.md, PR 33).
            "tolerance": 0.01,
            "beside": "chunked",
            "timed": True,
        },
        {
            "name": "gated_delta_net",
            "xla": "recurrent",
            "run": run_gdn,
            "operands": gdn_operands,
            # Outputs up to 0.1; bfloat16 operands move one by under
            # 0.002 even on keys that repeat each other (interpreted:
            # tests/test_ops_gated_delta_rule.py).
            "tolerance": 0.01,
            "beside": "chunked",
            "timed": True,
        },
        {
            "name": "grouped_experts",
            "xla": "ragged",
            "run": run_experts,
            "operands": experts_operands,
            # Outputs up to about 4, rounded to bfloat16 on both paths
            # (steps of 2^-6 there); only the order of the float32 sums
            # differs (exact at this shape on a v5e: PERF.md section 6).
            "tolerance": 0.05,
            "timed": True,
        },
    ]


# --- observations shared by the phases -----------------------------------


def device_record() -> dict:
    import jax

    first = jax.devices()[0]
    return {
        "platform": first.platform,
        "kind": first.device_kind,
        "count": len(jax.devices()),
    }


def _cache_stats() -> dict:
    from alphatriangle_tpu.compile_cache import get_compile_cache

    return get_compile_cache().stats()


def _cache_delta(before: dict, after: dict) -> dict:
    """What the compile cache did during one phase. A failed reload or
    a failed AOT compile falls back quietly inside compile_cache.py;
    here it fails the phase."""
    events = after["events"][len(before["events"]):]
    delta = {
        key: after[key] - before[key]
        for key in (
            "hits", "misses", "deserialize_errors", "serialize_errors",
            "exec_errors",
        )
    }
    delta["dir"] = after["dir"]
    delta["compile_seconds"] = round(
        sum(e["seconds"] for e in events if e["event"] == "miss"), 2
    )
    delta["load_seconds"] = round(
        sum(e["seconds"] for e in events if e["event"] == "hit"), 2
    )
    delta["events"] = events
    _check(
        delta["deserialize_errors"] == 0 and delta["exec_errors"] == 0,
        f"compile cache fell back: {delta}",
    )
    return delta


def _peak_bytes() -> "int | None":
    import jax

    stats = jax.devices()[0].memory_stats()  # None on the CPU
    return stats.get("peak_bytes_in_use") if stats else None


def _devices_of(tree) -> set:
    import jax

    return {
        d for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()
    }


def _ledger(run_dir: Path) -> list[dict]:
    return [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]


def _ledger_losses(records: list[dict]) -> list[float]:
    return [
        r["means"]["Loss/total_loss"]
        for r in records
        if r.get("kind") == "tick" and "Loss/total_loss" in r.get("means", {})
    ]


def _params_moved(before, after) -> bool:
    import jax
    import numpy as np

    return any(
        not np.array_equal(x, np.asarray(y))
        for x, y in zip(
            jax.tree_util.tree_leaves(before),
            jax.tree_util.tree_leaves(jax.device_get(after)),
        )
    )


def _run_training(cfgs: dict, train_cfg, mesh_cfg, root: Path) -> dict:
    """`training.runner.run_training`, the library's entry point, with
    a handle on what it built: it returns an exit code only, and it
    turns a set-up or restore exception into `return 1`."""
    import jax

    from alphatriangle_tpu.config import PersistenceConfig
    from alphatriangle_tpu.training import runner

    built: dict = {}
    real_setup = runner.setup_training_components

    def setup_and_keep(**kwargs):
        components = real_setup(**kwargs)
        built["components"] = components
        built["params_at_setup"] = jax.device_get(
            components.trainer.state.params
        )
        built["setup_seconds"] = round(time.monotonic() - t0, 2)
        return components

    runner.setup_training_components = setup_and_keep
    t0 = time.monotonic()
    try:
        code = runner.run_training(
            train_config=train_cfg,
            env_config=cfgs["env"],
            model_config=cfgs["model"],
            mcts_config=cfgs["mcts"],
            mesh_config=mesh_cfg,
            persistence_config=PersistenceConfig(
                ROOT_DATA_DIR=str(root), RUN_NAME=train_cfg.RUN_NAME
            ),
            use_tensorboard=False,
            log_level="WARNING",
        )
    finally:
        runner.setup_training_components = real_setup
    _check(code == 0, f"run_training({train_cfg.RUN_NAME}) returned {code}")
    components = built["components"]
    records = _ledger(components.persistence_config.get_run_base_dir())
    losses = _ledger_losses(records)
    _check(bool(losses), "no learner step reached the ledger")
    _check(
        all(x == x and abs(x) != float("inf") for x in losses),
        f"loss not finite: {losses}",
    )
    state = components.trainer.state
    _check(
        int(state.step) == train_cfg.MAX_TRAINING_STEPS,
        f"stopped at step {int(state.step)}",
    )
    _check(
        _params_moved(built["params_at_setup"], state.params),
        "params did not change across the learner steps",
    )
    built["records"] = records
    built["losses"] = [round(x, 5) for x in losses]
    return built


# --- the phases ----------------------------------------------------------


def phase_train_sync(
    cfgs: dict,
    root: Path,
    run_name: str,
    *,
    steps: int,
    min_buffer: int,
    chunk_moves: int,
) -> dict:
    """A few learner steps in the default loop mode. DEVICE_REPLAY is
    left at "auto": on an accelerator it must resolve to the HBM ring,
    and ring, params and optimizer state must sit on the first device."""
    import jax

    from alphatriangle_tpu.config import MeshConfig
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

    built = _run_training(
        cfgs,
        _train_config(
            cfgs["train"],
            RUN_NAME=run_name,
            MAX_TRAINING_STEPS=steps,
            # One fused group after each rollout, so the K-step learner
            # program the preset names is the one that runs (left to
            # itself the loop matches the rows a chunk yields, which
            # under playout-cap randomisation is about half a group,
            # and half groups run as single steps).
            LEARNER_STEPS_PER_ROLLOUT=cfgs["train"].FUSED_LEARNER_STEPS,
            MIN_BUFFER_SIZE_TO_TRAIN=min_buffer,
            ROLLOUT_CHUNK_MOVES=chunk_moves,
        ),
        MeshConfig(DP_SIZE=1),
        root,
    )
    c = built["components"]
    device = jax.devices()[0]
    on_accelerator = jax.default_backend() != "cpu"
    ring = isinstance(c.buffer, DeviceReplayBuffer)
    _check(
        ring == on_accelerator,
        f"DEVICE_REPLAY=auto gave {type(c.buffer).__name__} on "
        f"{jax.default_backend()}",
    )
    placed = {"params": c.trainer.state.params, "opt": c.trainer.state.opt_state}
    if ring:
        placed["ring"] = c.buffer.storage
    for name, tree in placed.items():
        _check(
            _devices_of(tree) == {device},
            f"{name} sits on {_devices_of(tree)}, not {device}",
        )
    return {
        "run": run_name,
        "buffer": type(c.buffer).__name__,
        "ring_bytes": c.buffer.storage_nbytes() if ring else 0,
        "device": str(device),
        "learner_steps": steps,
        "losses": built["losses"],
        "setup_seconds": built["setup_seconds"],
    }


def phase_train_megastep(
    cfgs: dict,
    root: Path,
    run_name: str,
    *,
    iterations: int,
    learner_steps: int,
    min_buffer: int,
    chunk_moves: int,
) -> dict:
    """`--fused-megastep --fused-learner-steps K --device-replay on`:
    rollout + ring ingest + K learner steps as one device program."""
    from alphatriangle_tpu.config import MeshConfig

    built = _run_training(
        cfgs,
        _train_config(
            cfgs["train"],
            RUN_NAME=run_name,
            FUSED_MEGASTEP=True,
            FUSED_LEARNER_STEPS=learner_steps,
            DEVICE_REPLAY="on",
            MAX_TRAINING_STEPS=iterations * learner_steps,
            MIN_BUFFER_SIZE_TO_TRAIN=min_buffer,
            ROLLOUT_CHUNK_MOVES=chunk_moves,
        ),
        MeshConfig(DP_SIZE=1),
        root,
    )
    c = built["components"]
    return {
        "run": run_name,
        "ring_bytes": c.buffer.storage_nbytes(),
        "losses": built["losses"],
        "setup_seconds": built["setup_seconds"],
        **_one_dispatch_per_iteration(built, iterations, mesh_devices=1),
    }


def _one_dispatch_per_iteration(
    built: dict, iterations: int, mesh_devices: int
) -> dict:
    """The megastep's contract, from the run's own ledger and counters."""
    runner = built["components"].megastep
    gauges = [
        r
        for r in built["records"]
        if r.get("kind") == "util"
        and isinstance(r.get("dispatches_per_iteration"), (int, float))
    ]
    _check(bool(gauges), "no util record carries dispatches_per_iteration")
    last = gauges[-1]
    _check(
        last["dispatches_per_iteration"] == 1.0,
        f"dispatches_per_iteration {last['dispatches_per_iteration']}",
    )
    _check(
        last["mesh_devices"] == mesh_devices,
        f"mesh_devices {last['mesh_devices']}, wanted {mesh_devices}",
    )
    _check(
        runner.dispatch_count == iterations,
        f"{runner.dispatch_count} megastep dispatches for "
        f"{iterations} iterations",
    )
    _check(
        built["components"].trainer.dispatch_count == 0,
        "the learner dispatched outside the megastep",
    )
    return {
        "iterations": iterations,
        "dispatches_per_iteration": last["dispatches_per_iteration"],
        "mesh_devices": last["mesh_devices"],
    }


def phase_serve(
    cfgs: dict,
    root: Path,
    train_run: str,
    *,
    slots: int,
    sessions: int,
    max_moves: int,
) -> dict:
    """`cli serve --smoke` over the checkpoint and configs `train_run`
    saved: a PolicyService at the same net, warmed, pre-flighted, then
    one wave of simulated sessions. Every request must be answered."""
    import jax

    from alphatriangle_tpu import cli

    mcts = cfgs["mcts"]
    argv = [
        "serve", "--smoke",
        "--device", jax.default_backend(),
        "--run-name", train_run,
        "--root-dir", str(root),
        "--serve-run-name", "serve",
        "--slots", str(slots),
        "--sims", str(mcts.max_simulations),
        "--sessions", str(sessions),
        "--max-moves", str(max_moves),
        "--seed", str(SEED),
        # A wave this short ends before the default eighth dispatch;
        # tick every dispatch so its latencies reach the ledger.
        "--tick-every", "1",
    ]
    if mcts.root_selection == "gumbel":
        argv.append("--gumbel")
    printed = io.StringIO()
    with contextlib.redirect_stdout(printed):
        code = cli.main(argv)
    _check(code == 0, f"cli serve returned {code}: {printed.getvalue()!r}")
    report = json.loads(printed.getvalue().strip().splitlines()[-1])
    _check(
        report["source"].startswith("step "),
        f"served {report['source']!r}, not the checkpoint of {train_run}",
    )
    _check(
        report["sessions_served"] == sessions,
        f"{report['sessions_served']} of {sessions} sessions served",
    )
    _check(
        report["moves_served"] == report["serve_requests_total"] > 0
        and report["serve_queue_depth"] == 0,
        f"requests left unanswered: {report}",
    )
    # The report's own percentiles are of a window the last tick has
    # drained; the service's ledger has every window.
    latencies = [
        r["serve_move_latency_ms_p50"]
        for r in _ledger(Path(report["ledger"]).parent)
        if r.get("serve_move_latency_ms_p50") is not None
    ]
    _check(bool(latencies), "no move latency reached the serve ledger")
    return {
        "slots": slots,
        "sims": mcts.max_simulations,
        "source": report["source"],
        "sessions": report["sessions_served"],
        "requests": report["serve_requests_total"],
        "answered": report["moves_served"],
        "shed": report["serve_requests_total"] - report["moves_served"],
        "dispatches": report["dispatches"],
        "move_latency_ms_p50": latencies[-1],
    }


def _device_ops_ms(fn, operands, calls: int = 5, top: int = 6) -> dict:
    """Device milliseconds a call of `fn`, by operation, dearest first
    (`calls` executions under the profiler; the instruction's name and
    output shape): the kernel's custom call beside the fusions XLA
    makes of the same work. Empty where the trace has no `XLA Ops`
    line (a CPU run)."""
    import tempfile

    import jax

    from alphatriangle_tpu.profiling import device_operations

    jax.block_until_ready(fn(*operands))
    with tempfile.TemporaryDirectory() as trace_dir:
        jax.profiler.start_trace(trace_dir)
        try:
            for _ in range(calls):
                out = fn(*operands)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(str(found[-1]))
        total: dict = {}
        for _, ops, _ in device_operations(data.planes):
            for event, _, duration_ns in ops:
                name = event.split("(", 1)[0].strip()
                total[name] = total.get(name, 0.0) + duration_ns
    dearest = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return {name: round(ns / calls / 1e6, 3) for name, ns in dearest}


def phase_kernels(
    cfgs: dict,
    recurrence: "dict | None" = None,
    gdn: "dict | None" = None,
    experts: "dict | None" = None,
) -> dict:
    """Each Pallas kernel at the shapes of `cfgs` (the recurrences' at
    `recurrence` and `gdn`, the experts' round at `experts`, else
    `RECURRENCE`, `GDN_RECURRENCE`, `EXPERTS`), compiled for this
    backend and compared with its XLA lowering on the same operands.
    On a TPU the compiled text must hold the kernel: the dispatchers
    interpret it on any other backend, which proves nothing here."""
    import jax
    import numpy as np

    on_tpu = jax.default_backend() == "tpu"
    shapes = kernel_shapes(cfgs, recurrence, gdn, experts)
    parity: dict = {}
    wrong = []
    for i, case in enumerate(kernel_cases(shapes)):
        t0 = time.monotonic()
        operands = jax.jit(case["operands"])(jax.random.PRNGKey(SEED + i))
        compiled = (
            jax.jit(functools.partial(case["run"], "pallas"))
            .lower(*operands)
            .compile()
        )
        if ("tpu_custom_call" in compiled.as_text()) != on_tpu:
            wrong.append(f"{case['name']}: kernel in compiled text != {on_tpu}")
        reference = jax.jit(functools.partial(case["run"], case["xla"]))
        got = jax.tree_util.tree_leaves(compiled(*operands))
        want = jax.tree_util.tree_leaves(reference(*operands))
        _check(len(got) == len(want), f"{case['name']}: output count")
        verdict = "exact"
        for j, (g, x) in enumerate(zip(got, want)):
            g, x = np.asarray(g), np.asarray(x)
            if np.array_equal(g, x):
                continue
            gap = float(np.max(np.abs(g.astype(np.float64) - x)))
            if j in case.get("rounding", ()) and np.allclose(
                g, x, rtol=1e-5, atol=1e-5
            ):
                verdict = f"f32 rounding (output {j}: max |diff| {gap:.3g})"
            elif gap <= case.get("tolerance", -1.0):
                verdict = f"{g.dtype} rounding (output {j}: max |diff| {gap:.3g})"
            else:
                verdict = f"output {j} differs (max |diff| {gap:.3g})"
                wrong.append(f"{case['name']}: {verdict}")
        parity[case["name"]] = {
            "vs": case["xla"],
            "parity": verdict,
            "seconds": round(time.monotonic() - t0, 1),
        }
        # What the kernel is timed beside: its reference, or (`beside`)
        # the lowering it replaces where the reference is only an
        # oracle; that one is held to the reference as well.
        beside, timed = case["xla"], reference
        if "beside" in case:
            beside = case["beside"]
            timed = jax.jit(functools.partial(case["run"], beside))
            gap = float(
                np.max(np.abs(np.asarray(timed(*operands), np.float64) - want[0]))
            )
            if gap > case["tolerance"]:
                wrong.append(f"{case['name']}: {beside} differs (max |diff| {gap:.3g})")
            parity[case["name"]][beside] = f"max |diff| {gap:.3g}"
        if case.get("timed"):
            parity[case["name"]]["device_ms_a_call"] = {
                "pallas": _device_ops_ms(compiled, operands),
                beside: _device_ops_ms(timed, operands, top=16),
            }
    _check(not wrong, f"kernels: {wrong}; parity so far: {parity}")
    return {"shapes": shapes, "compiled": on_tpu, "parity": parity}


def phase_native_engine(cfgs: dict, *, games: int, moves: int) -> dict:
    """The C++ host engine, built from engine.cpp in this checkout (the
    parent removed any library that travelled with it), sees the legal
    moves the JAX engine sees along a playout."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.env.native import (
        NativeTriangleEnv,
        native_build_error,
        native_library_path,
    )

    env = TriangleEnv(cfgs["env"])
    existed = native_library_path().exists()
    try:
        native = NativeTriangleEnv(env)
    except RuntimeError as exc:
        raise SmokeFailure(f"native engine: {native_build_error()}") from exc
    states = env.reset_batch(
        jax.random.split(jax.random.PRNGKey(SEED), games)
    )
    for move in range(moves):
        batch = native.new_batch(games)
        batch.occupied[:] = np.asarray(states.occupied)
        batch.shape_idx[:] = np.asarray(states.shape_idx)
        batch.done[:] = np.asarray(states.done).astype(np.uint8)
        valid = np.asarray(env.valid_mask_batch(states))
        _check(
            np.array_equal(valid, native.valid_mask(batch)),
            f"valid-action masks differ at move {move}",
        )
        states, _, _ = env.step_batch(
            states, jnp.asarray(valid.argmax(axis=1), jnp.int32)
        )
    return {
        "library": native_library_path().name,
        "built_here": not existed,
        "games": games,
        "moves": moves,
    }


def phase_dp_megastep(
    cfgs: dict,
    root: Path,
    *,
    dp: int,
    iterations: int,
    learner_steps: int,
    min_buffer: int,
    chunk_moves: int,
) -> dict:
    """The dp-sharded megastep across `dp` devices, the same iterations
    at dp=1 on the first device beside it, and the dp=1 run's
    checkpoint resumed on the dp mesh for as many iterations again.
    The two fresh runs draw different per-shard keys, so their losses
    are reported side by side, not compared."""
    from alphatriangle_tpu.config import MeshConfig

    steps = iterations * learner_steps

    def run(run_name: str, mesh_dp: int, max_steps: int) -> dict:
        return _run_training(
            cfgs,
            _train_config(
                cfgs["train"],
                RUN_NAME=run_name,
                FUSED_MEGASTEP=True,
                FUSED_LEARNER_STEPS=learner_steps,
                DEVICE_REPLAY="on",
                MAX_TRAINING_STEPS=max_steps,
                CHECKPOINT_SAVE_FREQ_STEPS=steps,
                MIN_BUFFER_SIZE_TO_TRAIN=min_buffer,
                ROLLOUT_CHUNK_MOVES=chunk_moves,
            ),
            MeshConfig(DP_SIZE=mesh_dp),
            root,
        )

    single = run("dp1", 1, steps)
    _check(
        not getattr(single["components"].buffer, "is_sharded", False),
        "the dp=1 run built a sharded ring",
    )
    gauge_1 = _one_dispatch_per_iteration(single, iterations, mesh_devices=1)
    single_losses = single["losses"]
    del single  # its ring and trees leave the first device
    gc.collect()

    sharded = run(f"dp{dp}", dp, steps)
    gauge_dp = _one_dispatch_per_iteration(sharded, iterations, mesh_devices=dp)
    placement = _check_sharded(sharded["components"], dp)
    sharded_losses = sharded["losses"]
    del sharded
    gc.collect()

    # Same run name and root: run_training restores dp1's checkpoint
    # and ring spill into the dp-mesh components, then trains on.
    resumed = run("dp1", dp, 2 * steps)
    _one_dispatch_per_iteration(resumed, iterations, mesh_devices=dp)
    _check_sharded(resumed["components"], dp)
    _check(
        resumed["components"].checkpoints.latest_step() == 2 * steps,
        "the resumed run did not save its last step",
    )
    return {
        "dp": dp,
        **gauge_dp,
        "dp1_dispatches_per_iteration": gauge_1["dispatches_per_iteration"],
        "losses_dp1": single_losses,
        f"losses_dp{dp}": sharded_losses,
        "losses_resumed": resumed["losses"],
        "resumed_from_step": steps,
        "resumed_to_step": 2 * steps,
        **placement,
    }


def _check_sharded(c, dp: int) -> dict:
    """What tests/test_megastep_sharded.py pins on virtual devices:
    ring shards, lane slices and params each on `dp` distinct devices,
    params bit-identical across them, every shard's device priorities
    equal to its host SumTree mirror."""
    import jax
    import numpy as np

    _check(getattr(c.buffer, "is_sharded", False), "the ring is not sharded")
    _check(c.megastep.sharded and c.megastep.dp == dp, "megastep not dp-wide")

    def shard_devices(array) -> list:
        return sorted(s.device.id for s in array.addressable_shards)

    spread = {
        "ring": shard_devices(c.buffer.storage["policy_target"]),
        "lanes": shard_devices(c.self_play.states.step_count),
        "params": shard_devices(
            jax.tree_util.tree_leaves(c.trainer.state.params)[0]
        ),
    }
    for name, ids in spread.items():
        _check(
            len(ids) == len(set(ids)) == dp,
            f"{name} shards sit on devices {ids}, wanted {dp} distinct",
        )
    for leaf in jax.tree_util.tree_leaves(c.trainer.state.params):
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        _check(
            all(np.array_equal(shards[0], s) for s in shards[1:]),
            "params differ across shards",
        )
    priorities = np.asarray(c.megastep._priorities)
    for k, tree in enumerate(c.buffer.trees):
        size = int(c.buffer._sizes[k])
        _check(size > 0, f"shard {k} never ingested")
        on_device = priorities[k * c.buffer.stride:k * c.buffer.stride + size]
        on_host = tree.tree[np.arange(size) + tree._cap2]
        _check(
            np.allclose(on_device, on_host, rtol=1e-4, atol=1e-6),
            f"shard {k}: device priorities left the host mirror",
        )
    return {f"{name}_devices": ids for name, ids in spread.items()}


# --- child: holds the chip, runs phases ----------------------------------


def _phase_calls(child: str) -> dict:
    """Each phase at the size a chip run uses: preset 3, few steps."""
    cfgs = flagship_configs()
    k = cfgs["train"].FUSED_LEARNER_STEPS
    # One 16-move chunk of 512 lanes yields ~2k rows under playout-cap
    # randomisation (a quarter of the moves are recorded).
    warmup = {"min_buffer": 1024, "chunk_moves": 16}
    return {
        "train-sync": lambda: phase_train_sync(
            cfgs, RUNS, f"sync_{child}", steps=2 * k, **warmup
        ),
        "train-megastep": lambda: phase_train_megastep(
            cfgs, RUNS, "megastep", iterations=3, learner_steps=k, **warmup
        ),
        "serve": lambda: phase_serve(
            cfgs, RUNS, "sync_cold", slots=64, sessions=8, max_moves=6
        ),
        "kernels": lambda: phase_kernels(cfgs),
        "native-engine": lambda: phase_native_engine(cfgs, games=64, moves=8),
        "dp-megastep": lambda: phase_dp_megastep(
            cfgs, RUNS, dp=4, iterations=2, learner_steps=k, **warmup
        ),
    }


def _emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run_child(child: str, phases: tuple, chips: int) -> int:
    device = device_record()
    if device["platform"] != "tpu" or device["count"] != chips:
        _emit({"phase": "device", "ok": False, "device": device, "want_chips": chips})
        return 2
    _emit({"phase": "device", "ok": True, "device": device})
    calls = _phase_calls(child)
    for phase in phases:
        gc.collect()  # the last phase's ring and trees leave the device
        before = _cache_stats()
        t0 = time.monotonic()
        try:
            result = calls[phase]()
            cache = _cache_delta(before, _cache_stats())
        except BaseException as exc:
            _emit({"phase": phase, "process": child, "ok": False, "error": repr(exc)})
            raise
        _emit(
            {
                "phase": phase,
                "process": child,
                "ok": True,
                "seconds": round(time.monotonic() - t0, 1),
                **result,
                "compile_cache": cache,
                "peak_bytes_in_use": _peak_bytes(),
            }
        )
    return 0


# --- parent: never imports JAX -------------------------------------------


def _spawn(child: str, chips: int, deadline: float) -> tuple[int, list[dict]]:
    """Run one child to its end (or the deadline), echo what it prints,
    hand back its exit code and its phase records."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(REPO), env.get("PYTHONPATH")) if p
    )
    proc = subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve()),
         "--chips", str(chips), "--child", child],
        stdout=subprocess.PIPE,
        text=True,
        cwd=REPO,
        env=env,
    )
    try:
        out, _ = proc.communicate(timeout=max(1.0, deadline - time.monotonic()))
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
        out += json.dumps({"phase": child, "ok": False, "error": "time limit"}) + "\n"
    records = []
    for line in out.splitlines():
        print(line, flush=True)
        if line.startswith("{"):
            try:
                records.append(json.loads(line))
            except ValueError:
                pass
    return proc.returncode, records


def _cache_verdict(records: list[dict]) -> dict:
    """The two train-sync runs side by side. The second process must
    have taken both its rollout and its learner program from the cache
    (its params moved: the phase checked that), and so have finished
    sooner. A cache that came already filled makes the first process
    warm too; then there is nothing to be sooner than."""
    cold, warm = (
        next(
            r
            for r in records
            if r.get("process") == process and r["phase"] == "train-sync"
        )
        for process in ("cold", "warm")
    )
    reloaded = all(
        any(
            e["program"].startswith(family) and e["event"] == "hit"
            for e in warm["compile_cache"]["events"]
        )
        for family in ("self_play_chunk", "learner")
    )
    cold_compiled = cold["compile_cache"]["misses"] > 0
    return {
        "phase": "cache",
        "ok": reloaded
        and (not cold_compiled or warm["seconds"] < cold["seconds"]),
        "cold_seconds": cold["seconds"],
        "warm_seconds": warm["seconds"],
        "cold_setup_seconds": cold["setup_seconds"],
        "warm_setup_seconds": warm["setup_seconds"],
        "cold_compile_seconds": cold["compile_cache"]["compile_seconds"],
        "warm_load_seconds": warm["compile_cache"]["load_seconds"],
        "warm_hits": warm["compile_cache"]["hits"],
        "warm_misses": warm["compile_cache"]["misses"],
    }


def run_parent(chips: int, records: list[dict]) -> "str | None":
    """Run the children of the plan in turn, gathering what they print
    into `records`; the reason the run failed, or None."""
    deadline = time.monotonic() + BUDGET_SECONDS[chips]
    # Nothing that travelled with the tree is read back: old runs go,
    # and so does any engine library built on another machine.
    shutil.rmtree(RUNS, ignore_errors=True)
    for stale in NATIVE_DIR.glob("_libat_engine*.so"):
        stale.unlink()
    for child, phases in CHILDREN[chips]:
        code, seen = _spawn(child, chips, deadline)
        records += seen
        done = {r["phase"] for r in seen if r.get("ok") is True}
        if code != 0 or not set(phases) <= done:
            return f"process {child!r} exit {code}, passed {sorted(done)}"
    if chips == 1:
        verdict = _cache_verdict(records)
        _emit(verdict)
        if not verdict["ok"]:
            return "the second process did not start from the cache"
    return None


def main(argv: "list[str] | None" = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=sorted(CHILDREN), default=1,
        help="4: run only the dp=4 megastep and its dp=1 comparison.",
    )
    parser.add_argument("--child", default=None, help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child is not None:
        phases = dict(CHILDREN[args.chips])[args.child]
        return run_child(args.child, phases, args.chips)

    records: list[dict] = []
    failed = run_parent(args.chips, records)
    REPORT.parent.mkdir(parents=True, exist_ok=True)
    REPORT.write_text("".join(json.dumps(r) + "\n" for r in records))
    device = next(
        (r["device"] for r in records if r.get("phase") == "device"), None
    )
    if failed is None and (not device or device["platform"] != "tpu"):
        failed = "no TPU"
    if failed is not None:
        _emit({"ok": False, "device": device, "failed": failed})
        return 1
    _emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
