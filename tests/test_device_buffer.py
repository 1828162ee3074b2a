"""Device-resident replay ring (rl/device_buffer.py).

Covers: host/device ingest parity (content, order, slots, ring wrap),
on-device row validation, PER bookkeeping via ingest counts,
sample+gather training equivalence against the host path, snapshot
round trips in both directions, and the training loop running end to
end in device-replay mode (sync + overlapped).
"""

import numpy as np
import pytest

from alphatriangle_tpu.rl.buffer import ExperienceBuffer
from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer


GRID_SHAPE = (1, 3, 4)
OTHER_DIM = 5
ACTION_DIM = 12


def _cfg(tiny_train_config, **updates):
    return tiny_train_config.model_copy(update=updates)


def _dev_buffer(cfg, seed=0):
    return DeviceReplayBuffer(
        cfg,
        grid_shape=GRID_SHAPE,
        other_dim=OTHER_DIM,
        action_dim=ACTION_DIM,
        seed=seed,
    )


def _rows(n, rng, value=None):
    """n valid experience rows (grids in {-1,0,1}, normalized policy)."""
    grid = rng.integers(-1, 2, size=(n, *GRID_SHAPE)).astype(np.float32)
    other = rng.random((n, OTHER_DIM), dtype=np.float32)
    policy = rng.random((n, ACTION_DIM), dtype=np.float32) + 0.01
    policy /= policy.sum(axis=1, keepdims=True)
    val = (
        np.full(n, value, np.float32)
        if value is not None
        else rng.normal(size=n).astype(np.float32)
    )
    pw = (rng.random(n) > 0.3).astype(np.float32)
    return grid, other, policy, val, pw


class TestIngestParity:
    def test_add_dense_matches_host_buffer(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=32, USE_PER=True,
                   PER_BETA_ANNEAL_STEPS=100)
        rng = np.random.default_rng(1)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        for n in (5, 11, 7):
            rows = _rows(n, rng)
            s_host = host.add_dense(*rows[:4], policy_weight=rows[4])
            s_dev = dev.add_dense(*rows[:4], policy_weight=rows[4])
            np.testing.assert_array_equal(s_host, s_dev)
        assert len(host) == len(dev)
        hs, ds = host.get_state(), dev.get_state()
        assert hs["pos"] == ds["pos"] and hs["size"] == ds["size"]
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )
        np.testing.assert_allclose(hs["priorities"], ds["priorities"])

    def test_ring_wraparound(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=8, USE_PER=False)
        rng = np.random.default_rng(2)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        for n in (6, 5, 4):  # wraps twice
            rows = _rows(n, rng)
            host.add_dense(*rows[:4], policy_weight=rows[4])
            dev.add_dense(*rows[:4], policy_weight=rows[4])
        assert len(dev) == 8 and dev._pos == host._pos
        hs, ds = host.get_state(), dev.get_state()
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )

    def test_single_ingest_larger_than_capacity(self, tiny_train_config):
        """One add of 20 rows into an 8-slot ring keeps the newest 8 in
        the same slots the host ring's last-write-wins produces."""
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=8, USE_PER=False)
        rng = np.random.default_rng(7)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        rows = _rows(20, rng)
        host.add_dense(*rows[:4], policy_weight=rows[4])
        dev.add_dense(*rows[:4], policy_weight=rows[4])
        assert len(dev) == 8 and dev._pos == host._pos == 20 % 8
        hs, ds = host.get_state(), dev.get_state()
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )

    def test_invalid_rows_dropped(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=16, USE_PER=False)
        rng = np.random.default_rng(3)
        dev = _dev_buffer(cfg)
        grid, other, policy, val, pw = _rows(6, rng)
        grid[1, 0, 0, 0] = np.nan  # non-finite feature
        policy[3] *= 3.0  # not a distribution
        val[4] = np.inf  # non-finite return
        slots = dev.add_dense(grid, other, policy, val, policy_weight=pw)
        assert len(dev) == 3 and len(slots) == 3
        state = dev.get_state()
        keep = [0, 2, 5]
        np.testing.assert_array_equal(
            state["storage"]["grid"], grid[keep].astype(np.int8)
        )
        np.testing.assert_allclose(
            state["storage"]["value_target"], val[keep]
        )

    def test_sample_returns_indices_only(self, tiny_train_config):
        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=32,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=True,
            PER_BETA_ANNEAL_STEPS=10,
        )
        rng = np.random.default_rng(4)
        dev = _dev_buffer(cfg)
        assert dev.sample(4, current_train_step=0) is None  # not ready
        rows = _rows(12, rng)
        dev.add_dense(*rows[:4], policy_weight=rows[4])
        s = dev.sample(4, current_train_step=0)
        assert s is not None and "batch" not in s
        assert s["indices"].shape == (4,) and (s["indices"] < 12).all()
        assert s["weights"].shape == (4,) and (s["weights"] <= 1.0).all()
        # PER priority updates shift sampling mass (inherited machinery).
        dev.update_priorities(np.array([0]), np.array([100.0]))
        hits = sum(
            0 in dev.sample(4, current_train_step=1)["indices"]
            for _ in range(50)
        )
        assert hits > 25


def _block(lead, rng, share=1.0, bad=()):
    """One experience block with leading dims `lead`: `share` of its
    rows masked in; the flat rows listed in `bad` spoiled in turn by a
    NaN cell, a policy that sums to 3, an infinite return, a NaN in the
    other features, an infinite policy entry."""
    n = int(np.prod(lead))
    grid, other, policy, val, pw = _rows(n, rng)
    for k, i in enumerate(bad):
        if k % 5 == 0:
            grid[i, 0, 0, 0] = np.nan
        elif k % 5 == 1:
            policy[i] *= 3.0
        elif k % 5 == 2:
            val[i] = np.inf
        elif k % 5 == 3:
            other[i, -1] = np.nan
        else:
            policy[i, 0] = np.inf
    return {
        "grid": grid.reshape(*lead, *GRID_SHAPE),
        "other": other.reshape(*lead, OTHER_DIM),
        "policy": policy.reshape(*lead, ACTION_DIM),
        "ret": val.reshape(lead),
        "pw": pw.reshape(lead),
        "mask": (rng.random(lead) < share),
    }


def _random_ring(cap, rng):
    """A ring that already holds something in every slot, the row past
    the ring included."""
    grid, other, policy, val, pw = _rows(cap + 1, rng)
    return {
        "grid": grid.astype(np.int8),
        "other_features": other,
        "policy_target": policy,
        "value_target": val,
        "policy_weight": pw,
    }


def _scatter_oracle(storage, cursor, blocks, cap):
    """The ingest as it was before the window write, written plainly:
    every candidate row scattered by `.at[pos].set`, the rows that are
    not kept at the slot past the ring. Returns (ring, cursor, count,
    pos, keep, the flat rows)."""
    import jax.numpy as jnp

    def flat(block, f):
        lead = block["mask"].shape
        return block[f].reshape(-1, *block[f].shape[len(lead):])

    rows = {
        f: np.concatenate([flat(b, f) for b in blocks])
        for f in ("grid", "other", "policy", "ret", "pw")
    }
    mask = np.concatenate([b["mask"].reshape(-1) for b in blocks])
    with np.errstate(invalid="ignore"):
        valid = (
            mask
            & np.isfinite(rows["grid"]).all(axis=(1, 2, 3))
            & np.isfinite(rows["other"]).all(axis=1)
            & np.isfinite(rows["policy"]).all(axis=1)
            & np.isfinite(rows["ret"])
            & (np.abs(rows["policy"].sum(axis=1) - 1.0) < 1e-3)
        )
    offsets = np.cumsum(valid.astype(np.int32)) - 1
    count = int(valid.sum())
    keep = valid & (offsets >= count - cap)
    pos = np.where(keep, (cursor + offsets) % cap, cap)
    fields = {
        "grid": "grid", "other_features": "other", "policy_target": "policy",
        "value_target": "ret", "policy_weight": "pw",
    }
    with np.errstate(invalid="ignore"):
        ring = {
            name: np.asarray(
                jnp.asarray(storage[name])
                .at[pos]
                .set(jnp.asarray(rows[f].astype(storage[name].dtype)))
            )
            for name, f in fields.items()
        }
    return ring, (cursor + count) % cap, count, pos, keep, rows, valid


# (capacity, cursor, [(leading dims, share masked in, spoiled rows)...],
#  least rows of a window or None for the module's own)
WINDOW_CASES = {
    "no_valid_row": (16, 5, [((2, 3), 0.0, ()), ((2, 3, 2), 0.0, ())], 4),
    "all_valid": (64, 7, [((2, 4), 1.0, ()), ((2, 4, 3), 1.0, ())], 4),
    "valid_between_invalid": (
        64, 60, [((3, 4), 0.7, (0, 3, 4, 7, 10)),
                 ((3, 4, 2), 0.5, (1, 2, 5, 9, 20, 23))], 4,
    ),
    "wraps_the_rings_end": (32, 27, [((2, 4), 1.0, ()), ((2, 4, 2), 0.5, (3,))], 4),
    "ends_at_the_rings_end": (32, 24, [((8,), 1.0, ())], 4),
    "more_kept_rows_than_a_window": (
        64, 11, [((4,), 1.0, ()), ((29,), 1.0, (5, 6))], 4,
    ),
    "larger_than_the_ring": (8, 3, [((4,), 1.0, ()), ((25,), 0.9, (7,))], 4),
    "larger_than_the_ring_by_one": (8, 7, [((9,), 1.0, ())], 4),
    "ring_smaller_than_a_window": (6, 4, [((4, 4), 0.3, (2,))], 4),
    "one_row": (16, 15, [((1,), 1.0, ())], 4),
    "the_modules_own_width_one_window": (
        1500, 1400, [((2, 8), 0.5, (3,)), ((2, 8, 3), 0.2, ())], None,
    ),
    "the_modules_own_width_three_windows": (
        3000, 2900, [((8,), 1.0, ()), ((2600,), 1.0, (17, 1200))], None,
    ),
}


class TestWindowWrite:
    """The ingest's ring write (`ring_scatter` -> `write_windows`): the
    kept rows brought together by a gather and written as windows of
    the ring, against the scatter of every candidate row it replaced
    and against the host ring."""

    @pytest.fixture
    def case(self, request, monkeypatch):
        from alphatriangle_tpu.rl import device_buffer

        cap, cursor, spec, at_least = WINDOW_CASES[request.param]
        if at_least is not None:
            monkeypatch.setattr(
                device_buffer, "_WINDOW_ROWS_AT_LEAST", at_least
            )
        rng = np.random.default_rng(sorted(WINDOW_CASES).index(request.param))
        blocks = tuple(_block(*b[:1], rng, *b[1:]) for b in spec)
        return cap, cursor, blocks, rng

    @pytest.mark.parametrize("case", sorted(WINDOW_CASES), indirect=True)
    def test_ring_is_the_scatters_bit_for_bit(self, case):
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.rl.device_buffer import (
            ingest_window_rows,
            ingest_windows,
            ring_scatter,
            write_windows,
        )

        cap, cursor, blocks, rng = case
        storage = _random_ring(cap, rng)
        want, want_cursor, want_count, pos, keep, rows, _ = _scatter_oracle(
            storage, cursor, blocks, cap
        )
        ingest = jax.jit(ring_scatter, static_argnums=(3, 4))
        got, got_cursor, got_count, got_pos, got_keep = ingest(
            storage, jnp.int32(cursor), blocks, cap, True
        )
        assert int(got_count) == want_count
        assert int(got_cursor) == want_cursor
        for name in want:
            np.testing.assert_array_equal(
                np.asarray(got[name])[:cap], want[name][:cap], err_msg=name
            )
            # nothing writes the row past the ring any more
            np.testing.assert_array_equal(
                np.asarray(got[name])[cap], storage[name][cap], err_msg=name
            )
            assert got[name].dtype == storage[name].dtype
            assert got[name].shape == storage[name].shape
        # `with_positions`: today's slots and keep mask per candidate row
        np.testing.assert_array_equal(np.asarray(got_pos), pos)
        np.testing.assert_array_equal(np.asarray(got_keep), keep)
        # without positions: the same ring, cursor and count
        plain = ingest(storage, jnp.int32(cursor), blocks, cap, False)
        assert len(plain) == 3 and int(plain[2]) == want_count
        for name in want:
            np.testing.assert_array_equal(
                np.asarray(plain[0][name]), np.asarray(got[name])
            )
        # the windows the device's loop wrote are the host's count
        width = ingest_window_rows(blocks, cap)
        kept = min(want_count, cap)
        _, windows = jax.jit(write_windows, static_argnums=(4, 5))(
            storage,
            {f: np.nan_to_num(v) for f, v in rows.items()},
            keep,
            jnp.int32((cursor + want_count - kept) % cap),
            cap,
            width,
        )
        assert int(windows) == ingest_windows(want_count, cursor, cap, width)
        assert int(windows) >= -(-kept // width)

    @pytest.mark.parametrize("case", sorted(WINDOW_CASES), indirect=True)
    def test_ring_is_the_host_rings(self, case, tiny_train_config):
        """The same blocks through `DeviceReplayBuffer` and, the rows
        that pass, through the host `ExperienceBuffer`, both brought to
        the case's cursor first: same slots, same rows, same cursor."""
        cap, cursor, blocks, rng = case
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=cap, USE_PER=True,
                   PER_BETA_ANNEAL_STEPS=100)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        first = _rows(cursor, rng)
        if cursor:
            host.add_dense(*first[:4], policy_weight=first[4])
            dev.add_dense(*first[:4], policy_weight=first[4])
        *_, rows, valid = _scatter_oracle(
            _random_ring(cap, rng), cursor, blocks, cap
        )
        count, slots = dev._ingest_blocks(blocks)
        host_slots = host.add_dense(
            rows["grid"][valid], rows["other"][valid], rows["policy"][valid],
            rows["ret"][valid], policy_weight=rows["pw"][valid],
        )
        assert count == int(valid.sum())
        np.testing.assert_array_equal(slots, host_slots)
        assert dev._pos == host._pos and len(dev) == len(host)
        hs, ds = host.get_state(), dev.get_state()
        if len(host):
            for k in hs["storage"]:
                np.testing.assert_array_equal(
                    hs["storage"][k], ds["storage"][k], err_msg=k
                )
            np.testing.assert_allclose(hs["priorities"], ds["priorities"])

    def test_the_megasteps_call(self):
        """As `rl/megastep.py` calls it: inside a jitted program, the
        chunk's (T, B) and (T, B, n) blocks, `with_positions`, and the
        priorities of the fresh rows set from `pos` and `keep`."""
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.rl.device_buffer import ring_scatter

        cap, cursor = 40, 33
        rng = np.random.default_rng(11)
        blocks = (
            _block((4, 4), rng, 0.8, (1, 6)),
            _block((4, 4, 3), rng, 0.3, (0, 9, 30)),
        )
        storage = _random_ring(cap, rng)
        priorities = rng.random(cap + 1).astype(np.float32)

        @jax.jit
        def step(storage, priorities, cursor, mat, flush):
            new_storage, new_cursor, count, pos, keep = ring_scatter(
                storage, cursor, (mat, flush), cap, with_positions=True
            )
            priorities = priorities.at[pos].set(jnp.where(keep, 7.0, 0.0))
            return new_storage, priorities.at[cap].set(0.0), new_cursor, count

        got, got_priorities, got_cursor, got_count = step(
            storage, priorities, jnp.int32(cursor), *blocks
        )
        want, want_cursor, want_count, pos, keep, *_ = _scatter_oracle(
            storage, cursor, blocks, cap
        )
        want_priorities = priorities.copy()
        want_priorities[pos[keep]] = 7.0
        want_priorities[cap] = 0.0
        assert (int(got_cursor), int(got_count)) == (want_cursor, want_count)
        np.testing.assert_array_equal(np.asarray(got_priorities), want_priorities)
        for name in want:
            np.testing.assert_array_equal(
                np.asarray(got[name])[:cap], want[name][:cap], err_msg=name
            )

    def test_the_dp_sharded_ingest(self, tiny_train_config):
        """`ShardedDeviceReplayBuffer`'s per-shard ingest inside
        `shard_map`: each shard's lanes through the same write into the
        shard's own ring, twice, so that every shard wraps."""
        import jax

        from alphatriangle_tpu.config import MeshConfig
        from alphatriangle_tpu.rl.sharded_device_buffer import (
            ShardedDeviceReplayBuffer,
        )

        dp, cap_local = 4, 16
        mesh = MeshConfig(DP_SIZE=dp).build_mesh(jax.devices()[:dp])
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=dp * cap_local,
                   USE_PER=False, SELF_PLAY_BATCH_SIZE=2 * dp, BATCH_SIZE=dp)
        buf = ShardedDeviceReplayBuffer(
            cfg, grid_shape=GRID_SHAPE, other_dim=OTHER_DIM,
            action_dim=ACTION_DIM, mesh=mesh, dp_axis="dp",
        )
        rng = np.random.default_rng(12)
        want = {
            k: np.asarray(v).reshape(dp, cap_local + 1, *v.shape[1:]).copy()
            for k, v in jax.device_get(buf.storage).items()
        }
        cursors = np.zeros(dp, np.int64)
        lanes = 2  # a shard's lanes
        for bad in ((3, 17), (0, 40)):
            blocks = (
                _block((3, lanes * dp), rng, 0.9, bad[:1]),
                _block((3, lanes * dp, 2), rng, 0.4, bad[1:]),
            )
            total, _ = buf._ingest_blocks(blocks)
            counted = 0
            for k in range(dp):
                local = tuple(
                    {f: v[:, k * lanes:(k + 1) * lanes] for f, v in b.items()}
                    for b in blocks
                )
                ring, cursor, count, *_ = _scatter_oracle(
                    {f: v[k] for f, v in want.items()},
                    int(cursors[k]), local, cap_local,
                )
                for f in want:
                    want[f][k] = ring[f]
                cursors[k] = cursor
                counted += count
            assert total == counted
            np.testing.assert_array_equal(buf._cursors, cursors)
        assert (buf._sizes == cap_local).all()  # every shard wrapped
        got = jax.device_get(buf.storage)
        for f, v in want.items():
            np.testing.assert_array_equal(
                np.asarray(got[f]).reshape(v.shape)[:, :cap_local],
                v[:, :cap_local], err_msg=f,
            )


def _flagship_ingest_shapes():
    """`flagship-rollout`'s ring and one chunk's payload, as shapes."""
    import jax
    import jax.numpy as jnp

    from chipbench import manifest

    cell = manifest.cell("flagship-rollout")
    configs = manifest.program_configs(cell["config_file"])
    env, model, train = configs["env"], configs["model"], configs["train"]
    cap = train.BUFFER_CAPACITY
    grid = (model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS)
    other, actions = model.OTHER_NN_INPUT_FEATURES_DIM, env.action_dim
    shape = jax.ShapeDtypeStruct
    storage = {
        "grid": shape((cap + 1, *grid), jnp.int8),
        "other_features": shape((cap + 1, other), jnp.float32),
        "policy_target": shape((cap + 1, actions), jnp.float32),
        "value_target": shape((cap + 1,), jnp.float32),
        "policy_weight": shape((cap + 1,), jnp.float32),
    }

    def block(lead):
        return {
            "grid": shape((*lead, *grid), jnp.float32),
            "other": shape((*lead, other), jnp.float32),
            "policy": shape((*lead, actions), jnp.float32),
            "ret": shape(lead, jnp.float32),
            "pw": shape(lead, jnp.float32),
            "mask": shape(lead, jnp.bool_),
        }

    lead = (cell["traffic_file"]["chunk_moves"], train.SELF_PLAY_BATCH_SIZE)
    blocks = (block(lead), block((*lead, train.N_STEP_RETURNS)))
    return cap, storage, blocks


class TestIngestProgram:
    def test_no_scatter_of_the_candidates_and_the_ring_in_place(self):
        """The ingest's lowered program at `flagship-rollout`'s shapes
        (49,152 candidate rows, a 3,000,000-row ring; lowering allocates
        nothing): no scatter takes the candidates as its updates, the
        ring is written by `dynamic_update_slice`, and each of its five
        arrays is donated and aliased to the output that replaces it."""
        import re
        import types

        import jax

        cap, storage, blocks = _flagship_ingest_shapes()
        candidates = sum(int(np.prod(b["mask"].shape)) for b in blocks)
        assert (cap, candidates) == (3_000_000, 49_152)
        text = (
            jax.jit(
                DeviceReplayBuffer._ingest_impl,
                static_argnums=0,
                donate_argnums=1,
            )
            .lower(
                types.SimpleNamespace(capacity=cap),
                storage,
                jax.ShapeDtypeStruct((), np.int32),
                blocks,
            )
            .as_text()
        )
        scatters = [
            line for line in text.splitlines() if "stablehlo.scatter" in line
        ]
        assert not [s for s in scatters if f"tensor<{candidates}x" in s]
        assert text.count("stablehlo.dynamic_update_slice") == len(storage)
        main = next(
            line for line in text.splitlines() if "func.func public @main" in line
        )
        rings = re.findall(
            rf"tensor<{cap + 1}(?:x\d+)*x\w+> {{tf.aliasing_output = (\d) : i32}}",
            main,
        )
        assert rings == ["0", "1", "2", "3", "4"]

    def test_a_buffers_own_program_donates_its_ring(self, tiny_train_config):
        dev = _dev_buffer(_cfg(tiny_train_config, BUFFER_CAPACITY=16))
        block = {
            k: np.asarray(v)
            for k, v in _block((4,), np.random.default_rng(0)).items()
        }
        text = dev._ingest_jit.lower(
            dev.storage, np.int32(0), (block,)
        ).as_text()
        assert text.count("tf.aliasing_output") == len(dev.storage)
        assert "stablehlo.scatter" not in text

    def test_programs_that_take_the_ring_see_the_ring_they_saw(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        """The ring keeps its arrays' shapes and types through the new
        write, the row past the ring included, so a program that takes
        it as an argument (`learner_fused_from_ring`) lowers from a ring
        that has been written to the text it lowers to from a fresh one.
        (`tests/test_trainer.py::test_the_flagships_learner_programs_
        are_the_parents_text` holds that text to the digest taken two
        commits before this write, at `flagship-p3`'s widths.)"""
        import jax

        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=32,
                   MIN_BUFFER_SIZE_TO_TRAIN=8, FUSED_LEARNER_STEPS=2)
        shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        dev = DeviceReplayBuffer(
            cfg, grid_shape=shape,
            other_dim=tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM,
            action_dim=tiny_env_config.action_dim,
        )
        trainer = Trainer(
            NeuralNetwork(tiny_model_config, tiny_env_config, seed=3), cfg
        )

        def text():
            return (
                jax.jit(trainer._train_steps_from_impl)
                .lower(
                    trainer.state, dev.storage,
                    np.zeros((2, 4), np.int32), np.ones((2, 4), np.float32),
                )
                .as_text()
            )

        fresh = text()
        before = {k: (v.shape, v.dtype) for k, v in dev.storage.items()}
        rng = np.random.default_rng(6)
        n = 40  # more than the ring holds
        grid = rng.integers(-1, 2, size=(n, *shape)).astype(np.float32)
        other = rng.random(
            (n, tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM), dtype=np.float32
        )
        policy = rng.random((n, tiny_env_config.action_dim), dtype=np.float32)
        policy /= policy.sum(axis=1, keepdims=True)
        dev.add_dense(grid, other, policy, rng.normal(size=n).astype(np.float32))
        assert {k: (v.shape, v.dtype) for k, v in dev.storage.items()} == before
        assert all(v.shape[0] == cfg.BUFFER_CAPACITY + 1 for v in dev.storage.values())
        assert text() == fresh


# a ring array's trailing dims and dtype: the widths the cells' rings
# have (360 and 30 at the flagship, 756 at the trunk cells), one tile of
# sublanes, the ranks and both dtypes
READ_ARRAYS = {
    "f32-360": ((360,), np.float32),
    "f32-756": ((756,), np.float32),
    "f32-30": ((30,), np.float32),
    "f32-8": ((8,), np.float32),
    "s8-360": ((360,), np.int8),
    "f32-rank1": ((), np.float32),
    "s8-rank4": ((1, 8, 15), np.int8),
}
READ_IN_PLACE = {"f32-360", "f32-8", "s8-360"}


class TestReadRows:
    @pytest.mark.parametrize("idx_rank", (1, 2))
    @pytest.mark.parametrize("name", sorted(READ_ARRAYS))
    def test_rows_are_the_plain_gathers_bit_for_bit(self, name, idx_rank):
        """`read_rows` against `ring[idx]`, with a repeated index and
        the row past the ring (`cap`); the shape helper names the
        arrays the function reads through the view of sublane tiles,
        and no others."""
        import jax

        from alphatriangle_tpu.rl.device_buffer import read_rows, ring_read

        cap = 37
        rng = np.random.default_rng(sorted(READ_ARRAYS).index(name))
        trailing, dtype = READ_ARRAYS[name]
        ring = (rng.normal(size=(cap + 1, *trailing)) * 50).astype(dtype)
        idx = rng.integers(0, cap + 1, size=(3, 5) if idx_rank == 2 else (15,))
        idx.flat[:3] = cap, 4, 4
        idx = idx.astype(np.int32)
        storage = {name: ring}
        got = jax.jit(read_rows)(storage, idx)[name]
        assert got.dtype == ring.dtype and got.shape == idx.shape + trailing
        np.testing.assert_array_equal(np.asarray(got), ring[idx])
        in_place = name in READ_IN_PLACE
        assert ring_read(storage) == {
            "in_place": [name] if in_place else [],
            "as_is": [] if in_place else [name],
        }
        reshapes = [
            eqn.params["new_sizes"]
            for eqn in jax.make_jaxpr(read_rows)(storage, idx).eqns
            if eqn.primitive.name == "reshape"
            and eqn.invars[0].aval.shape == ring.shape
        ]
        assert reshapes == ([(cap + 1, ring.shape[1] // 8, 8)] if in_place else [])

    def test_every_array_of_a_ring_in_its_place(self, tiny_train_config):
        """A buffer's own storage at the flagship's widths: one call
        reads all five arrays, and only `policy_target` is viewed."""
        from alphatriangle_tpu.rl.device_buffer import read_rows, ring_read

        dev = DeviceReplayBuffer(
            _cfg(tiny_train_config, BUFFER_CAPACITY=16),
            grid_shape=(1, 8, 15), other_dim=30, action_dim=360,
        )
        rng = np.random.default_rng(3)
        storage = {
            k: rng.integers(-1, 2, size=v.shape).astype(v.dtype)
            for k, v in dev.storage.items()
        }
        assert ring_read(dev.storage) == ring_read(storage) == {
            "in_place": ["policy_target"],
            "as_is": ["grid", "other_features", "value_target", "policy_weight"],
        }
        idx = np.array([[16, 0, 3], [3, 15, 16]], np.int32)
        rows = read_rows(storage, idx)
        assert list(rows) == list(storage)
        for k, v in storage.items():
            np.testing.assert_array_equal(np.asarray(rows[k]), v[idx], err_msg=k)


    def test_a_ring_with_nothing_to_view_is_gathered_as_before(
        self, tiny_train_config
    ):
        """At the trunk cells' widths (756 actions, 30 features, a
        12 x 21 grid) the rule views no array, and `read_rows` traces to
        the plain gathers' own program: `glm-flash-learner`'s step is
        the one it was."""
        import jax

        from alphatriangle_tpu.rl.device_buffer import read_rows, ring_read

        dev = DeviceReplayBuffer(
            _cfg(tiny_train_config, BUFFER_CAPACITY=16),
            grid_shape=(1, 12, 21), other_dim=30, action_dim=756,
        )
        assert ring_read(dev.storage)["in_place"] == []
        idx = np.zeros((1, 4), np.int32)
        plain = jax.make_jaxpr(
            lambda storage, idx: {k: v[idx] for k, v in storage.items()}
        )(dev.storage, idx)
        assert str(jax.make_jaxpr(read_rows)(dev.storage, idx)) == str(plain)


class TestTrainEquivalence:
    def test_train_steps_from_matches_host_path(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        """K fused device-gathered steps == K fused host-staged steps
        on the same rows (identical final params + per-step outputs)."""
        import jax

        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False,
            FUSED_LEARNER_STEPS=3,
        )
        rng = np.random.default_rng(5)
        grid_shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        other_dim = tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM
        action_dim = tiny_env_config.action_dim
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=grid_shape,
            other_dim=other_dim,
            action_dim=action_dim,
        )
        n = 32
        grid = rng.integers(-1, 2, size=(n, *grid_shape)).astype(np.float32)
        other = rng.random((n, other_dim), dtype=np.float32)
        policy = rng.random((n, action_dim), dtype=np.float32) + 0.01
        policy /= policy.sum(axis=1, keepdims=True)
        val = rng.normal(size=n).astype(np.float32)
        pw = (rng.random(n) > 0.5).astype(np.float32)
        dev.add_dense(grid, other, policy, val, policy_weight=pw)

        samples = [dev.sample(cfg.BATCH_SIZE) for _ in range(3)]
        host_batches = []
        for s in samples:
            i = s["indices"]
            host_batches.append(
                {
                    "grid": grid[i].astype(np.int8).astype(np.float32),
                    "other_features": other[i],
                    "policy_target": policy[i],
                    "value_target": val[i],
                    "policy_weight": pw[i],
                    "weights": s["weights"],
                }
            )

        net_a = NeuralNetwork(tiny_model_config, tiny_env_config, seed=7)
        net_b = NeuralNetwork(tiny_model_config, tiny_env_config, seed=7)
        tr_a = Trainer(net_a, cfg)
        tr_b = Trainer(net_b, cfg)
        outs_host = tr_a.train_steps(host_batches)
        outs_dev = tr_b.train_steps_from(dev, samples)
        assert len(outs_host) == len(outs_dev) == 3
        for (m_h, td_h), (m_d, td_d) in zip(outs_host, outs_dev):
            for key in m_h:
                np.testing.assert_allclose(
                    m_h[key], m_d[key], rtol=1e-5, err_msg=key
                )
            np.testing.assert_allclose(td_h, td_d, rtol=1e-5)
        pa = jax.device_get(tr_a.state.params)
        pb = jax.device_get(tr_b.state.params)
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5), pa, pb
        )
        assert tr_a.global_step == tr_b.global_step == 3

    def test_pipelined_begin_finish(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False,
        )
        rng = np.random.default_rng(6)
        grid_shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=grid_shape,
            other_dim=tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM,
            action_dim=tiny_env_config.action_dim,
        )
        n = 16
        grid = rng.integers(-1, 2, size=(n, *grid_shape)).astype(np.float32)
        other = rng.random(
            (n, tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM),
            dtype=np.float32,
        )
        policy = rng.random((n, tiny_env_config.action_dim), dtype=np.float32)
        policy /= policy.sum(axis=1, keepdims=True)
        dev.add_dense(grid, other, policy, np.zeros(n, np.float32))
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=8)
        tr = Trainer(net, cfg)
        # Two groups in flight (K=2 then K=1), fetched oldest-first.
        h1 = tr.train_steps_from_begin(dev, [dev.sample(4), dev.sample(4)])
        h2 = tr.train_steps_from_begin(dev, [dev.sample(4)])
        assert tr.train_steps_from_begin(dev, []) is None
        outs1 = tr.train_steps_finish(h1)
        outs2 = tr.train_steps_finish(h2)
        assert len(outs1) == 2 and len(outs2) == 1
        assert outs1[0][1].shape == (4,)  # per-step TD rows
        assert tr.global_step == 3
        want = [float(tr.schedule(i)) for i in range(1, 6)]
        lrs = [m["learning_rate"] for m, _ in outs1 + outs2]
        assert lrs == pytest.approx(want[:3], rel=1e-6)
        # A from-ring group labels its steps without the optax schedule
        # (tests/test_trainer.py holds the other learner paths to that).
        tr.schedule = None
        outs3 = tr.train_steps_from(dev, [dev.sample(4), dev.sample(4)])
        lrs = [m["learning_rate"] for m, _ in outs3]
        assert lrs == pytest.approx(want[3:], rel=1e-6)


class TestSelfPlayIntegration:
    def test_play_chunk_device_matches_host_harvest(
        self,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
    ):
        """Same seed, two engines: the device payload ingested into the
        ring equals the host harvest's rows, and stats agree."""
        from alphatriangle_tpu.env.engine import TriangleEnv
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=512, USE_PER=False)
        env = TriangleEnv(tiny_env_config)
        extractor = get_feature_extractor(env, tiny_model_config)
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=3)
        mk = lambda: SelfPlayEngine(  # noqa: E731
            env, extractor, net, tiny_mcts_config, cfg, seed=11
        )
        host_eng, dev_eng = mk(), mk()
        result = host_eng.play_moves(8)
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=(
                tiny_model_config.GRID_INPUT_CHANNELS,
                tiny_env_config.ROWS,
                tiny_env_config.COLS,
            ),
            other_dim=extractor.other_dim,
            action_dim=tiny_env_config.action_dim,
        )
        stats, payload = dev_eng.play_moves_device(8)
        added = dev.ingest_payload(payload)
        assert added == result.num_experiences == len(dev)
        assert stats.num_episodes == result.num_episodes
        assert stats.episode_scores == result.episode_scores
        assert stats.total_simulations == result.total_simulations
        assert stats.num_experiences == 0  # stats-only harvest
        state = dev.get_state()
        np.testing.assert_array_equal(
            state["storage"]["grid"], result.grid.astype(np.int8)
        )
        np.testing.assert_allclose(
            state["storage"]["policy_target"], result.policy_target,
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            state["storage"]["value_target"], result.value_target, rtol=1e-6
        )
        np.testing.assert_array_equal(
            state["storage"]["policy_weight"], result.policy_weight
        )


class TestSnapshotRoundTrip:
    @pytest.mark.parametrize("direction", ["dev_to_host", "host_to_dev"])
    def test_round_trip(self, tiny_train_config, direction):
        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=16,
            USE_PER=True,
            PER_BETA_ANNEAL_STEPS=50,
        )
        rng = np.random.default_rng(9)
        src: ExperienceBuffer = (
            _dev_buffer(cfg) if direction == "dev_to_host"
            else ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        )
        rows = _rows(20, rng)  # wraps the 16-slot ring
        src.add_dense(*rows[:4], policy_weight=rows[4])
        src.update_priorities(np.arange(4), np.array([1.0, 2.0, 3.0, 4.0]))
        snap = src.get_state()
        dst: ExperienceBuffer = (
            ExperienceBuffer(cfg, action_dim=ACTION_DIM)
            if direction == "dev_to_host"
            else _dev_buffer(cfg)
        )
        dst.set_state(snap)
        assert len(dst) == len(src) == 16
        a, b = src.get_state(), dst.get_state()
        # set_state re-orders slots chronologically; compare as sets of
        # rows via lexicographic sort on the value column.
        oa, ob = np.argsort(a["storage"]["value_target"]), np.argsort(
            b["storage"]["value_target"]
        )
        for k in a["storage"]:
            np.testing.assert_allclose(
                a["storage"][k][oa].astype(np.float32),
                b["storage"][k][ob].astype(np.float32),
                err_msg=k,
            )
        s = dst.sample(4, current_train_step=0)
        assert s is not None


class TestLoopIntegration:
    @pytest.mark.parametrize("async_mode", [False, True])
    def test_training_loop_device_replay(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
        async_mode,
    ):
        from alphatriangle_tpu.config import MeshConfig, PersistenceConfig
        from alphatriangle_tpu.training.loop import LoopStatus, TrainingLoop
        from alphatriangle_tpu.training.setup import setup_training_components

        cfg = _cfg(
            tiny_train_config,
            DEVICE_REPLAY="on",
            ASYNC_ROLLOUTS=async_mode,
            ASYNC_CHUNK_SECONDS=None,
            FUSED_LEARNER_STEPS=2,
            MAX_TRAINING_STEPS=6,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            BUFFER_CAPACITY=256,
            CHECKPOINT_SAVE_FREQ_STEPS=4,
            RUN_NAME=f"pytest_devreplay_{async_mode}",
        )
        comps = setup_training_components(
            train_config=cfg,
            env_config=tiny_env_config,
            model_config=tiny_model_config,
            mcts_config=tiny_mcts_config,
            # The device ring lives on ONE chip; pin a 1-device mesh
            # (the test harness exposes 8 virtual CPU devices).
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=PersistenceConfig(
                ROOT_DATA_DIR=str(tmp_path), RUN_NAME=cfg.RUN_NAME
            ),
            use_tensorboard=False,
        )
        assert getattr(comps.buffer, "is_device", False)
        loop = TrainingLoop(comps)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 6
        assert loop.experiences_added > 0
        ckpts = list(tmp_path.rglob("step_*"))
        assert ckpts, "no checkpoint written"

    def test_training_loop_trains_a_routed_trunk_in_blocks(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
    ):
        """The loop's device-replay learner with a routed latent stack
        as the trunk: the same entry points, ring and sampler; a step in
        blocks of 4 boards under recomputation, the routers' counters
        off the group's fetch, the biases moved by the rule."""
        from alphatriangle_tpu.config import (
            MeshConfig, PersistenceConfig, TrunkConfig,
        )
        from alphatriangle_tpu.training.loop import LoopStatus, TrainingLoop
        from alphatriangle_tpu.training.setup import setup_training_components

        trunk = TrunkConfig(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, layer_types=["latent_attention"] * 2,
            mlp_layer_types=["dense", "sparse"], experts_held=(0, 2),
            kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, norm_position="pre", rope_layers="latent",
            router_bias=True, latent_gate=False, learner_block_boards=4,
            router_bias_rate=0.001,
        )
        model = tiny_model_config.model_copy(update={"TRUNK": trunk, "REMAT": True})
        cfg = _cfg(
            tiny_train_config,
            DEVICE_REPLAY="on",
            FUSED_LEARNER_STEPS=2,
            BATCH_SIZE=8,
            MAX_TRAINING_STEPS=4,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            BUFFER_CAPACITY=256,
            CHECKPOINT_SAVE_FREQ_STEPS=4,
            RUN_NAME="pytest_devreplay_trunk",
        )
        comps = setup_training_components(
            train_config=cfg,
            env_config=tiny_env_config,
            model_config=model,
            mcts_config=tiny_mcts_config,
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=PersistenceConfig(
                ROOT_DATA_DIR=str(tmp_path), RUN_NAME=cfg.RUN_NAME
            ),
            use_tensorboard=False,
        )
        before = np.asarray(comps.net.variables["params"]["DecoderTrunk_0"]["l1_router_bias"])
        loop = TrainingLoop(comps)
        assert loop.run() == LoopStatus.COMPLETED and loop.global_step == 4
        counted = comps.trainer.last_counters
        assert counted["expert_loads"].shape == (2, 1, 4)
        tokens = 8 * tiny_env_config.ROWS * tiny_env_config.COLS
        assert counted["routed"] == 2 * tokens * 2
        after = np.asarray(
            comps.trainer.state.params["DecoderTrunk_0"]["l1_router_bias"]
        )
        moved = np.abs(after - before)
        assert moved.max() <= 4 * 0.001 + 1e-7 and moved.max() > 0
