"""Custom-op tests: every ops/ lowering pair must be numerically
pinned against the other (the Pallas kernels run in interpret mode on
CPU) — exact for the gather, backup and PER index-select ops, and
tolerance + fixed-seed arena equality for the bf16 inference path —
and full searches must be invariant to the backend choice."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.mcts import BatchedMCTS
from alphatriangle_tpu.ops import (
    backup_update,
    gather_rows,
    per_sample,
    subtree_promote,
)
from alphatriangle_tpu.ops.encoder_layer import (
    block_boards,
    encoder_layer,
    layer_path,
    partitioned,
)


class TestGatherRows:
    @pytest.mark.parametrize("mode", ["einsum", "pallas", "take"])
    def test_matches_numpy(self, mode):
        rng = np.random.default_rng(0)
        stats = rng.random((6, 17, 40)).astype(np.float32)
        idx = rng.integers(0, 17, (6, 5)).astype(np.int32)
        out = np.asarray(gather_rows(stats, idx, mode))
        expect = np.stack([stats[b][idx[b]] for b in range(6)])
        np.testing.assert_array_equal(out, expect)

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="unknown gather"):
            gather_rows(np.zeros((1, 2, 3)), np.zeros((1, 1), np.int32), "x")

    def test_jittable_under_vmapped_search_shapes(self):
        # Negative-free int32 indices with K not a multiple of 128
        # (flagship 6A = 2160 is; exercise the ragged case too).
        rng = np.random.default_rng(1)
        stats = rng.random((3, 9, 130)).astype(np.float32)
        idx = rng.integers(0, 9, (3, 4)).astype(np.int32)
        for mode in ("einsum", "pallas", "take"):
            out = jax.jit(lambda s, i, m=mode: gather_rows(s, i, m))(
                stats, idx
            )
            np.testing.assert_array_equal(
                np.asarray(out),
                np.stack([stats[b][idx[b]] for b in range(3)]),
            )


def _max_gap(a, b) -> float:
    return float(
        jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
    )


def _encoder_layer(dtype, activation: str = "ReLU", seed: int = 0):
    """A flagship-width layer (D 128, 4 heads, FC 256) as Flax makes it,
    and its variables moved off their initial values: biases start at 0
    and scales at 1, where a kernel that dropped them would still agree."""
    from alphatriangle_tpu.nn.model import _ACTIVATIONS, TransformerEncoderLayer

    act = _ACTIVATIONS[activation]
    layer = TransformerEncoderLayer(128, 4, 256, act, dtype)
    variables = layer.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8, 128), dtype), False
    )
    leaves, tree = jax.tree_util.tree_flatten(variables)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(leaves))
    moved = [
        leaf + 0.05 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)
    ]
    return layer, jax.tree_util.tree_unflatten(tree, moved), act


class TestEncoderLayer:
    """The fused layer (interpreted) against `TransformerEncoderLayer`'s
    Flax modules on the same variables, and the choice between the two."""

    def _three_answers(self, shape, dtype, activation="ReLU"):
        """(kernel, Flax in `dtype`, Flax in float32) on one input."""
        layer, variables, act = _encoder_layer(dtype, activation)
        x = jax.random.normal(jax.random.PRNGKey(7), (*shape, 128), dtype)
        got = encoder_layer(
            x, variables["params"], heads=4, act=act, interpret=True
        )
        assert got.shape == x.shape and got.dtype == dtype
        exact = layer.clone(dtype=jnp.float32).apply(
            variables, x.astype(jnp.float32), False
        )
        return got, layer.apply(variables, x, False), exact

    # The flagship's boards, preset 5's 252 tokens, and a batch the
    # block of 32 boards does not divide (a padded last step).
    @pytest.mark.parametrize("shape", [(2, 120), (2, 252), (35, 24)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_kernel_matches_flax(self, shape, dtype):
        assert block_boards(35, 24, 128, 256, 2) == 32  # 35 = 32 + 3
        got, flax, exact = self._three_answers(shape, dtype)
        if dtype == jnp.float32:
            assert _max_gap(got, flax) < 1e-5
            return
        # The kernel rounds where Flax rounds, but for the residual
        # stream, float32 inside the layer: it must not lie further from
        # the float32 answer than Flax's bfloat16 path, and the two lie
        # within their rounding (outputs reach 4-8: 2^-6 a step).
        assert _max_gap(got, exact) <= max(0.02, _max_gap(flax, exact))
        assert _max_gap(got, flax) < 0.15

    @pytest.mark.parametrize(
        "activation", ["ReLU", "GELU", "SiLU", "Tanh", "Sigmoid"]
    )
    def test_every_activation_of_the_config(self, activation):
        from alphatriangle_tpu.nn.model import _ACTIVATIONS

        assert set(_ACTIVATIONS) == {"ReLU", "GELU", "SiLU", "Tanh", "Sigmoid"}
        got, flax, _ = self._three_answers((3, 24), jnp.float32, activation)
        assert _max_gap(got, flax) < 1e-5
        got, flax, exact = self._three_answers((3, 24), jnp.bfloat16, activation)
        assert _max_gap(got, exact) <= max(0.02, _max_gap(flax, exact))

    def test_the_layer_takes_its_own_variables(self, monkeypatch):
        """Through the module: `fused=True` hands the kernel the
        variables the Flax path declared."""
        import functools

        from alphatriangle_tpu.nn import model as nn_model

        layer, variables, _ = _encoder_layer(jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 128))
        assert variables["params"].keys() == {
            "LayerNorm_0", "MultiHeadDotProductAttention_0", "LayerNorm_1",
            "Dense_0", "Dense_1",
        }
        monkeypatch.setattr(
            nn_model,
            "encoder_layer",
            functools.partial(nn_model.encoder_layer, interpret=True),
        )
        got = layer.clone(fused=True).apply(variables, x, False)
        assert _max_gap(got, layer.apply(variables, x, False)) < 1e-5

    FUSED = dict(
        initializing=False, train=False, handed_in=False, masked=False,
        partitioned=False, backend="tpu", dtype=jnp.bfloat16, seq=120,
        heads=4, head_dim=32, mlp_dim=256,
    )

    @pytest.mark.parametrize(
        "change,path",
        [
            ({}, "fused"),
            ({"dtype": jnp.float32}, "fused"),
            ({"seq": 252}, "fused"),
            ({"mlp_dim": 1024}, "fused"),
            ({"backend": "cpu"}, "flax"),
            ({"backend": "gpu"}, "flax"),
            ({"initializing": True}, "flax"),
            ({"train": True}, "flax"),
            ({"masked": True}, "flax"),
            ({"handed_in": True}, "flax"),
            ({"partitioned": True}, "flax"),
            ({"dtype": jnp.float16}, "flax"),
            ({"heads": 2, "head_dim": 16}, "flax"),  # 32 lanes of 128
            ({"mlp_dim": 192}, "flax"),  # a hidden of one and a half rows
            ({"seq": 8192}, "flax"),  # one board's scores: 268 MB
        ],
    )
    def test_path_is_chosen_by_what_the_call_observes(self, change, path):
        assert layer_path(**{**self.FUSED, **change}) == path

    @pytest.mark.parametrize(
        "seq,itemsize,boards",
        [
            (120, 2, 32), (120, 4, 32), (252, 2, 8), (256, 2, 19), (256, 4, 14),
            (2000, 2, 0),
        ],
    )
    def test_block_plan_follows_the_board(self, seq, itemsize, boards):
        """Boards a grid step: 32 at the flagship's 120 tokens, as many
        as the plan holds at 256, 8 at preset 5's 252 (not whole sublane
        tiles), none where one board's values pass the plan."""
        assert block_boards(8192, seq, 128, 256, itemsize) == boards
        assert block_boards(5, seq, 128, 256, itemsize) == min(boards, 5)

    @pytest.mark.parametrize(
        "placed,manual,want",
        [
            (None, (), False),  # a program of one device
            ((1, 1), (), False),  # a mesh of one device
            ((4, 2), (), True),  # split by the compiler
            ((4, 2), ("dp",), True),  # some axis still the compiler's
            ((4, 2), ("dp", "tp"), False),  # all under a shard_map
        ],
    )
    def test_partitioned_reads_the_mesh_of_the_trace(self, placed, manual, want):
        """What the choice observes of a mesh: a value derived from
        operands placed on one (here the weight alone) carries it."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        seen = []

        def program(x, w):
            y = x @ w
            seen.append(partitioned(jax.lax.scan(lambda c, _: (c, None), y, None, 2)[0]))
            return y

        x, w = jnp.ones((8, 4)), jnp.ones((4, 4))
        if placed is not None:
            n = placed[0] * placed[1]
            mesh = Mesh(np.array(jax.devices()[:n]).reshape(placed), ("dp", "tp"))
            w = jax.device_put(w, NamedSharding(mesh, PartitionSpec()))
            if manual:
                program = jax.shard_map(
                    program, mesh=mesh, in_specs=PartitionSpec(),
                    out_specs=PartitionSpec(), axis_names=set(manual),
                    check_vma=False,
                )
        jax.eval_shape(program, x, w)
        assert seen == [want]

    def test_a_board_that_does_not_fit_is_refused(self):
        _, variables, act = _encoder_layer(jnp.bfloat16)
        x = jax.ShapeDtypeStruct((1, 8192, 128), jnp.bfloat16)
        assert block_boards(1, 8192, 128, 256, 2) == 0
        with pytest.raises(ValueError, match="does not fit"):
            jax.eval_shape(
                lambda x, p: encoder_layer(x, p, heads=4, act=act),
                x, variables["params"],
            )


class TestPerSample:
    """Stratified PER draw: the Pallas compare-count and XLA
    searchsorted lowerings share one prefix-sum, so index selection is
    bit-identical by construction."""

    # cap off/on the kernel tile boundary, below one tile, K=1.
    @pytest.mark.parametrize("cap,k,b", [(37, 2, 8), (512, 1, 16), (700, 3, 32)])
    def test_pallas_matches_xla_exactly(self, cap, k, b):
        key = jax.random.PRNGKey(3)
        prios = jax.random.uniform(jax.random.PRNGKey(7), (cap + 1,))
        prios = prios.at[cap].set(0.0)  # trash slot
        idx_x, probs_x = per_sample(prios, cap, k, b, key, mode="xla")
        idx_p, probs_p = per_sample(prios, cap, k, b, key, mode="pallas")
        np.testing.assert_array_equal(np.asarray(idx_x), np.asarray(idx_p))
        np.testing.assert_array_equal(
            np.asarray(probs_x), np.asarray(probs_p)
        )
        assert idx_p.dtype == jnp.int32 and probs_p.dtype == jnp.float32

    @pytest.mark.parametrize("mode", ["xla", "pallas"])
    def test_draw_is_stratified_proportional(self, mode):
        """Each selected slot must bound its stratum draw:
        cum[idx-1] <= u < cum[idx] (the SumTree descent invariant)."""
        cap, k, b = 133, 2, 16
        prios = jax.random.uniform(jax.random.PRNGKey(9), (cap,))
        cum = np.cumsum(np.asarray(prios))
        key = jax.random.PRNGKey(11)
        idx, _ = per_sample(prios, cap, k, b, key, mode=mode)
        # Reconstruct the shared stratum draws exactly as per_sample.
        u = np.asarray(
            (
                jnp.arange(b, dtype=jnp.float32)[None, :]
                + jax.random.uniform(key, (k, b))
            )
            / b
            * jnp.cumsum(prios[:cap])[-1]
        )
        idx = np.asarray(idx)
        assert (u[idx > 0] >= cum[idx[idx > 0] - 1]).all()
        assert (u[idx < cap - 1] <= cum[idx[idx < cap - 1]]).all()

    @pytest.mark.parametrize("mode", ["xla", "pallas"])
    def test_zero_priority_never_selected(self, mode):
        """Empty/trash slots have empty cumsum segments."""
        cap = 64
        prios = jnp.zeros((cap,)).at[jnp.array([3, 17, 40])].set(1.0)
        idx, probs = per_sample(
            prios, cap, 4, 8, jax.random.PRNGKey(13), mode=mode
        )
        assert set(np.asarray(idx).ravel()) <= {3, 17, 40}
        assert (np.asarray(probs) > 0).all()

    def test_unknown_mode_raises(self):
        with pytest.raises(ValueError, match="unknown PER sample"):
            per_sample(
                jnp.ones((8,)), 8, 1, 4, jax.random.PRNGKey(0), mode="x"
            )


def _backup_operands(seed=2, batch=3, n=9, a=12, w=4, depth=5):
    """Random edge planes + a random (not necessarily consistent)
    descent record, with duplicate (node, action) hits across members
    and levels so update-order semantics are actually exercised."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 12)
    return dict(
        e_visits=jax.random.uniform(ks[0], (batch, n, a)),
        e_value=jax.random.normal(ks[1], (batch, n, a)),
        children=jnp.full((batch, n, a), -1.0).at[:, 0, :3].set(1.0),
        e_reward=jax.random.normal(ks[2], (batch, n, a)),
        parents=jax.random.randint(ks[3], (batch, w), 0, n),
        actions=jax.random.randint(ks[4], (batch, w), 0, a),
        new_child=jnp.where(
            jax.random.bernoulli(ks[5], 0.5, (batch, w)),
            jax.random.randint(ks[6], (batch, w), 1, n).astype(jnp.float32),
            -1.0,
        ),
        rewards=jax.random.normal(ks[7], (batch, w)),
        rec_node=jax.random.randint(ks[8], (batch, w, depth), -1, 3),
        rec_action=jax.random.randint(ks[9], (batch, w, depth), -1, 4),
        rec_active=jax.random.bernoulli(ks[10], 0.7, (batch, w, depth)),
        returns=jax.random.normal(ks[11], (batch, w, depth)),
    )


class TestBackupUpdate:
    def test_pallas_matches_xla_exactly(self):
        ops = _backup_operands()
        outs_x = backup_update(*ops.values(), mode="xla")
        outs_p = backup_update(*ops.values(), mode="pallas")
        for got, want in zip(outs_p, outs_x):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_xla_matches_numpy_reference(self):
        """The op must reproduce the scatter math `_wave` originally
        spelled, computed here as a sequential numpy loop."""
        ops = _backup_operands(seed=5)
        ev, eq, ch, er = (
            np.asarray(ops[k], np.float64)
            for k in ("e_visits", "e_value", "children", "e_reward")
        )
        parents, actions = np.asarray(ops["parents"]), np.asarray(ops["actions"])
        new_child = np.asarray(ops["new_child"])
        rewards = np.asarray(ops["rewards"])
        rec_node, rec_action = np.asarray(ops["rec_node"]), np.asarray(ops["rec_action"])
        rec_active = np.asarray(ops["rec_active"])
        returns = np.asarray(ops["returns"])
        batch, w = parents.shape
        depth = rec_node.shape[-1]
        for bi in range(batch):
            for j in range(w):
                p, ac = parents[bi, j], actions[bi, j]
                ch[bi, p, ac] = max(ch[bi, p, ac], new_child[bi, j])
                er[bi, p, ac] = rewards[bi, j]
            for lvl in range(depth):
                for j in range(w):
                    nd = max(rec_node[bi, j, lvl], 0)
                    ac = max(rec_action[bi, j, lvl], 0)
                    if rec_active[bi, j, lvl]:
                        ev[bi, nd, ac] += 1.0
                        eq[bi, nd, ac] += returns[bi, j, lvl]
        got = backup_update(*ops.values(), mode="xla")
        for g, want in zip(got, (ev, eq, ch, er)):
            np.testing.assert_allclose(
                np.asarray(g), want.astype(np.float32), atol=1e-5
            )

    def test_unknown_mode_raises(self):
        ops = _backup_operands()
        with pytest.raises(ValueError, match="unknown backup"):
            backup_update(*ops.values(), mode="x")


def _promote_operands(seed=0, batch=3, nodes=8, actions=3):
    """Random forest planes with hand-known topology on lane 0/1 plus a
    random-ish lane: children form a proper forest (each node at most
    one parent, ids increasing away from the root) so the scatter-min
    BFS in `_promotion_plan` and a literal traversal must agree."""
    rng = np.random.default_rng(seed)
    ch = np.full((batch, nodes, actions), -1.0, np.float32)
    # lane 0: 0 -> {1, 2}, 1 -> {3}, 2 -> {4}; action 0 promotes node 1.
    ch[0, 0, 0], ch[0, 0, 1] = 1.0, 2.0
    ch[0, 1, 0] = 3.0
    ch[0, 2, 1] = 4.0
    # lane 1: chosen action has no child (invalid promotion).
    ch[1, 0, 0] = 5.0
    # lane 2: a deeper chain 0 -> 1 -> 2 -> 3 under action 0.
    ch[2, 0, 0] = 1.0
    ch[2, 1, 1] = 2.0
    ch[2, 2, 0] = 3.0
    planes = tuple(
        rng.random((batch, nodes, actions)).astype(np.float32)
        for _ in range(3)
    ) + (ch,) + tuple(
        rng.random((batch, nodes, actions)).astype(np.float32)
        for _ in range(2)
    )
    terminal = rng.random((batch, nodes)) < 0.3
    acts = np.array([0, 1, 0], np.int32)
    return planes, terminal, acts


def _eager_promote(planes, terminal, actions, max_retained):
    """Literal-BFS reference for `subtree_promote` (mirrors the
    reuse-smoke reference): traverse from the chosen child, order
    (depth, node id), truncate at the budget, remap children, zero
    freed rows, broadcast the chosen child over freed state_index."""
    from collections import deque

    ev, eq, er, ch, pr, va = [np.asarray(p, np.float32) for p in planes]
    term = np.asarray(terminal, bool)
    b_n, n, a_dim = ev.shape
    outs = [np.zeros_like(p) for p in (ev, eq, er, ch, pr, va)]
    outs[3][:] = -1.0
    term_out = np.zeros_like(term)
    state_index = np.zeros((b_n, n), np.int32)
    promo_valid = np.zeros(b_n, bool)
    retained = np.zeros(b_n, np.int32)
    for b in range(b_n):
        c0 = int(ch[b, 0, actions[b]])
        if c0 < 0:
            continue
        promo_valid[b] = True
        depth = {c0: 0}
        dq = deque([c0])
        while dq:
            u = dq.popleft()
            for act in range(a_dim):
                v = int(ch[b, u, act])
                if v >= 0 and v not in depth:
                    depth[v] = depth[u] + 1
                    dq.append(v)
        order = sorted(depth, key=lambda u: (depth[u], u))
        rank = {u: r for r, u in enumerate(order)}
        ret = min(len(order), max_retained)
        retained[b] = ret
        for r, u in enumerate(order[:ret]):
            for i, plane in enumerate((ev, eq, er, None, pr, va)):
                if plane is not None:
                    outs[i][b, r] = plane[b, u]
            for act in range(a_dim):
                v = int(ch[b, u, act])
                kept = v >= 0 and v in rank and rank[v] < max_retained
                outs[3][b, r, act] = float(rank[v]) if kept else -1.0
            term_out[b, r] = term[b, u]
        state_index[b, :ret] = order[:ret]
        state_index[b, ret:] = c0
    return outs, term_out, state_index, promo_valid, retained


class TestSubtreePromote:
    """Root promotion for subtree reuse (docs/KERNELS.md): both
    lowerings against a literal-BFS numpy reference, including budget
    truncation and the invalid-promotion lane."""

    @pytest.mark.parametrize("mode", ["xla", "pallas"])
    @pytest.mark.parametrize("max_retained", [8, 3])
    def test_matches_eager_reference(self, mode, max_retained):
        planes, terminal, acts = _promote_operands()
        ref_planes, ref_term, ref_sidx, ref_pv, ref_ret = _eager_promote(
            planes, terminal, acts, max_retained
        )
        got = subtree_promote(
            *[jnp.asarray(p) for p in planes],
            jnp.asarray(terminal),
            jnp.asarray(acts),
            max_retained=max_retained,
            bfs_rounds=4,
            mode=mode,
        )
        refs = list(ref_planes) + [ref_term, ref_sidx, ref_pv, ref_ret]
        for g, want in zip(got, refs):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))

    def test_pallas_matches_xla_exactly(self):
        planes, terminal, acts = _promote_operands(seed=9)
        kw = dict(max_retained=5, bfs_rounds=4)
        out_x = subtree_promote(
            *[jnp.asarray(p) for p in planes],
            jnp.asarray(terminal), jnp.asarray(acts), mode="xla", **kw
        )
        out_p = subtree_promote(
            *[jnp.asarray(p) for p in planes],
            jnp.asarray(terminal), jnp.asarray(acts), mode="pallas", **kw
        )
        for g, want in zip(out_p, out_x):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want))

    def test_unknown_mode_raises(self):
        planes, terminal, acts = _promote_operands()
        with pytest.raises(ValueError, match="unknown subtree_promote"):
            subtree_promote(
                *[jnp.asarray(p) for p in planes],
                jnp.asarray(terminal),
                jnp.asarray(acts),
                max_retained=4,
                bfs_rounds=4,
                mode="x",
            )


class TestSearchGatherInvariance:
    @pytest.mark.slow
    def test_search_identical_across_modes(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        from alphatriangle_tpu.env.engine import TriangleEnv
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork

        env = TriangleEnv(tiny_env_config)
        fe = get_feature_extractor(env, tiny_model_config)
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        roots = env.reset_batch(
            jax.random.split(jax.random.PRNGKey(4), 4)
        )
        outs = {}
        for mode in ("einsum", "pallas", "take"):
            cfg = tiny_mcts_config.model_copy(
                update={"descent_gather": mode}
            )
            mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
            outs[mode] = np.asarray(
                mcts.search(net.variables, roots, jax.random.PRNGKey(5))
                .visit_counts
            )
        np.testing.assert_array_equal(outs["einsum"], outs["take"])
        np.testing.assert_array_equal(outs["einsum"], outs["pallas"])


def _tiny_net(tiny_env_config, tiny_model_config):
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork

    env = TriangleEnv(tiny_env_config)
    fe = get_feature_extractor(env, tiny_model_config)
    net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
    return env, fe, net


class TestSearchBackupInvariance:
    def test_search_identical_across_backup_modes(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        env, fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        roots = env.reset_batch(jax.random.split(jax.random.PRNGKey(4), 4))
        outs = {}
        for mode in ("xla", "pallas"):
            cfg = tiny_mcts_config.model_copy(update={"backup_update": mode})
            mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
            out = mcts.search(net.variables, roots, jax.random.PRNGKey(5))
            outs[mode] = (
                np.asarray(out.visit_counts),
                np.asarray(out.root_value),
            )
        np.testing.assert_array_equal(outs["xla"][0], outs["pallas"][0])
        np.testing.assert_array_equal(outs["xla"][1], outs["pallas"][1])

    @pytest.mark.slow
    def test_fixed_seed_chunk_bit_identical(
        self,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        """A whole self-play chunk (search + select + step + n-step
        window) must be bit-identical under either backup backend —
        the rollout-program-level parity pin."""
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        env, fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        harvests = {}
        for mode in ("xla", "pallas"):
            engine = SelfPlayEngine(
                env,
                fe,
                net,
                tiny_mcts_config.model_copy(update={"backup_update": mode}),
                tiny_train_config,
                batch_size=4,
                seed=123,
            )
            engine.play_chunk(2)
            result = engine.harvest()
            harvests[mode] = (
                result.policy_target,
                result.value_target,
                np.asarray(engine.states.score),
            )
        for got, want in zip(harvests["pallas"], harvests["xla"]):
            np.testing.assert_array_equal(got, want)


class TestInferencePrecision:
    def test_f32_policy_is_identity(self, tiny_model_config):
        from alphatriangle_tpu.nn.precision import (
            cast_params_for_inference,
            inference_dtype,
        )

        assert inference_dtype(tiny_model_config) == jnp.float32
        variables = {"params": {"w": jnp.ones((2, 2))}}
        assert (
            cast_params_for_inference(variables, tiny_model_config)
            is variables
        )

    def test_bf16_forward_within_tolerance(
        self, tiny_env_config, tiny_model_config
    ):
        """bf16-cast params must give close (not bit-equal: the heads'
        final f32 Dense sees rounded weights) priors and values."""
        from alphatriangle_tpu.nn.precision import cast_params_for_inference

        env, fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        bf16_cfg = tiny_model_config.model_copy(
            update={"INFERENCE_PRECISION": "bfloat16"}
        )
        cast = cast_params_for_inference(net.variables, bf16_cfg)
        leaf = jax.tree_util.tree_leaves(cast["params"])[0]
        assert leaf.dtype == jnp.bfloat16
        states = env.reset_batch(jax.random.split(jax.random.PRNGKey(8), 8))
        grids, others = jax.vmap(fe.extract)(states)
        pol_f32, val_f32 = net.model.apply(
            net.variables, grids, others, train=False
        )
        pol_bf16, val_bf16 = net.model.apply(cast, grids, others, train=False)
        assert pol_bf16.dtype == jnp.float32  # heads stay f32
        p32 = jax.nn.softmax(pol_f32, axis=-1)
        p16 = jax.nn.softmax(pol_bf16, axis=-1)
        np.testing.assert_allclose(
            np.asarray(p16), np.asarray(p32), atol=0.05
        )
        np.testing.assert_allclose(
            np.asarray(val_bf16), np.asarray(val_f32), atol=0.2, rtol=0.1
        )

    def test_fixed_seed_arena_equality(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        """Paired-hands arena (arena.py): the same fixed-seed games
        played greedily under f32 vs bf16 inference must score within
        tolerance — the Elo-neutrality gate for the precision policy
        (KataGo, arXiv:1902.10565)."""
        from alphatriangle_tpu.arena import greedy_mcts_policy, play
        from alphatriangle_tpu.nn.precision import cast_params_for_inference

        env, fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        cfg = tiny_mcts_config.model_copy(update={"wave_noise_scale": 0.0})
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        bf16_cfg = tiny_model_config.model_copy(
            update={"INFERENCE_PRECISION": "bfloat16"}
        )

        class _Net:
            def __init__(self, variables):
                self.variables = variables

        scores = {}
        for name, variables in (
            ("f32", net.variables),
            ("bf16", cast_params_for_inference(net.variables, bf16_cfg)),
        ):
            s, _, _ = play(
                env,
                greedy_mcts_policy(_Net(variables), mcts),
                games=4,
                max_moves=8,
                seed=21,
            )
            scores[name] = s
        # Paired hands strip hand luck; a per-game score gap only
        # appears where rounding flips a near-tie move choice.
        assert (
            abs(float(scores["bf16"].mean() - scores["f32"].mean())) <= 3.0
        )

    def test_int8_round_trip_within_per_channel_tolerance(
        self, tiny_env_config, tiny_model_config
    ):
        """Weight-only int8 (nn/precision.py): every floating matrix
        leaf becomes {int8 q, per-channel f32 scale}; dequantization
        must land within one per-channel scale unit of the original
        (0.5 from symmetric rounding + ~0.5 from the bf16 dequant
        target), and the quantized tree must read far fewer bytes."""
        from alphatriangle_tpu.nn.precision import (
            dequantize_params,
            is_quantized_leaf,
            quantize_params_for_inference,
            quantized_param_bytes,
        )

        _env, _fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        q = quantize_params_for_inference(net.variables)
        q_leaves = [
            leaf
            for leaf in jax.tree_util.tree_leaves(
                q, is_leaf=is_quantized_leaf
            )
            if is_quantized_leaf(leaf)
        ]
        assert q_leaves, "no matrix leaf was quantized"
        for leaf in q_leaves:
            assert leaf["q"].dtype == jnp.int8
            assert leaf["scale"].dtype == jnp.float32
        deq = dequantize_params(q)
        flat_orig = jax.tree_util.tree_leaves(net.variables)
        flat_deq = jax.tree_util.tree_leaves(deq)
        checked = 0
        for orig, got in zip(flat_orig, flat_deq):
            if orig.ndim < 2 or not jnp.issubdtype(
                orig.dtype, jnp.floating
            ):
                continue
            scale = jnp.max(
                jnp.abs(orig.astype(jnp.float32)),
                axis=tuple(range(orig.ndim - 1)),
                keepdims=True,
            ) / 127.0
            err = jnp.abs(
                got.astype(jnp.float32) - orig.astype(jnp.float32)
            )
            assert float(jnp.max(err / jnp.maximum(scale, 1e-12))) <= 1.0
            checked += 1
        assert checked == len(q_leaves)
        # The HBM-read win the quantization exists for: int8 weights +
        # per-channel scales must read far fewer bytes than f32.
        f32_bytes = quantized_param_bytes(net.variables)
        int8_bytes = quantized_param_bytes(q)
        assert int8_bytes < f32_bytes / 2

    def test_int8_fixed_seed_arena_within_gate(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        """The same Elo-neutrality gate as bf16, for the int8 path:
        paired fixed-seed greedy games under f32 vs quantized weights
        must score within tolerance (the search dequantizes marker
        leaves at its evaluate choke point, mcts/search.py)."""
        from alphatriangle_tpu.arena import greedy_mcts_policy, play
        from alphatriangle_tpu.nn.precision import cast_params_for_inference

        env, fe, net = _tiny_net(tiny_env_config, tiny_model_config)
        cfg = tiny_mcts_config.model_copy(update={"wave_noise_scale": 0.0})
        mcts = BatchedMCTS(env, fe, net.model, cfg, net.support)
        int8_cfg = tiny_model_config.model_copy(
            update={"INFERENCE_PRECISION": "int8"}
        )

        class _Net:
            def __init__(self, variables):
                self.variables = variables

        scores = {}
        for name, variables in (
            ("f32", net.variables),
            ("int8", cast_params_for_inference(net.variables, int8_cfg)),
        ):
            s, _, _ = play(
                env,
                greedy_mcts_policy(_Net(variables), mcts),
                games=4,
                max_moves=8,
                seed=21,
            )
            scores[name] = s
        assert (
            abs(float(scores["int8"].mean() - scores["f32"].mean())) <= 3.0
        )
