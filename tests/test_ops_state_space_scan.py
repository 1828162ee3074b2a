"""Mamba-2's scan as one kernel (ops/state_space_scan.py), interpreted
on the CPU: against the token-by-token recurrence at the shapes the
kernel takes (heads of 64, a state of 128, 8 groups, chunks of 128),
beside the chunked form it replaces on a TPU, and the choice between
the two. What the TPU's compiler says of it is
tests/test_chip_compile.py's; what it costs is the chip's to say
(PERF.md)."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.nn import state_space
from alphatriangle_tpu.ops import state_space_scan as kernel
from alphatriangle_tpu.ops.state_space_scan import (
    block_boards,
    ssm_path,
    state_space_scan,
)

BOARDS, HEADS, HEAD, GROUPS, STATE, CHUNK = 3, 16, 64, 8, 128, 128


def _inputs(seq, decay="drawn", seed=0, boards=BOARDS, dtype=jnp.float32):
    """x `(b, s, heads, 64)`, B and C `(b, s, 8, 128)` in `dtype`, the
    step and log a `(b, s, heads)` and D as the mixer makes them: steps
    about Mamba-2's initial ones, A drawn over `A_RANGE` or at either
    end of it (a decay near 1 and a steep one)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.nn.silu(jax.random.normal(keys[0], (boards, seq, HEADS, HEAD)))
    b, c = (
        jax.random.normal(k, (boards, seq, GROUPS, STATE)) / math.sqrt(STATE)
        for k in keys[1:3]
    )
    step = jax.nn.softplus(
        0.5 * jax.random.normal(keys[3], (boards, seq, HEADS))
        + state_space.init_dt_bias(keys[4], (HEADS,))
    )
    a_log = {
        "drawn": state_space.init_a_log(keys[5], (HEADS,)),
        "slow": jnp.full((HEADS,), math.log(state_space.A_RANGE[0])),
        "steep": jnp.full((HEADS,), math.log(state_space.A_RANGE[1])),
    }[decay]
    log_a = -step * jnp.exp(a_log)
    skip = state_space.init_skip(keys[6], (HEADS,))
    return x.astype(dtype), step, log_a, b.astype(dtype), c.astype(dtype), skip


def _kernel(x, step, log_a, b, c, skip, dtype):
    boards, s = x.shape[:2]
    xbc = jnp.concatenate(
        [v.reshape(boards, s, -1) for v in (x, b, c)], axis=-1
    )
    return state_space_scan(
        xbc, step, log_a, skip, heads=HEADS, head_dim=HEAD, groups=GROUPS,
        chunk=CHUNK, dtype=dtype, interpret=True,
    )


def _flat(y):
    return y.reshape(*y.shape[:2], HEADS * HEAD)


def _gap(got, want) -> float:
    return float(jnp.abs(got - want).max()) / max(1.0, float(jnp.abs(want).max()))


@pytest.mark.parametrize("seq", [252, 256])
@pytest.mark.parametrize("decay", ["drawn", "slow", "steep"])
def test_the_kernel_is_the_token_by_token_recurrence(seq, decay):
    """With float32 operands: at the cell's 252 tokens (the second chunk
    filled) and at two whole chunks, with A drawn and at either end of
    its range."""
    inputs = _inputs(seq, decay)
    want = _flat(state_space.recurrent(*inputs))
    got = _kernel(*inputs, jnp.float32)
    assert got.shape == want.shape == (BOARDS, seq, HEADS * HEAD)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    assert _gap(got, want) < 2e-5


# The kernel rounds where `chunked` rounds and sums in another order:
# it may lie this much further from the recurrence than `chunked` does
# on the same inputs.
ROUNDING_ROOM = 1.05


@pytest.mark.parametrize("decay", ["drawn", "slow", "steep"])
def test_bfloat16_operands_round_as_the_chunked_form(decay):
    inputs = _inputs(252, decay, dtype=jnp.bfloat16)
    want = _flat(state_space.recurrent(*inputs))
    got = _kernel(*inputs, jnp.bfloat16)
    chunked = _flat(state_space.chunked(*inputs, CHUNK, jnp.bfloat16))
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    for reduce in (jnp.max, jnp.mean):
        assert float(reduce(jnp.abs(got - want))) <= ROUNDING_ROOM * float(
            reduce(jnp.abs(chunked - want))
        )


def test_the_kernel_is_causal_reads_its_group_and_keeps_its_skip():
    """Change token 5's x, then token 200's (past the first chunk's
    edge): outputs before it stay, outputs from it on move. Change
    group 1's B: only heads 2 and 3 (group 1's) move. With C at nought
    what is left is D x."""
    x, step, log_a, b, c, skip = _inputs(252, boards=1)
    a = _kernel(x, step, log_a, b, c, skip, jnp.float32)
    for at in (5, 200):
        moved = _kernel(x.at[:, at].add(1.0), step, log_a, b, c, skip, jnp.float32)
        moved = np.asarray(jnp.abs(a - moved).max(axis=(0, 2)))
        assert (moved[:at] == 0).all() and moved[at] > 1e-3
    by_head = _kernel(x, step, log_a, b.at[:, :, 1].add(1.0), c, skip, jnp.float32)
    by_head = np.asarray(jnp.abs(a - by_head).max(axis=(0, 1)).reshape(HEADS, HEAD).max(1))
    assert (by_head[2:4] > 1e-3).all()
    assert (np.delete(by_head, [2, 3]) == 0).all()
    np.testing.assert_allclose(
        _kernel(x, step, log_a, b, jnp.zeros_like(c), skip, jnp.float32),
        _flat(skip[:, None] * x),
        atol=1e-6,
    )


def test_a_batch_the_block_does_not_divide_and_a_walk_not_unrolled(monkeypatch):
    """Five boards, two a grid step: the last step is padded, and what
    the padded board computes is never written. The board's chunks
    walked by a loop, as a long sequence's are."""
    monkeypatch.setattr(kernel, "_MAX_BLOCK_BOARDS", 2)
    monkeypatch.setattr(kernel, "_UNROLLED_CHUNKS", 1)
    inputs = _inputs(130, boards=5)
    got = _kernel(*inputs, jnp.float32)
    assert got.shape == (5, 130, HEADS * HEAD)
    assert _gap(got, _flat(state_space.recurrent(*inputs))) < 2e-5


CELL = dict(
    partitioned=False, backend="tpu", seq=252, heads=128, head_dim=64,
    groups=8, state_size=128, chunk=128, dtype=jnp.bfloat16,
)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "kernel"),  # nemotron-super-rollout's mixer on one chip
        ({"dtype": jnp.float32}, "kernel"),
        ({"head_dim": 128, "seq": 700, "chunk": 256}, "kernel"),
        ({"backend": "cpu"}, "chunked"),
        ({"backend": "gpu"}, "chunked"),
        ({"partitioned": True}, "chunked"),
        ({"head_dim": 8, "state_size": 8, "chunk": 8}, "chunked"),  # the tests' stack
        ({"head_dim": 48}, "chunked"),  # not whole 128-lane blocks
        ({"heads": 8}, "chunked"),  # one head a group: a block would span two
        ({"state_size": 64}, "chunked"),
        ({"chunk": 64}, "chunked"),
        ({"seq": 40000}, "chunked"),  # one board's blocks pass the plan
    ],
)
def test_path_is_chosen_by_what_the_call_observes(change, path):
    assert ssm_path(**{**CELL, **change}) == path


@pytest.mark.parametrize("seq,boards", [(252, 16), (2048, 6), (8192, 2), (40000, 0)])
def test_block_plan_follows_the_board(seq, boards):
    """Boards a grid step: 16 at the cell's 252 tokens, as many as the
    plan holds of a longer sequence's blocks, none where one board's
    pass it."""
    assert block_boards(64, seq, 128, 128, 2) == boards
    assert block_boards(3, seq, 128, 128, 2) == min(boards, 3)


@pytest.mark.parametrize(
    "change,message",
    [
        ({"head_dim": 48, "heads": 4}, "128-lane blocks whose heads"),
        ({"chunk": 64}, "chunk of 64"),
        ({"groups": 16}, "share a group"),
    ],
)
def test_what_the_kernel_cannot_take_is_refused(change, message):
    arguments = dict(heads=HEADS, head_dim=HEAD, groups=GROUPS, chunk=CHUNK,
                     dtype=jnp.float32)
    arguments.update(change)
    width = arguments["heads"] * arguments["head_dim"] + 2 * GROUPS * STATE
    xbc = jnp.zeros((BOARDS, 12, width))
    rows = jnp.zeros((BOARDS, 12, arguments["heads"]))
    with pytest.raises(ValueError, match=message):
        jax.eval_shape(
            lambda *a: state_space_scan(*a, **arguments),
            xbc, rows, rows, jnp.zeros((arguments["heads"],)),
        )


def test_the_trunk_reads_the_mesh_of_its_trace(monkeypatch):
    """A state-space layer traced into a program the compiler splits
    over a mesh keeps the chunked form, on a backend said to be a TPU
    too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk
    from tests.test_trunk import SSM

    cfg = TrunkConfig(
        **{**SSM, "mamba_head_dim": 64, "ssm_state_size": 128, "chunk_size": 128}
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def program(x):
        seen.append(trunk.scan_path(cfg, x * 2.0, jnp.bfloat16))
        return x

    x = jnp.ones((8, 252, 32))
    jax.eval_shape(program, x)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    jax.eval_shape(
        program, jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp")))
    )
    assert seen == ["kernel", "chunked"]
