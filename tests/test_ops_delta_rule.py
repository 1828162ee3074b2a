"""The gated delta rule as one kernel (ops/delta_rule.py), interpreted
on the CPU: against the token-by-token recurrence at the shapes the
kernel takes (a head of 128 keys and values), beside the chunked form
it replaces on a TPU, and the choice between the two. What the TPU's
compiler says of it is tests/test_chip_compile.py's; what it costs is
the chip's to say (PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.nn import linear_attention as delta_rule
from alphatriangle_tpu.ops import delta_rule as kernel
from alphatriangle_tpu.ops.delta_rule import (
    block_boards,
    gated_delta_rule,
    linear_path,
)

BOARDS, HEADS, HEAD = 3, 2, 128


def _inputs(seq, decay="drawn", seed=0, boards=BOARDS):
    """q, k, v, g `(b, s, heads, 128)` and beta `(b, s, heads)` as a
    mixer makes them: unit keys, queries over sqrt(128), g in (-5, 0)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    shape = (boards, seq, HEADS, HEAD)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], shape)) * HEAD**-0.5
    k = unit(jax.random.normal(keys[1], shape))
    v = jax.random.normal(keys[2], shape)
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], shape))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    if decay == "lower_bound":
        g = jnp.full_like(g, -5.0)
    elif decay == "none":
        g = jnp.full_like(g, -1e-4)
    elif decay == "repeating_keys":
        # Keys that say nearly the same thing and next to no decay: the
        # triangle I + beta A is as far from I as it gets.
        k = unit(jax.random.normal(keys[5], shape[:1] + (1,) + shape[2:]) + 0.5 * k)
        g = 0.01 * g
        beta = jax.nn.sigmoid(1.0 + jax.random.normal(keys[4], shape[:3]))
    return q, k, v, g, beta


def _heads_first(y):
    b, s = y.shape[:2]
    return jnp.moveaxis(y, 2, 1).reshape(b * HEADS, s, *y.shape[3:])


def _boards_first(o, boards=BOARDS):
    """(b x heads, s, 128) -> (b, s, heads x 128), the kernel's layout."""
    s = o.shape[1]
    return jnp.moveaxis(o.reshape(boards, HEADS, s, HEAD), 1, 2).reshape(
        boards, s, HEADS * HEAD
    )


def _recurrent(q, k, v, g, beta):
    return _boards_first(
        delta_rule.recurrent(*(_heads_first(y) for y in (q, k, v, g, beta))),
        q.shape[0],
    )


def _kernel(q, k, v, g, beta, dtype, chunk=64):
    b, s = q.shape[:2]
    return gated_delta_rule(
        *(y.reshape(b, s, HEADS * HEAD) for y in (q, k, v, g)), beta,
        heads=HEADS, chunk=chunk, lower_bound=-5.0, dtype=dtype, interpret=True,
    )


def _gap(got, want) -> float:
    return float(jnp.abs(got - want).max()) / max(1.0, float(jnp.abs(want).max()))


@pytest.mark.parametrize("seq", [77, 252, 256])
@pytest.mark.parametrize(
    "decay", ["drawn", "lower_bound", "none", "repeating_keys"]
)
def test_the_kernel_is_the_token_by_token_recurrence(seq, decay):
    """With float32 operands, under the bound the chunked form is held
    to: sequences that are no multiple of the chunk, decays drawn over
    (-5, 0), g = -5 on every step (16 rows gather exp 80), next to no
    decay, and keys that repeat each other (where the doublings of a
    whole 64-row triangle lose every digit; the kernel's go by blocks
    of 16)."""
    q, k, v, g, beta = _inputs(seq, decay)
    want = _recurrent(q, k, v, g, beta)
    got = _kernel(q, k, v, g, beta, jnp.float32)
    assert got.shape == want.shape == (BOARDS, seq, HEADS * HEAD)
    assert got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert _gap(got, want) < 2e-5


@pytest.mark.parametrize("seq,chunk", [(12, 16), (40, 32), (130, 128)])
def test_the_chunk_is_any_number_of_sub_blocks(seq, chunk):
    q, k, v, g, beta = _inputs(seq)
    got = _kernel(q, k, v, g, beta, jnp.float32, chunk)
    assert _gap(got, _recurrent(q, k, v, g, beta)) < 2e-5


# The kernel rounds where `chunked` rounds, in another order (beta
# scales columns; the inverse goes by blocks): it may lie this much
# further from the recurrence than `chunked` does on the same inputs.
ROUNDING_ROOM = 1.5


@pytest.mark.parametrize("decay", ["drawn", "lower_bound", "none"])
def test_bfloat16_operands_round_no_worse_than_the_chunked_form(decay):
    q, k, v, g, beta = _inputs(252, decay)
    v = v.astype(jnp.bfloat16)
    want = _recurrent(q, k, v, g, beta)
    got = _kernel(q, k, v, g, beta, jnp.bfloat16)
    chunked = _boards_first(
        delta_rule.chunked(
            *(_heads_first(y) for y in (q, k, v, g, beta)), 64, -5.0, jnp.bfloat16
        )
    )
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    for reduce in (jnp.max, jnp.mean):
        assert float(reduce(jnp.abs(got - want))) <= ROUNDING_ROOM * float(
            reduce(jnp.abs(chunked - want))
        )


def test_bfloat16_operands_on_keys_that_repeat_each_other():
    """Where the triangle is far from I the inverse's three passes
    show: the answer stays within bfloat16's rounding of the
    recurrence's (values up to 0.1; one pass lies ten times further)."""
    q, k, v, g, beta = _inputs(252, "repeating_keys")
    v = v.astype(jnp.bfloat16)
    want = _recurrent(q, k, v, g, beta)
    got = _kernel(q, k, v, g, beta, jnp.bfloat16)
    assert float(jnp.abs(got - want).max()) < 2e-3
    assert float(jnp.abs(got - want).mean()) < 2e-4


def test_the_kernel_is_causal_and_its_state_decays():
    """Change token 5: outputs before it stay, outputs from it on move;
    at the lower bound what token 5 wrote is gone sixteen tokens on,
    across the chunk's edge at 64 as inside a chunk."""
    for at in (5, 60):
        q, k, v, g, beta = _inputs(100, "lower_bound")
        a = _kernel(q, k, v, g, beta, jnp.float32)
        b = _kernel(q, k, v.at[:, at].add(1.0), g, beta, jnp.float32)
        moved = np.asarray(jnp.abs(a - b).max(axis=(0, 2)))
        assert (moved[:at] == 0).all() and moved[at] > 1e-4
        assert moved[at + 16 :].max() < 1e-30


def test_a_batch_the_block_does_not_divide(monkeypatch):
    """Five boards, two a grid step: the last step is padded, and what
    the padded board computes is never written."""
    monkeypatch.setattr(kernel, "_MAX_BLOCK_BOARDS", 2)
    q, k, v, g, beta = _inputs(70, boards=5)
    got = _kernel(q, k, v, g, beta, jnp.float32)
    assert got.shape == (5, 70, HEADS * HEAD)
    assert _gap(got, _recurrent(q, k, v, g, beta)) < 2e-5


CELL = dict(
    partitioned=False, backend="tpu", seq=252, head_dim=128, chunk=64,
    lower_bound=-5.0, dtype=jnp.bfloat16,
)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "kernel"),  # ling-flash-rollout's mixer on one chip
        ({"dtype": jnp.float32}, "kernel"),
        ({"head_dim": 256, "seq": 120, "chunk": 16}, "kernel"),
        ({"backend": "cpu"}, "chunked"),
        ({"backend": "gpu"}, "chunked"),
        ({"partitioned": True}, "chunked"),
        ({"head_dim": 32}, "chunked"),  # the tests' heads: 32 lanes of 128
        ({"head_dim": 192}, "chunked"),
        ({"chunk": 24}, "chunked"),  # not whole sub-blocks: chunked refuses it
        ({"lower_bound": -6.0}, "chunked"),  # 16 steps pass exp 88: the same
        ({"seq": 40000}, "chunked"),  # one board's blocks pass the plan
    ],
)
def test_path_is_chosen_by_what_the_call_observes(change, path):
    assert linear_path(**{**CELL, **change}) == path


@pytest.mark.parametrize(
    "seq,boards", [(252, 8), (2048, 4), (8192, 1), (16384, 0)]
)
def test_block_plan_follows_the_board(seq, boards):
    """Boards a grid step: 8 at the cell's 252 tokens, as many as the
    plan holds of a longer sequence's blocks, none where one board's
    pass it."""
    assert block_boards(64, seq, 128, 128, 64, 2) == boards
    assert block_boards(3, seq, 128, 128, 64, 2) == min(boards, 3)


@pytest.mark.parametrize(
    "change,message",
    [
        ({"chunk": 24}, "sub-blocks"),
        ({"lower_bound": -6.0}, "float32"),
        ({"heads": 8}, "128-lane"),  # heads of 32
    ],
)
def test_what_the_kernel_cannot_take_is_refused(change, message):
    q, k, v, g, beta = _inputs(12)
    arguments = dict(heads=HEADS, chunk=16, lower_bound=-5.0, dtype=jnp.float32)
    arguments.update(change)
    beta = jnp.zeros((BOARDS, 12, arguments["heads"]))
    with pytest.raises(ValueError, match=message):
        jax.eval_shape(
            lambda *a: gated_delta_rule(*a, **arguments),
            *(y.reshape(BOARDS, 12, HEADS * HEAD) for y in (q, k, v, g)), beta,
        )


def test_the_trunk_reads_the_mesh_of_its_trace(monkeypatch):
    """A linear layer traced into a program the compiler splits over a
    mesh keeps the chunked form, on a backend said to be a TPU too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk
    from tests.test_trunk import HYBRID

    cfg = TrunkConfig(**{**HYBRID, "head_dim": 128, "linear_chunk": 64})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def program(x):
        seen.append(trunk.recurrence_path(cfg, x * 2.0, jnp.bfloat16))
        return x

    x = jnp.ones((8, 252, 64))
    jax.eval_shape(program, x)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    jax.eval_shape(
        program, jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp")))
    )
    assert seen == ["kernel", "chunked"]
