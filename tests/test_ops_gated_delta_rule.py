"""The gated-delta-net recurrence as one kernel (ops/gated_delta_rule.py),
interpreted on the CPU: against the token-by-token recurrence (each key
head repeated for its value heads, the decay on every channel) and the
chunked form it replaces on a TPU, at the shapes the kernel takes (heads
of 128 keys and values, two value heads a key head), and the choice
between the two. What the TPU's compiler says of it is
tests/test_chip_compile.py's; what it costs is the chip's to say
(PERF.md)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.nn import linear_attention
from alphatriangle_tpu.ops import gated_delta_rule as kernel
from alphatriangle_tpu.ops.gated_delta_rule import block_boards, gated_delta_net, gdn_path

BOARDS, GROUP, HEAD, CHUNK = 3, 2, 128, 64


def _inputs(seq, decay="drawn", key_heads=2, boards=BOARDS, seed=0):
    """q, k `(b, s, key heads x 128)`, v `(b, s, value heads x 128)`, g
    and beta `(b, s, value heads)` as the mixer makes them: unit keys,
    queries over sqrt(128), g = -A softplus(.) with A in (1, 16); or g
    past -88 on every token; or next to no decay on keys that repeat
    each other (the triangle as far from I as it gets)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    heads = key_heads * GROUP
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (boards, seq, key_heads, HEAD))) * HEAD**-0.5
    k = jax.random.normal(keys[1], (boards, seq, key_heads, HEAD))
    v = jax.random.normal(keys[2], (boards, seq, heads * HEAD))
    rate = jax.random.uniform(keys[3], (heads,), minval=1.0, maxval=16.0)
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], (boards, seq, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (boards, seq, heads)))
    if decay == "past_88":
        g = jnp.full_like(g, -100.0)
    elif decay == "repeating_keys":
        k = jax.random.normal(keys[6], (boards, 1, key_heads, HEAD)) + 0.5 * k
        g = jnp.full_like(g, -1e-4)
        beta = jax.nn.sigmoid(1.0 + jax.random.normal(keys[5], (boards, seq, heads)))
    flat = lambda x: x.reshape(boards, seq, key_heads * HEAD)  # noqa: E731
    return flat(q), flat(unit(k)), v, g, beta


def _kernel(q, k, v, g, beta, dtype=jnp.float32, v_first=0):
    return gated_delta_net(
        q, k, v, g, beta, key_heads=q.shape[2] // HEAD, value_dim=HEAD,
        chunk=CHUNK, dtype=dtype, v_first=v_first, interpret=True,
    )


def _by_value_head(q, k, v, g, beta):
    """The operands `(b x value heads, s, ...)`, each key head repeated
    for its value heads and g on every channel."""
    b, s, width = q.shape
    heads = g.shape[2]
    first = lambda x: jnp.moveaxis(x, 2, 1).reshape(b * heads, s, *x.shape[3:])  # noqa: E731
    repeat = lambda x: jnp.repeat(x.reshape(b, s, width // HEAD, HEAD), GROUP, axis=2)  # noqa: E731
    return (
        first(repeat(q)), first(repeat(k)), first(v.reshape(b, s, heads, HEAD)),
        first(jnp.broadcast_to(g[..., None], (b, s, heads, HEAD))), first(beta),
    )


def _recurrent(q, k, v, g, beta):
    b, s, _ = q.shape
    o = linear_attention.recurrent(*_by_value_head(q, k, v, g, beta))
    return jnp.moveaxis(o.reshape(b, -1, s, HEAD), 1, 2).reshape(b, s, -1)


def _chunked(q, k, v, g, beta, dtype=jnp.float32):
    b, s, width = q.shape
    key_heads = width // HEAD

    def first(x):  # (b, s, key heads, ...) -> (b x key heads, s, ...)
        x = jnp.moveaxis(x.reshape(b, s, key_heads, *x.shape[3:]), 2, 1)
        return x.reshape(b * key_heads, s, *x.shape[3:])

    o = linear_attention.chunked_scalar(
        first(q.reshape(b, s, key_heads, HEAD)), first(k.reshape(b, s, key_heads, HEAD)),
        first(v.reshape(b, s, key_heads, GROUP, HEAD)),
        first(g.reshape(b, s, key_heads, GROUP)),
        first(beta.reshape(b, s, key_heads, GROUP)), CHUNK, dtype,
    )
    return jnp.moveaxis(o.reshape(b, key_heads, s, GROUP, HEAD), 1, 2).reshape(b, s, -1)


def _gap(got, want) -> float:
    return float(jnp.abs(got - want).max()) / max(1.0, float(jnp.abs(want).max()))


@pytest.mark.parametrize("seq", [12, 77, 252])
@pytest.mark.parametrize("decay", ["drawn", "past_88", "repeating_keys"])
def test_the_kernel_is_the_token_by_token_recurrence(seq, decay):
    """With float32 operands: a last chunk filled at every length, one
    key head at 12 tokens and two elsewhere; decays as the layer draws
    them, past e^-88 within one token (nothing is inf or nan; the state
    forgets), and next to none on keys that repeat each other, where the
    doublings of a whole triangle of 64 lose every digit (the kernel's
    go by blocks of 16). Where that form holds, the kernel is it too."""
    inputs = _inputs(seq, decay, key_heads=1 if seq == 12 else 2)
    want = _recurrent(*inputs)
    got = _kernel(*inputs)
    assert got.shape == want.shape == (BOARDS, seq, inputs[2].shape[2])
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    assert _gap(got, want) < 2e-5
    if decay != "repeating_keys":
        assert _gap(got, _chunked(*inputs)) < 2e-5


# The kernel rounds where `chunked_scalar` rounds, in another order
# (beta scales columns; the inverse goes by blocks): it may lie this much
# further from the recurrence than `chunked_scalar` does on the same
# inputs.
ROUNDING_ROOM = 1.5


@pytest.mark.parametrize("decay", ["drawn", "past_88"])
def test_bfloat16_operands_round_no_worse_than_the_chunked_form(decay):
    q, k, v, g, beta = _inputs(252, decay)
    v = v.astype(jnp.bfloat16)
    want = _recurrent(q, k, v, g, beta)
    got = _kernel(q, k, v, g, beta, jnp.bfloat16)
    chunked = _chunked(q, k, v, g, beta, jnp.bfloat16)
    assert got.dtype == jnp.float32 and bool(jnp.isfinite(got).all())
    for reduce in (jnp.max, jnp.mean):
        assert float(reduce(jnp.abs(got - want))) <= ROUNDING_ROOM * float(
            reduce(jnp.abs(chunked - want))
        )


def test_bfloat16_operands_on_keys_that_repeat_each_other():
    """Where the triangle is far from I the inverse's three passes
    show: the answer stays within bfloat16's rounding of the
    recurrence's (values up to 0.1), where the chunked form's whole
    triangle is off by more than the answer."""
    q, k, v, g, beta = _inputs(252, "repeating_keys")
    v = v.astype(jnp.bfloat16)
    want = _recurrent(q, k, v, g, beta)
    got = _kernel(q, k, v, g, beta, jnp.bfloat16)
    assert float(jnp.abs(got - want).max()) < 2e-3
    assert float(jnp.abs(got - want).mean()) < 2e-4
    assert float(jnp.abs(_chunked(q, k, v, g, beta) - want).max()) > 0.1


def test_the_kernel_is_causal_and_reads_its_key_head():
    """Change token 5's value, then token 200's (past the third chunk's
    edge): outputs before it stay, outputs from it on move. Change key
    head 1: value heads 0 and 1 (key head 0's) stay, 2 and 3 move."""
    q, k, v, g, beta = _inputs(252, boards=1)
    g = 0.001 * g  # next to nothing forgets within the sequence
    a = _kernel(q, k, v, g, beta)
    for at in (5, 200):
        moved = _kernel(q, k, v.at[:, at].add(1.0), g, beta)
        moved = np.asarray(jnp.abs(a - moved).max(axis=(0, 2)))
        assert (moved[:at] == 0).all() and moved[at] > 1e-4 and (moved[at:] > 0).all()
    by_head = _kernel(q, k.at[..., HEAD:].multiply(-1.0), v, g, beta)
    by_head = np.asarray(jnp.abs(a - by_head).max(axis=(0, 1)).reshape(4, HEAD).max(1))
    assert (by_head[:2] == 0).all() and (by_head[2:] > 1e-3).all()


def test_v_is_read_where_the_convolution_wrote_it(monkeypatch):
    """v's heads at an offset of a wider array, as the mixer's `[q | k |
    v]`; five boards two a grid step (the last step padded: what the
    padded board computes is never written) and a walk by a loop."""
    monkeypatch.setattr(kernel, "_MAX_BLOCK_BOARDS", 2)
    monkeypatch.setattr(kernel, "_UNROLLED_CHUNKS", 1)
    q, k, v, g, beta = _inputs(130, boards=5)
    qkv = jnp.concatenate([q, k, v], axis=-1)
    got = _kernel(q, k, qkv, g, beta, v_first=2 * q.shape[2])
    assert got.shape == (5, 130, 4 * HEAD)
    assert _gap(got, _recurrent(q, k, v, g, beta)) < 2e-5


CELL = dict(
    partitioned=False, backend="tpu", seq=252, dk=128, dv=128, group=2,
    chunk=64, dtype=jnp.bfloat16,
)


@pytest.mark.parametrize(
    "change,path",
    [
        ({}, "kernel"),  # gigachat35-rollout's mixer on one chip
        ({"dtype": jnp.float32}, "kernel"),
        ({"dk": 256, "dv": 256, "group": 4, "seq": 120, "chunk": 16}, "kernel"),
        ({"backend": "cpu"}, "chunked"),
        ({"backend": "gpu"}, "chunked"),
        ({"partitioned": True}, "chunked"),
        ({"dk": 32}, "chunked"),  # not whole 128-lane blocks
        ({"dk": 8, "dv": 8, "chunk": 8}, "chunked"),  # the tests' stack
        ({"dv": 192}, "chunked"),
        ({"chunk": 24}, "chunked"),  # not whole sub-blocks
        ({"seq": 40000}, "chunked"),  # one board's blocks pass the plan
    ],
)
def test_path_is_chosen_by_what_the_call_observes(change, path):
    assert gdn_path(**{**CELL, **change}) == path


@pytest.mark.parametrize("seq,boards", [(252, 8), (2048, 4), (8192, 1), (16384, 0)])
def test_block_plan_follows_the_board(seq, boards):
    """Boards a grid step: 8 at the cell's 252 tokens, as many as the
    plan holds of a longer sequence's blocks, none where one board's
    pass it."""
    assert block_boards(64, seq, 128, 128, 2, 64, 2) == boards
    assert block_boards(3, seq, 128, 128, 2, 64, 2) == min(boards, 3)


@pytest.mark.parametrize(
    "change,message",
    [
        ({"chunk": 24}, "sub-blocks"),
        ({"key_heads": 4}, "128-lane"),  # keys of 64
        ({"v_first": 64}, "not on a block"),
    ],
)
def test_what_the_kernel_cannot_take_is_refused(change, message):
    q, k, v, g, beta = _inputs(12)
    arguments = dict(key_heads=2, value_dim=HEAD, chunk=16, dtype=jnp.float32)
    arguments.update(change)
    with pytest.raises(ValueError, match=message):
        jax.eval_shape(lambda *a: gated_delta_net(*a, **arguments), q, k, v, g, beta)


def test_the_trunk_reads_the_mesh_of_its_trace(monkeypatch):
    """A gated-delta-net layer traced into a program the compiler splits
    over a mesh keeps the chunked form, on a backend said to be a TPU
    too."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk
    from tests.test_trunk import GDN

    cfg = TrunkConfig(
        **{**GDN, "linear_key_head_dim": 128, "linear_value_head_dim": 128,
           "linear_chunk": 64}
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    seen = []

    def program(x):
        seen.append(trunk.delta_net_path(cfg, x * 2.0, jnp.bfloat16))
        return x

    x = jnp.ones((8, 252, 32))
    jax.eval_shape(program, x)
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(4, 2), ("dp", "tp"))
    jax.eval_shape(
        program, jax.device_put(x, NamedSharding(mesh, PartitionSpec("dp")))
    )
    assert seen == ["kernel", "chunked"]
