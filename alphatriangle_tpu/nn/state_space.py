"""The selective state space of a `state_space` layer of the trunk
(nn/trunk.py): Mamba-2's recurrence, one scalar decay a head.

A head h keeps a state S (p x n: `mamba_head_dim` x `ssm_state_size`),
from zero. At token t, with the head's input x_t (p), its step
Delta_t > 0, its decay a_t = exp(-Delta_t exp(A_log_h)) in (0, 1) and
the B_t, C_t (n) of the head's group (`n_groups` groups of heads share
them):

    S_t = a_t S_{t-1} + Delta_t x_t B_t^T
    y_t = S_t C_t + D_h x_t

`recurrent` is that, a token at a time. `chunked` is the same sum taken
`chunk` tokens at a time, as matrix products (the SSD form). Within a
chunk, with G_t the running sum of log a from the chunk's first token
and S_0 the state the chunk starts from,

    y_t = exp(G_t) S_0 C_t + sum_{i <= t} exp(G_t - G_i) (C_t . B_i) Delta_i x_i + D x_t
    S_last = exp(G_last) S_0 + sum_i exp(G_last - G_i) Delta_i x_i B_i^T

The decay is a scalar a head, so exp(G_t - G_i), i <= t, is one number
in (0, 1] a pair and is taken as it stands: nothing grows, and nothing
has to be split into sub-blocks (KDA's decay is a vector, and its
pairwise products are: nn/linear_attention.py). The pairwise products
C_t . B_i are made once a group, not a head. Everything that does not
read S_0 is made for all chunks at once; a scan over the chunks then
carries the state.

Steps, decays and states are float32; the operands of the matrix
products are `dtype`, their sums float32.

Two forms of one sum, and where each runs. `recurrent` is the
definition and the tests' oracle: nothing calls it in a program.
`chunked` is plain `jax.numpy`: it runs on the CPU, in a program
partitioned over a mesh and at shapes the kernel refuses. On one TPU
chip the same chunked form runs as one kernel a layer and block of
boards, a chunk's decays and weights kept in VMEM
(`ops/state_space_scan.py`); which of the two a layer takes is decided
by what its site can observe (`ssm_path`), never by an option
(docs/KERNELS.md).
"""

import jax
import jax.numpy as jnp
from jax import Array

# How Mamba-2 draws a head's `A_log` and `dt_bias`: A uniform over
# (1, 16), the step log-uniform over (0.001, 0.1) and floored at 1e-4,
# `dt_bias` its inverse softplus (the published `time_step_min`,
# `time_step_max`, `time_step_floor`: an initialisation, no clamp of a
# running step).
A_RANGE = (1.0, 16.0)
STEP_RANGE = (1e-3, 1e-1)
STEP_FLOOR = 1e-4


def init_a_log(key, shape, dtype=jnp.float32) -> Array:
    low, high = A_RANGE
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, low, high)).astype(dtype)


def init_dt_bias(key, shape, dtype=jnp.float32) -> Array:
    low, high = (jnp.log(x) for x in STEP_RANGE)
    step = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
    step = jnp.maximum(step, STEP_FLOOR)
    return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)  # softplus^-1


def init_skip(key, shape, dtype=jnp.float32) -> Array:
    """D: about 1, as Mamba-2 sets it, and no two heads alike, so that
    no constant can stand in for it."""
    return (1.0 + 0.5 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _by_head(x: Array, heads: int) -> Array:
    """(..., groups, n) -> (..., heads, n): head h reads group
    h // (heads / groups)."""
    return jnp.repeat(x, heads // x.shape[-2], axis=-2)


def recurrent(x: Array, step: Array, log_a: Array, b: Array, c: Array,
              skip: Array) -> Array:
    """y (batch, s, heads, p) float32 by the recurrence, a token at a
    time: x (batch, s, heads, p), step and log_a = log a (batch, s,
    heads), b and c (batch, s, groups, n), skip = D (heads,)."""
    x, step, log_a, b, c, skip = (
        v.astype(jnp.float32) for v in (x, step, log_a, b, c, skip)
    )
    heads = x.shape[2]
    b, c = _by_head(b, heads), _by_head(c, heads)

    def one(state, xs):
        x_t, step_t, log_a_t, b_t, c_t = xs
        state = jnp.exp(log_a_t)[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", step_t[..., None] * x_t, b_t
        )
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    start = jnp.zeros((*x.shape[:1], heads, x.shape[3], b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(
        one, start, tuple(jnp.swapaxes(v, 0, 1) for v in (x, step, log_a, b, c))
    )
    return jnp.swapaxes(y, 0, 1) + skip[:, None] * x


def chunked(x: Array, step: Array, log_a: Array, b: Array, c: Array,
            skip: Array, chunk: int, dtype) -> Array:
    """`recurrent`'s y, `chunk` tokens at a time; any s (the last chunk
    is filled with tokens that decay nothing and write nothing)."""
    batch, s, heads, p = x.shape
    groups, n = b.shape[-2:]
    chunks = -(-s // chunk)
    fill = chunks * chunk - s

    def cut(v):  # (batch, s, ...) -> (batch, chunks, chunk, ...), float32
        v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, fill)) + ((0, 0),) * (v.ndim - 2))
        return v.reshape(batch, chunks, chunk, *v.shape[2:])

    dot = lambda eq, *vs: jnp.einsum(  # noqa: E731
        eq, *(v.astype(dtype) for v in vs), preferred_element_type=jnp.float32
    )
    xc, dt, la, bc, cc = cut(x), cut(step), cut(log_a), cut(b), cut(c)
    cum = jnp.cumsum(la, axis=2)  # (batch, chunks, chunk, heads), <= 0
    written = dt[..., None] * xc  # Delta_i x_i
    t, i = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    # exp(G_t - G_i) for i <= t, nought ahead: (batch, chunks, heads, t, i).
    gap = jnp.moveaxis(cum, 3, 2)
    decay = jnp.exp(
        jnp.where(i <= t, gap[..., :, None] - gap[..., None, :], -jnp.inf)
    )
    pairs = dot("bctgn,bcign->bcgti", cc, bc)  # C_t . B_i, a group
    weights = decay * jnp.repeat(pairs, heads // groups, axis=2)
    within = dot("bchti,bcihp->bcthp", weights, written)
    # What a token leaves in the chunk's last state, and what the chunk
    # writes there in all: (batch, chunks, heads, p, n).
    left = jnp.exp(cum[:, :, -1:] - cum)[..., None] * written
    wrote = dot("bcihp,bcihn->bchpn", left, _by_head(bc, heads))
    kept = jnp.exp(cum[:, :, -1])  # the chunk's whole decay, (batch, chunks, heads)

    def one(state, xs):
        wrote_c, kept_c = xs
        return kept_c[..., None, None] * state + wrote_c, state

    start = jnp.zeros((batch, heads, p, n), jnp.float32)
    _, before = jax.lax.scan(
        one, start, (jnp.swapaxes(wrote, 0, 1), jnp.swapaxes(kept, 0, 1))
    )
    before = jnp.swapaxes(before, 0, 1)  # the state each chunk starts from
    carried = jnp.exp(cum)[..., None] * dot(
        "bchpn,bcthn->bcthp", before, _by_head(cc, heads)
    )
    y = (within + carried).reshape(batch, chunks * chunk, heads, p)[:, :s]
    return y + skip.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
