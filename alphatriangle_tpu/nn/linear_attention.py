"""The gated delta rule with a decay for every channel (KDA), as the
recurrence of a `linear_attention` layer of the trunk (nn/trunk.py).

A head keeps a state S (dk x dv), from zero. At token t, with key k_t
and query q_t (dk), value v_t (dv), log decay g_t (dk, every entry in
(`kda_lower_bound`, 0)) and beta_t in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

`recurrent` is that, a token at a time. `chunked` is the same sum taken
`chunk` tokens at a time, as matrix products. Within a chunk, with G_t
the running sum of g from the chunk's first token and S_0 the state the
chunk starts from,

    S_t = Diag(exp G_t) S_0 + sum_{i <= t} Diag(exp(G_t - G_i)) k_i u_i^T
    u_t = beta_t (v_t - (k_t exp G_t)^T S_0 - sum_{i < t} A_ti u_i),
    A_ti = k_t . (k_i exp(G_t - G_i))            (i < t)
    o_t = (q_t exp G_t)^T S_0 + sum_{i <= t} P_ti u_i,
    P_ti = q_t . (k_i exp(G_t - G_i))            (i <= t)

so U = (I + beta A)^-1 beta (V - (K exp G) S_0): a unit lower triangular
system, whose inverse is a product of log2(chunk) factors because
beta A is nilpotent. The inverse and the two products it heads do not
read S_0 and are made for all chunks at once; a scan over the chunks
then carries the state.

exp(G_t - G_i) is a product over channels and cannot be split into
exp(G_t) exp(-G_i): at the lower bound of -5 a step, exp(-G_i) passes
float32 (exp 88) after 18 tokens. The rows of a chunk are therefore
taken in sub-blocks of `SUB` = 16, each against its own reference row r
(its eighth): on the left exp(G_t - G_r), on the right exp(G_r - G_i),
each at most exp(8 x 5) = exp 40 inside the sub-block and their
product, 16 rows apart at most, at most exp 80; for the keys before
the sub-block the right factor is under 1. Keys after it are masked,
and their exponent is clipped at the same 40 so that nothing is
infinite.

Norms, decays, states and A, P are float32; the operands of the matrix
products are `dtype`, but for the triangular inverse, which is kept in
float32 at the highest precision (a wrong digit there is multiplied by
every later token of the chunk).

Three forms of one sum, and where each runs. `recurrent` is the
definition and the tests' oracle: nothing calls it in a program.
`chunked` is plain `jax.numpy` and runs wherever a linear-attention
layer is traced for anything but one TPU chip: the CPU, heads that are
not whole 128-lane blocks (the tests' dk = 32, dv = 16), a program the
compiler partitions over a mesh. On one TPU chip with heads of 128 the
layer runs `ops/delta_rule.py`'s kernel, which is `chunked`'s
mathematics, the same sub-blocks and bound, with a chunk's
intermediates kept in VMEM and the triangle inverted by blocks of
`SUB`; `nn/trunk.py` picks between the two by what the trace can
observe (`ops/delta_rule.linear_path`), no option names either. The
doublings of a whole chunk below lose every digit where the keys of a
chunk repeat each other and nothing decays (powers of a 64 x 64
triangle with entries near 1 pass 1e10 before they cancel:
tests/test_ops_delta_rule.py holds the kernel there, not this form).
"""

import jax
import jax.numpy as jnp
from jax import Array

from ..ops.delta_rule import SUB  # rows of a sub-block, the kernel's too


def recurrent(q: Array, k: Array, v: Array, g: Array, beta: Array) -> Array:
    """o (n, s, dv) float32 by the recurrence, a token at a time: q, k,
    g (n, s, dk), v (n, s, dv), beta (n, s); n is boards x heads."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[:, :, None] * state
        seen = jnp.einsum("nk,nkv->nv", k_t, state)
        state = state + jnp.einsum("nk,nv->nkv", k_t, b_t[:, None] * (v_t - seen))
        return state, jnp.einsum("nk,nkv->nv", q_t, state)

    n, _, dk = q.shape
    start = jnp.zeros((n, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        step, start, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    )
    return jnp.swapaxes(out, 0, 1)


def _unit_lower_inverse(a: Array) -> Array:
    """(I + a)^-1 for a (..., c, c) strictly lower triangular, float32:
    (I - a)(I + a^2)(I + a^4)... up to the power that is nought."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=jnp.float32)
    product = lambda x, y: jnp.matmul(  # noqa: E731
        x, y, precision=jax.lax.Precision.HIGHEST
    )
    inverse, power, reach = eye - a, a, 2
    while reach < c:
        power = product(power, power)
        inverse = product(inverse, eye + power)
        reach *= 2
    return inverse


def _sub_block_factors(cum: Array, most: float) -> tuple[Array, Array]:
    """exp(G_t - G_r) for the rows of each sub-block, (..., blocks, SUB,
    dk), and exp(G_r - G_i) for every key of the chunk against each
    sub-block's reference row r, (..., blocks, c, dk), clipped at
    `most`, the largest exponent half a sub-block can gather."""
    *lead, c, dk = cum.shape
    blocks = c // SUB
    middle = cum[..., SUB // 2 - 1 :: SUB, :][..., :, None, :]
    rows = jnp.exp(cum.reshape(*lead, blocks, SUB, dk) - middle)
    keys = jnp.exp(jnp.minimum(middle - cum[..., None, :, :], most))
    return rows, keys


def _pairwise(left: Array, keys: Array, rows: Array, dtype) -> Array:
    """left_t . (k_i exp(G_t - G_i)) for all t, i of a chunk, unmasked:
    (..., c, c) float32, from left (..., c, dk), the keys already
    times their factor (..., blocks, c, dk) and the rows' factor. The
    caller masks i > t (or i >= t), where the value is finite and means
    nothing."""
    *lead, c, dk = left.shape
    out = jnp.einsum(
        "...bsk,...bik->...bsi",
        (left.reshape(rows.shape) * rows).astype(dtype), keys,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(*lead, c, c)


def chunked(
    q: Array, k: Array, v: Array, g: Array, beta: Array,
    chunk: int, lower_bound: float, dtype,
) -> Array:
    """`recurrent`'s o, `chunk` tokens at a time, for g >= `lower_bound`;
    any s (the last chunk is filled with tokens that decay nothing and
    write nothing)."""
    if chunk % SUB or SUB * abs(lower_bound) >= 88:
        raise ValueError(
            f"chunk {chunk} is not whole sub-blocks of {SUB}, or {SUB} steps "
            f"of {lower_bound} pass what float32's exp holds"
        )
    most = SUB // 2 * abs(lower_bound)
    n, s, dk = q.shape
    dv = v.shape[-1]
    chunks = -(-s // chunk)
    fill = chunks * chunk - s

    def cut(x):  # (n, s, ...) -> (chunks, n, chunk, ...), float32
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, fill)) + ((0, 0),) * (x.ndim - 2))
        return jnp.swapaxes(x.reshape(n, chunks, chunk, *x.shape[2:]), 0, 1)

    q, k, v, g, beta = cut(q), cut(k), cut(v), cut(g), cut(beta)
    cum = jnp.cumsum(g, axis=-2)
    t, i = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    rows, keys = _sub_block_factors(cum, most)
    keys = (k[..., None, :, :] * keys).astype(dtype)
    a = jnp.where(i < t, _pairwise(k, keys, rows, dtype), 0.0) * beta[..., None]
    p = jnp.where(i <= t, _pairwise(q, keys, rows, dtype), 0.0)
    inverse = _unit_lower_inverse(a).astype(dtype)
    decayed = jnp.exp(cum)
    k_in = k * decayed  # what a key reads of the chunk's first state
    dot = lambda eq, x, y: jnp.einsum(  # noqa: E731
        eq, x.astype(dtype), y.astype(dtype), preferred_element_type=jnp.float32
    )
    u_v = dot("...ti,...iv->...tv", inverse, beta[..., None] * v)
    u_k = dot("...ti,...ik->...tk", inverse, beta[..., None] * k_in)
    q_in = q * decayed
    last = cum[..., -1:, :]
    k_out = k * jnp.exp(last - cum)  # what a key leaves in the chunk's last state

    def step(state, xs):
        u_v, u_k, p, q_in, k_out, last = xs
        u = u_v - dot("ntk,nkv->ntv", u_k, state)
        out = dot("ntk,nkv->ntv", q_in, state) + dot("nti,niv->ntv", p, u)
        state = jnp.exp(last[:, 0])[:, :, None] * state + dot("ntk,ntv->nkv", k_out, u)
        return state, out

    start = jnp.zeros((n, dk, dv), jnp.float32)
    _, out = jax.lax.scan(step, start, (u_v, u_k, p, q_in, k_out, last))
    return jnp.swapaxes(out, 0, 1).reshape(n, chunks * chunk, dv)[:, :s]
