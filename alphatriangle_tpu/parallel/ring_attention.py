"""Sequence/context-parallel attention: ring and all-to-all (Ulysses).

The reference has no long-context capability at all — its transformer
attends over a <=few-hundred-token spatial sequence on one device
(`alphatriangle/nn/model.py:179-202,283-288`; SURVEY.md §5 "Long-context
/ sequence parallelism: absent"). This module makes sequence length a
*sharding* dimension instead of a ceiling, the TPU-native way:

- **Ring attention** (`ring_attention`): each device on the `sp` mesh
  axis holds a sequence shard of Q, K, V. K/V blocks rotate around the
  ICI ring with `lax.ppermute` while each device folds every block into
  a numerically-stable online softmax (flash-attention style running
  max / normalizer / weighted accumulator). Full bidirectional
  attention is computed without any device ever materializing the
  (S, S) score matrix or the full K/V — memory per device is
  O(S/n * S/n) per block pair, communication is the K/V shards
  streaming over ICI, overlapping compute.
- **Ulysses / all-to-all attention** (`ulysses_attention`): one
  `lax.all_to_all` reshards from sequence-sharded to head-sharded,
  every device computes dense attention over the FULL sequence for its
  head subset, and a second all-to-all reshards back. Cheaper when
  head_count >= sp and the sequence fits one device's HBM; ring wins
  when it doesn't.

Both are pure shard-level functions used inside `shard_map` over the
`MeshConfig` `sp` axis; `make_sp_attention` builds a drop-in
`attention_fn` for `flax.linen.MultiHeadDotProductAttention` (the
model's transformer accepts it via `AlphaTriangleNet.attention_fn`), so
the same network code runs single-device or sequence-sharded with no
change. Equivalence with dense attention (forward and gradients) is
pinned by tests/test_ring_attention.py on the virtual 8-device mesh.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import Array
from jax.sharding import Mesh, PartitionSpec as P


def _fold_block(
    q: Array, k: Array, v: Array, m: Array, l: Array, o: Array, scale: float
) -> tuple[Array, Array, Array]:
    """Fold one K/V block into the online-softmax accumulators.

    q: (B, Sq, H, D); k, v: (B, Sk, H, D).
    m (running max), l (running normalizer): (B, H, Sq) float32.
    o (unnormalized weighted values): (B, Sq, H, D) float32.
    """
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    s = s.astype(jnp.float32) * scale
    m_new = jnp.maximum(m, s.max(axis=-1))
    # exp(-inf - -inf) would be NaN, but m_new is finite whenever any
    # key exists in the block (bidirectional, no masking), and m only
    # equals -inf before the first block where alpha multiplies zeros.
    alpha = jnp.exp(m - m_new)  # (B, H, Sq)
    p = jnp.exp(s - m_new[..., None])  # (B, H, Sq, Sk)
    l = l * alpha + p.sum(axis=-1)
    pv = jnp.einsum(
        "bhqk,bkhd->bqhd",
        p,
        v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )
    o = o * alpha.transpose(0, 2, 1)[..., None] + pv
    return m_new, l, o


def ring_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    axis_name: str,
    n_shards: int,
    scale: float | None = None,
) -> Array:
    """Bidirectional ring attention over a sequence-sharded axis.

    Shard-level function (call inside `shard_map`): q, k, v are this
    device's (B, S_local, H, D) sequence shards; the return is the
    (B, S_local, H, D) attention output for the local queries against
    the GLOBAL sequence. K/V rotate `n_shards` hops around the
    `axis_name` ring via `ppermute`; accumulation is float32 online
    softmax regardless of input dtype.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    b, sq, h, _ = q.shape
    m = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l = jnp.zeros((b, h, sq), jnp.float32)
    o = jnp.zeros(q.shape, jnp.float32)
    perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]

    # Fold the local block first, then permute-and-fold n_shards-1
    # times: every fold sees the K/V block it needs and the last block
    # is NOT permuted onward afterwards (a trailing ppermute would be
    # pure dead ICI traffic unless XLA happens to DCE it).
    m, l, o = _fold_block(q, k, v, m, l, o, scale)

    def hop(_, carry):
        m, l, o, k, v = carry
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        m, l, o = _fold_block(q, k, v, m, l, o, scale)
        return m, l, o, k, v

    m, l, o, k, v = jax.lax.fori_loop(
        0, n_shards - 1, hop, (m, l, o, k, v), unroll=True
    )
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _dense_attention(q: Array, k: Array, v: Array, scale: float) -> Array:
    """Plain softmax(QK^T)V with float32 accumulation, (B, S, H, D)."""
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    )
    w = jax.nn.softmax(s.astype(jnp.float32) * scale, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd",
        w,
        v.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def ulysses_attention(
    q: Array,
    k: Array,
    v: Array,
    *,
    axis_name: str,
    scale: float | None = None,
) -> Array:
    """All-to-all (Ulysses-style) sequence-parallel attention.

    Shard-level function: reshards (B, S_local, H, D) -> full sequence
    with a head subset (B, S, H_local, D) via one `all_to_all`, runs
    dense attention locally, and reshards back. Requires the head count
    to be divisible by the sp axis size.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])

    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)  # (B, S, H_loc, D)
    out = _dense_attention(qh, kh, vh, scale)
    return jax.lax.all_to_all(
        out, axis_name, split_axis=1, concat_axis=2, tiled=True
    )


def make_sp_attention(
    mesh: Mesh,
    kind: str = "ring",
    sp_axis: str = "sp",
    dp_axis: str | None = "dp",
):
    """Build a sequence-sharded `attention_fn` for the model's
    transformer (drop-in for `flax.linen.dot_product_attention`).

    Inputs/outputs are global (B, S, H, D) arrays; batch is sharded on
    `dp_axis` (pass None to replicate it) and sequence on `sp_axis`.
    Attention-weight dropout is not supported (like most blockwise
    attention implementations); the caller must be deterministic or use
    zero attention dropout.
    """
    n = mesh.shape[sp_axis]
    spec = P(dp_axis, sp_axis, None, None)
    if kind == "ring":
        inner = functools.partial(
            ring_attention, axis_name=sp_axis, n_shards=n
        )
    elif kind == "ulysses":
        inner = functools.partial(ulysses_attention, axis_name=sp_axis)
    else:
        raise ValueError(f"Unknown sequence-parallel kind: {kind!r}")

    sharded = jax.shard_map(
        lambda q, k, v: inner(q, k, v),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    dp_total = mesh.shape[dp_axis] if dp_axis is not None else 1

    # dropout_rate/deterministic MUST be named parameters, not **kwargs:
    # flax's MultiHeadDotProductAttention filters the kwargs it forwards
    # to an attention_fn by inspecting its signature, so a **kwargs
    # catch-all would never receive them and the guard below would be
    # dead code on the real integration path.
    def attention_fn(
        query,
        key,
        value,
        bias=None,
        mask=None,
        dropout_rate=0.0,
        deterministic=True,
        **kwargs,
    ):
        if bias is not None or mask is not None:
            raise NotImplementedError(
                "sequence-parallel attention does not support bias/mask"
            )
        if kind == "ulysses" and query.shape[2] % n:
            raise ValueError(
                f"ulysses attention needs head count ({query.shape[2]}) "
                f"divisible by the sp axis size ({n}); use kind='ring'"
            )
        if dropout_rate and not deterministic:
            raise NotImplementedError(
                "sequence-parallel attention does not support attention-"
                "weight dropout; set ATTENTION_DROPOUT=0 or eval mode"
            )
        b, s = query.shape[0], query.shape[1]
        if b % dp_total or s % n:
            # Shapes that don't tile the mesh (e.g. the batch-1 dummy
            # of model.init) compute densely instead: identical math
            # (equivalence pinned by tests), just not sequence-sharded
            # for this call. Trace-time decision — shapes are static.
            return _dense_attention(
                query, key, value, 1.0 / math.sqrt(query.shape[-1])
            )
        return sharded(query, key, value)

    return attention_fn
