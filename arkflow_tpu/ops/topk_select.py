"""The indexer's choice without a sort: an exact k-th-score threshold.

An indexed layer attends, for each query, the ``k`` keys of largest index
score among those not after it, of equal scores the earlier position. What
the latent kernel reads is that choice as a mask over the context, so the
program needs one order statistic and one tie position a query, not an
ordering: a tile of rows stays in VMEM while the kernel finds, bit by bit
from the top, the largest score that ``k`` keys reach (32 passes of compare
and count over the scores' order-preserving integer keys), then the position
up to which that score's holders fill what is left (one pass a position bit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: rows of scores a program of ``dsa_topk_select`` holds at most (at a
#: 12,544-key context 1.6 MB each of scores, keys and mask, the first and the
#: last double-buffered: 8 MB of VMEM; on a v5e 48 us a call of 32 rows
#: against 73 at 8, a chunk tile of 64 rows 73 against 124: PERF.md PR 47)
_SELECT_ROWS = 32


def _select_kernel(pos_ref, s_ref, o_ref, key_ref, *, k: int, bits: int):
    rows, ctx = s_ref.shape
    pos = pos_ref[...]                                             # [rows, 1]

    def col():
        return jax.lax.broadcasted_iota(jnp.int32, (rows, ctx), 1)

    def seen():
        return col() <= pos

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

    few = jnp.max(pos) < k

    @pl.when(few)
    def _every_seen_key():
        o_ref[...] = seen().astype(jnp.float32)

    @pl.when(jnp.logical_not(few))
    def _search():
        # a key after the query is below every score, as the sort had it
        u = jax.lax.bitcast_convert_type(
            jnp.where(seen(), s_ref[...], -jnp.inf), jnp.int32)
        # integers in the floats' total order (-0.0 below +0.0: top_k's own)
        key_ref[...] = u ^ ((u >> 31) & 0x7FFFFFFF)

        def score_bit(i, t):       # count(key >= t) >= k holds throughout
            cand = t ^ jnp.left_shift(jnp.int32(1), 31 - i)
            return jnp.where(count(key_ref[...] >= cand) >= k, cand, t)

        t = jax.lax.fori_loop(
            0, 32, score_bit,
            jnp.full((rows, 1), jnp.iinfo(jnp.int32).min, jnp.int32))
        key = key_ref[...]
        above = key > t
        need = k - count(above)                        # >= 1 of the equals
        # the scratch turns to the equals' positions (others past every
        # position); those above wait in the output block meanwhile
        o_ref[...] = above.astype(jnp.float32)
        key_ref[...] = jnp.where(key == t, col(), 1 << bits)

        def position_bit(i, p):    # count(equals before p) < need holds
            cand = p | jnp.left_shift(jnp.int32(1), bits - 1 - i)
            return jnp.where(count(key_ref[...] < cand) < need, cand, p)

        p = jax.lax.fori_loop(0, bits, position_bit,
                              jnp.zeros((rows, 1), jnp.int32))
        chosen = (o_ref[...] > 0.0) | (key_ref[...] <= p)
        o_ref[...] = (chosen & seen()).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def dsa_topk_select(scores, positions, *, k: int, interpret: bool = False):
    """The ``k`` largest of each query's scores as a mask over the context.

    scores: float32 [B, C, context], positions: int32 [B, C] (query (b, i)
    sits at ``positions[b, i]`` and scores of keys after it are not read).
    Returns float32 [B, C, context], 1 at the ``min(k, position + 1)`` keys
    at or before the query of largest score, of equal scores the earlier
    position: the entries ``jax.lax.top_k`` of the scores masked to ``-inf``
    past the query names, exactly (its order is the floats' total order:
    ``-0.0`` below ``+0.0``, on the CPU and on a v5e alike)."""
    b, c, ctx = scores.shape
    k = min(k, ctx)
    n = b * c
    rows = min(_SELECT_ROWS, -(-n // 8) * 8)
    n_pad, ctx_pad = -(-n // rows) * rows, -(-ctx // 128) * 128
    # padded keys sit after every query, padded rows at position 0
    s = jnp.pad(scores.astype(jnp.float32).reshape(n, ctx),
                ((0, n_pad - n), (0, ctx_pad - ctx)))
    pos = jnp.pad(jnp.asarray(positions, jnp.int32).reshape(n, 1),
                  ((0, n_pad - n), (0, 0)))
    from jax.experimental.pallas import tpu as pltpu

    out = pl.pallas_call(
        functools.partial(_select_kernel, k=k,
                          bits=max(1, (ctx_pad - 1).bit_length())),
        grid=(n_pad // rows,),
        in_specs=[pl.BlockSpec((rows, 1), lambda i: (i, 0)),
                  pl.BlockSpec((rows, ctx_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, ctx_pad), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, ctx_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((rows, ctx_pad), jnp.int32)],
        interpret=interpret,
        name="dsa_topk_select",
    )(pos, s)
    return out[:n, :ctx].reshape(b, c, ctx)
