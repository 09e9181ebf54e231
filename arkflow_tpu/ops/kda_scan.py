"""The delta rule with a decay a key CHANNEL over a pool of matrix states.

A Kimi Delta Attention head (Kimi Linear, arXiv:2510.26692) carries a matrix
``S`` [d_key, d_value] per sequence, as a Gated DeltaNet head does
(``ops/gdn_scan``: the pool, its layout, rows, ``fresh`` and the padding rule
``g = 0, beta = 0`` are that file's), and forgets by a gate of its own for
every key channel:

    S <- Diag(exp(g_t)) S      g_t [d_key] <= 0: row k of S times exp(g_t[k])
    u  = S^T k_t
    d  = beta_t (v_t - u)
    S <- S + k_t (x) d
    o_t = S^T q_t

The decay stands INSIDE every contraction over the key channels, so neither
of ``gdn_scan``'s kernels computes it (one ``exp(g)`` a head there: a row
broadcast along the value lanes in the update, a scalar factored out of the
key contraction in the chunked form). Two steps again, each a Pallas kernel
that reads and writes the pool IN PLACE and a plain ``jax.numpy`` form:

* ``kda_state_update`` — one token a lane. With ``a = exp(g)`` a column,
  ``u = S^T (a * k)`` and ``o = S^T (a * q) + (k . q) d``, ``S <- a * S + k
  (x) d``: both contractions of the block as read, a lane's state read once
  and written once.
* ``kda_chunk_scan`` — a chunk from the row's state to the row's state in the
  chunked (WY) form with per-channel cumulative decays ``c`` (the running sum
  of ``g`` within a block of ``BLOCK`` tokens, [n, d_key]):

      A[i, j] = beta_i sum_k k_i[k] k_j[k] exp(c_i[k] - c_j[k]),   i > j
      T = (I + A)^-1,  W = T (K_beta * exp(c)),  U = T V_beta
      V' = U - W S
      O = (Q * exp(c)) S + M V',   M[i, j] = sum_k q_i[k] k_j[k] exp(c_i[k]
                                              - c_j[k]),   i >= j
      S <- Diag(exp(c_last)) S + (K * exp(c_last - c))^T V'

  EVERY EXPONENT TAKEN IS OF A NUMBER <= 0. ``exp(-c_j)`` alone overflows
  float32 after a few tokens of a fast channel, so the pairwise ``exp(c_i -
  c_j)`` is never factored as ``exp(c_i) exp(-c_j)``: the pairs (i, j), i >
  j, of a block are split by the level at which a halving of the block parts
  them — i in the upper half and j in the lower half of one interval of
  ``BLOCK >> level`` tokens — and referred to ``r``, the cumulative decay at
  that interval's middle: ``exp(c_i - c_j) = exp(c_i - r) exp(r - c_j)`` with
  both exponents non-positive, so each level is ONE product of decayed
  operands under the level's mask (``_pairs``: log2(BLOCK) products a block
  where one served a decay a head). A factor that underflows belongs to a
  pair whose own decay underflows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from arkflow_tpu.ops.gdn_scan import BLOCK, _HI, _STEP, _head_block


# -- plain forms ----------------------------------------------------------------


def recurrent_from(s0, q, k, v, g, beta):
    """The recurrence token by token, as written above: ``s0`` [b, H, K, V];
    ``q`` / ``k`` / ``g`` [b, T, H, K]; ``v`` [b, T, H, V]; ``beta``
    [b, T, H]. Returns (o [b, T, H, V], the state after T tokens). What the
    chunked form is held to."""
    f32 = jnp.float32

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None] * s
        u = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HI)
        d = b_t[..., None] * (v_t - u)
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    s_t, o = jax.lax.scan(step, s0.astype(f32), tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s_t


def _pairs(q, k, c, dot, nt):
    """The two pairwise products of one block under per-channel decays, from
    ``q`` / ``k`` / ``c`` [n, K] (``c`` the running sum of ``g``): (``sum_k
    k_i k_j exp(c_i - c_j)`` for i > j, ``sum_k q_i k_j exp(c_i - c_j)`` for
    i >= j), each [n, n] and zero elsewhere. Level by level of a halving of
    the block (the module's docstring): no exponent above 0 is taken."""
    n = q.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    tok = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    kk = jnp.zeros((n, n), jnp.float32)
    qk = jnp.where(row == col, jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    span = n
    while span > 1:
        half = span // 2
        # the interval's middle: the last token of its lower half
        mid = (row & -span) + half - 1
        ref = dot((col == mid).astype(jnp.float32), c)            # [n, K]
        upper = (tok & half) != 0
        e_i = jnp.where(upper, jnp.exp(jnp.minimum(c - ref, 0.0)), 0.0)
        k_j = k * jnp.where(upper, 0.0, jnp.exp(jnp.minimum(ref - c, 0.0)))
        same = (row & -span) == (col & -span)
        kk = kk + jnp.where(same, nt(k * e_i, k_j), 0.0)
        qk = qk + jnp.where(same, nt(q * e_i, k_j), 0.0)
        span = half
    return kk, qk


def _block(s, q, k, v, c, beta, dot, nt):
    """One block of the chunked form for one head: ``s`` [K, V]; ``q`` / ``k``
    / ``c`` [n, K]; ``v`` [n, V]; ``beta`` [n, 1]. Returns (o [n, V], the
    state after the block). The same lines run in the kernel (on values read
    from its refs) and, vmapped, in the plain form."""
    n = q.shape[0]
    kk, qk = _pairs(q, k, c, dot, nt)
    a = beta * kk                                                 # strictly lower
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(jnp.float32)
    p, power, reach = eye - a, a, 2
    while reach < n:                        # (I + A)^-1, A^n = 0
        power = dot(power, power)
        p, reach = p + dot(p, power), 2 * reach
    e = jnp.exp(c)
    v_new = dot(p, v * beta) - dot(dot(p, k * (beta * e)), s)
    o = dot(q * e, s) + dot(qk, v_new)
    # the block's whole decay, a column a key channel: the last column of
    # c's transpose (a [1, K] row is not turned into a column on a chip)
    c_t = c.T                                                     # [K, n]
    last = c_t[:, n - 1:n]                                        # [K, 1]
    s = jnp.exp(last) * s + dot(k.T * jnp.exp(last - c_t), v_new)
    return o, s


def chunk_from(s0, q, k, v, g, beta):
    """The chunked form over explicit states, shapes as ``recurrent_from``:
    blocks of ``BLOCK`` tokens, a ragged tail padded with ``g = 0, beta =
    0`` (which moves nothing). Returns (o [b, T, H, V], the state after)."""
    f32 = jnp.float32
    b, t, h, _ = q.shape
    pad = -t % BLOCK
    q, k, v, g, beta = (
        jnp.pad(a.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    nb = (t + pad) // BLOCK
    # [blocks, b, H, BLOCK, *]
    qb, kb, vb, gb = (jnp.moveaxis(a.reshape(b, nb, BLOCK, h, -1), (1, 3), (0, 2))
                      for a in (q, k, v, g))
    bb = jnp.moveaxis(beta.reshape(b, nb, BLOCK, h, 1), (1, 3), (0, 2))
    cb = jnp.cumsum(gb, axis=-2)
    dot = functools.partial(jnp.matmul, precision=_HI)
    nt = lambda x, y: dot(x, y.T)  # noqa: E731
    one = functools.partial(_block, dot=dot, nt=nt)
    heads = jax.vmap(jax.vmap(one))                               # over b, H

    def block(s, xs):
        o, s = heads(s, *xs)
        return s, o

    s_t, o = jax.lax.scan(block, s0.astype(f32), (qb, kb, vb, cb, bb))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, t + pad, h, -1)
    return o[:, :t], s_t


def _update_plain(state, layer, rows, q, k, v, g, beta):
    s = state[layer, rows].astype(jnp.float32)                    # [b, H, K, V]
    o, s = recurrent_from(s, *(a[:, None] for a in (q, k, v, g, beta)))
    return o[:, 0], state.at[layer, rows].set(s.astype(state.dtype))


def _scan_plain(state, layer, rows, fresh, q, k, v, g, beta):
    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                   state[layer, rows].astype(jnp.float32))
    o, s_t = chunk_from(s0, q, k, v, g, beta)
    return o, state.at[layer, rows].set(s_t.astype(state.dtype))


# -- the decode update ------------------------------------------------------------


def _update_kernel(rows_ref, s_ref, bv_ref, beta_ref, kqa_ref, o_ref, s_out,
                   *, hb: int):
    del rows_ref
    # k, q and the decay arrive as rows (``gdn_scan._update_kernel`` says
    # why); one transpose a grid step turns them into the columns the
    # products read
    cols = kqa_ref[...].T                                         # [K, 3 hb]
    for h in range(hb):
        s = s_ref[h].astype(jnp.float32)                          # [K, V]
        k_col, q_col = cols[:, h:h + 1], cols[:, hb + h:hb + h + 1]
        a_col = cols[:, 2 * hb + h:2 * hb + h + 1]                # exp(g): [K, 1]
        # both contractions of the block as read: u = S^T (a k), and
        # o = (a S + k (x) d)^T q = S^T (a q) + (k . q) d
        d = bv_ref[h:h + 1, :] - beta_ref[h:h + 1, :] * jnp.sum(
            s * (a_col * k_col), axis=0, keepdims=True)
        s_out[h] = (a_col * s + k_col * d).astype(s_out.dtype)
        o_ref[h:h + 1, :] = (
            jnp.sum(s * (a_col * q_col), axis=0, keepdims=True)
            + jnp.sum(k_col * q_col, axis=0, keepdims=True) * d)


def _update_pallas(state, layer, rows, q, k, v, g, beta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers, n_rows, h, dk, dv = state.shape
    b = q.shape[0]
    hb = _head_block(h, 16)
    wide = jnp.broadcast_to(beta[..., None], (b, h, dv))
    # a grid step's rows: its heads' keys, their queries, their decays
    kqa = jnp.concatenate([a.reshape(b, h // hb, hb, dk)
                           for a in (k, q, jnp.exp(g))], axis=2)  # [b, H/hb, 3 hb, K]
    # the layer rides in the row index: the pool is one run of layers * rows
    at = jnp.asarray(rows, jnp.int32) + jnp.asarray(layer, jnp.int32) * n_rows

    def lane(i, j, at_ref):
        return (i, j, 0)

    def row(i, j, at_ref):
        return (at_ref[i], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((None, hb, dk, dv), row),
            pl.BlockSpec((None, hb, dv), lane),
            pl.BlockSpec((None, hb, dv), lane),
            pl.BlockSpec((None, None, 3 * hb, dk),
                         lambda i, j, at_ref: (i, j, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((None, hb, dv), lane),
                   pl.BlockSpec((None, hb, dk, dv), row)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, dv), jnp.float32),
                   jax.ShapeDtypeStruct((layers * n_rows, h, dk, dv), state.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_state_update",
    )(at, state.reshape(layers * n_rows, h, dk, dv), wide * v, wide, kqa)
    return o, pool.reshape(state.shape)


def kda_state_update(state, layer, rows, q, k, v, g, beta, *,
                     kernel: bool = False, interpret: bool = False):
    """One token a lane. ``state`` [layers, rows, H, K, V] float32 (the whole
    pool); ``layer`` a scalar; ``rows`` [b] int32, the pool row of each lane
    (0: scratch); ``q`` / ``k`` [b, H, K] (normalised, ``q`` scaled); ``v``
    [b, H, V]; ``g`` [b, H, K] (<= 0; the log of the decay a key channel)
    and ``beta`` [b, H] (both 0: the lane's state stays as it is). All
    float32. Returns (o [b, H, V], the pool with the rows advanced)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if kernel:
        return _update_pallas(state, layer, rows, q, k, v, g, beta, interpret)
    return _update_plain(state, layer, rows, q, k, v, g, beta)


# -- the chunk scan -----------------------------------------------------------------


def _scan_kernel(rows_ref, fresh_ref, s_in, beta_ref, q_ref, k_ref, v_ref,
                 c_ref, o_ref, s_out, s_scr, *, hb: int):
    from jax.experimental import pallas as pl

    del rows_ref
    i, step = pl.program_id(0), pl.program_id(2)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)
    nt = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)       # x y^T

    @pl.when(step == 0)
    def _start():
        s_scr[...] = jnp.where(fresh_ref[i] != 0, 0.0,
                               s_in[...].astype(jnp.float32))

    dk, dv = s_scr.shape[1], s_scr.shape[2]
    for h in range(hb):
        kcols, vcols = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        for blk in range(_STEP // BLOCK):
            toks = slice(blk * BLOCK, (blk + 1) * BLOCK)
            o_ref[toks, vcols], s_scr[h] = _block(
                s_scr[h], q_ref[toks, kcols], k_ref[toks, kcols],
                v_ref[toks, vcols], c_ref[toks, kcols], beta_ref[h, toks, :],
                dot, nt)

    @pl.when(step == pl.num_programs(2) - 1)
    def _end():
        s_out[...] = s_scr[...].astype(s_out.dtype)


def _scan_pallas(state, layer, rows, fresh, q, k, v, g, beta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers, n_rows, h, dk, dv = state.shape
    b, t = q.shape[:2]
    pad = -t % _STEP
    q, k, v, g, beta = (
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        for a in (q, k, v, g, beta))
    t = t + pad
    hb = _head_block(h, 8)
    # the running sum of g within each block of the chunked form
    cs = jnp.cumsum(g.reshape(b, t // BLOCK, BLOCK, h, dk), axis=2)
    at = jnp.asarray(rows, jnp.int32) + jnp.asarray(layer, jnp.int32) * n_rows

    def row(i, j, c, at_ref, fresh_ref):
        return (at_ref[i], j, 0, 0)

    def tokens(i, j, c, *_):
        return (i, c, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb, t // _STEP),
        in_specs=[
            pl.BlockSpec((None, hb, dk, dv), row),
            pl.BlockSpec((None, hb, _STEP, 1), lambda i, j, c, *_: (i, j, c, 0)),
            pl.BlockSpec((None, _STEP, hb * dk), tokens),
            pl.BlockSpec((None, _STEP, hb * dk), tokens),
            pl.BlockSpec((None, _STEP, hb * dv), tokens),
            pl.BlockSpec((None, _STEP, hb * dk), tokens),
        ],
        out_specs=[pl.BlockSpec((None, _STEP, hb * dv), tokens),
                   pl.BlockSpec((None, hb, dk, dv), row)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
    )
    o, pool = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, t, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct((layers * n_rows, h, dk, dv), state.dtype)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="kda_chunk_scan",
    )(at, jnp.asarray(fresh, jnp.int32),
      state.reshape(layers * n_rows, h, dk, dv),
      jnp.moveaxis(beta, 1, 2)[..., None],
      q.reshape(b, t, h * dk), k.reshape(b, t, h * dk), v.reshape(b, t, h * dv),
      cs.reshape(b, t, h * dk))
    return o.reshape(b, t, h, dv)[:, :t - pad], pool.reshape(state.shape)


def kda_chunk_scan(state, layer, rows, fresh, q, k, v, g, beta, *,
                   kernel: bool = False, interpret: bool = False):
    """A chunk of T tokens a row, from the row's state to the row's state.
    ``state``, ``layer``, ``rows`` [b] as ``kda_state_update``; ``fresh``
    [b] bool: start from a zero state; ``q`` / ``k`` / ``g`` [b, T, H, K];
    ``v`` [b, T, H, V]; ``beta`` [b, T, H] (``g`` and ``beta`` 0 at a padded
    position: the state passes it by). Returns (o [b, T, H, V], the pool
    with the rows advanced)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if kernel:
        return _scan_pallas(state, layer, rows, fresh, q, k, v, g, beta, interpret)
    return _scan_plain(state, layer, rows, fresh, q, k, v, g, beta)
