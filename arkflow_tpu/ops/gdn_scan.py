"""The gated delta rule over a pool of per-sequence matrix states.

A Gated DeltaNet head (Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464) carries a matrix ``S`` [d_key, d_value] per sequence and
rewrites it at every token:

    S <- exp(g_t) S            the gate's decay (g_t <= 0)
    u  = S^T k_t               what the state returns for this key
    d  = beta_t (v_t - u)      the delta: how far the value is from it
    S <- S + k_t (x) d
    o_t = S^T q_t

float32 arithmetic throughout, and the pool is float32 as served: ``S`` is an
accumulator over the whole sequence. The states live in a pool ``[layers,
rows, heads, d_key, d_value]`` beside the K/V pages (``models/paged_decode.
cache_spec``: kind ``gdn``); row 0 is scratch, as page 0 is. ``d_key`` sits
on the second-minor axis (sublanes) and ``d_value`` on the minor one (lanes):
``v``, ``d`` and ``o`` are rows, ``k`` and ``q`` columns (``ops/ssm_scan``'s
conventions).

Two steps, each as a Pallas kernel that reads and writes the pool IN PLACE
(``input_output_aliases``; the layer and the rows ride in the block index)
and as the plain ``jax.numpy`` form the tests hold it to (and
``decode_kernel: gather`` serves with):

* ``gdn_state_update`` — one token a lane (a decode step). The equations
  touch the state twice (``S^T k`` before the write, ``S^T q`` after); the
  kernel reads a lane's state once and writes it once: with ``a = exp(g)``,
  ``u = a (S^T k)`` and ``o = a (S^T q) + (k . q) d``, so both contractions
  are taken of the block as it was read, while it sits in VMEM.
  Memory-bound: 2 x 2 MiB a lane a layer at 32 heads of 128 x 128.
* ``gdn_chunk_scan`` — a chunk of a prompt from the row's state to the row's
  state in the chunked (WY) form (Yang et al., arXiv:2406.06484, with the
  gate of arXiv:2412.06464). Inside a block of ``BLOCK`` = 64 tokens, with
  ``c`` the running sum of ``g``:

      A = strictly-lower((K_beta K^T) * exp(c_i - c_j))
      T = (I + A)^-1
      W = T (K_beta * exp(c)),  U = T V_beta

  then block by block ``V' = U - W S``, ``O = (Q * exp(c)) S + ((Q K^T) *
  exp(c_i - c_j) * lower) V'``, ``S <- exp(c_last) S + (K * exp(c_last -
  c))^T V'``. ``T`` is taken by products: ``A`` is strictly lower, so
  ``A^64 = 0`` and ``(I + A)^-1 = (I - A)(I + A^2)(I + A^4)(I + A^8)(I +
  A^16)(I + A^32)`` exactly — ten 64-cubed products on the MXU where forward
  substitution is 64 dependent row steps.

``g = 0, beta = 0`` leaves a state untouched and adds nothing: that is how a
padded position and an idle lane are told (the caller zeroes both; an idle
lane also names row 0). ``fresh`` rows start from a zero state, whatever the
row held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
#: tokens of one block of the chunked form
BLOCK = 64
#: tokens a grid step of the chunk kernel takes: two blocks (a block of the
#: operands ends on whole 128-lane rows where tokens sit on the minor axis)
_STEP = 2 * BLOCK


# -- plain forms ----------------------------------------------------------------


def recurrent_from(s0, q, k, v, g, beta):
    """The recurrence token by token, as written above: ``s0`` [b, H, K, V];
    ``q`` / ``k`` [b, T, H, K]; ``v`` [b, T, H, V]; ``g`` / ``beta``
    [b, T, H]. Returns (o [b, T, H, V], the state after T tokens). What the
    chunked form is held to."""
    f32 = jnp.float32

    def step(s, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        s = jnp.exp(g_t)[..., None, None] * s
        u = jnp.einsum("bhkv,bhk->bhv", s, k_t, precision=_HI)
        d = b_t[..., None] * (v_t - u)
        s = s + k_t[..., :, None] * d[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision=_HI)

    s_t, o = jax.lax.scan(step, s0.astype(f32), tuple(
        jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s_t


def _inverse_unit_lower(a):
    """``(I + a)^-1`` of a strictly lower ``a`` [..., n, n], n a power of
    two: ``(I - a)(I + a^2)(I + a^4)...``, exact since ``a^n = 0``."""
    n = a.shape[-1]
    mm = functools.partial(jnp.matmul, precision=_HI)
    p, power, reach = jnp.eye(n, dtype=a.dtype) - a, a, 2
    while reach < n:
        power = mm(power, power)
        p, reach = p + mm(p, power), 2 * reach
    return p


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
    qb, kb, vb = (jnp.moveaxis(a.reshape(b, nb, BLOCK, h, -1), (1, 3), (0, 2))
                  for a in (q, k, v))
    gb, bb = (jnp.moveaxis(a.reshape(b, nb, BLOCK, h), (1, 3), (0, 2))
              for a in (g, beta))
    c = jnp.cumsum(gb, axis=-1)                                   # [n, b, H, B]
    lower = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    decay = jnp.exp(jnp.where(lower, c[..., :, None] - c[..., None, :], -jnp.inf))
    mm = functools.partial(jnp.matmul, precision=_HI)
    kk = mm(kb, jnp.swapaxes(kb, -1, -2))
    a = jnp.where(jnp.tril(lower, -1), bb[..., None] * kk * decay, 0.0)
    tinv = _inverse_unit_lower(a)
    w = mm(tinv, kb * (bb * jnp.exp(c))[..., None])
    u = mm(tinv, vb * bb[..., None])
    qk = mm(qb, jnp.swapaxes(kb, -1, -2)) * decay
    last = c[..., -1]

    def block(s, xs):
        q_c, k_c, w_c, u_c, qk_c, c_c, last_c = xs
        v_new = u_c - mm(w_c, s)
        o = jnp.exp(c_c)[..., None] * mm(q_c, s) + mm(qk_c, v_new)
        s = jnp.exp(last_c)[..., None, None] * s + mm(
            jnp.swapaxes(k_c * jnp.exp(last_c[..., None] - c_c)[..., None], -1, -2),
            v_new)
        return s, o

    s_t, o = jax.lax.scan(block, s0.astype(f32), (qb, kb, w, u, qk, c, last))
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


def _head_block(heads: int, most: int) -> int:
    """Heads a grid step takes: the largest power of two up to ``most``
    that divides them."""
    return next(n for n in (16, 8, 4, 2, 1) if n <= most and heads % n == 0)


def _update_kernel(rows_ref, s_ref, keep_ref, bv_ref, beta_ref, kq_ref, o_ref,
                   s_out, *, hb: int):
    del rows_ref
    # k and q arrive as rows (a column operand [K, 1] a head would be padded
    # to whole 128-lane rows in HBM: 64 x its bytes); one transpose a grid
    # step turns the block's rows into the columns the products read
    cols = kq_ref[...].T                                          # [K, 2 hb]
    for h in range(hb):
        s = s_ref[h].astype(jnp.float32)                          # [K, V]
        k_col, q_col = cols[:, h:h + 1], cols[:, hb + h:hb + h + 1]
        keep = keep_ref[h:h + 1, :]                               # [1, V]
        # both contractions of the block as read: u = a (S^T k), and
        # o = (a S + k (x) d)^T q = a (S^T q) + (k . q) d
        d = bv_ref[h:h + 1, :] - beta_ref[h:h + 1, :] * keep * jnp.sum(
            s * k_col, axis=0, keepdims=True)
        s_out[h] = (keep * s + k_col * d).astype(s_out.dtype)
        o_ref[h:h + 1, :] = (keep * jnp.sum(s * q_col, axis=0, keepdims=True)
                             + jnp.sum(k_col * q_col, axis=0, keepdims=True) * d)


def _update_pallas(state, layer, rows, q, k, v, g, beta, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers, n_rows, h, dk, dv = state.shape
    b = q.shape[0]
    hb = _head_block(h, 16)
    keep = jnp.broadcast_to(jnp.exp(g)[..., None], (b, h, dv))
    wide = jnp.broadcast_to(beta[..., None], (b, h, dv))
    # a grid step's rows: its heads' keys, then their queries
    kq = jnp.concatenate([k.reshape(b, h // hb, hb, dk),
                          q.reshape(b, h // hb, hb, dk)], axis=2)  # [b, H/hb, 2 hb, K]
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
            pl.BlockSpec((None, hb, dv), lane),
            pl.BlockSpec((None, None, 2 * hb, dk),
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
        name="gdn_state_update",
    )(at, state.reshape(layers * n_rows, h, dk, dv), keep, wide * v, wide, kq)
    return o, pool.reshape(state.shape)


def gdn_state_update(state, layer, rows, q, k, v, g, beta, *,
                     kernel: bool = False, interpret: bool = False):
    """One token a lane. ``state`` [layers, rows, H, K, V] float32 (the whole
    pool); ``layer`` a scalar; ``rows`` [b] int32, the pool row of each lane
    (0: scratch); ``q`` / ``k`` [b, H, K] (normalised, ``q`` scaled); ``v``
    [b, H, V]; ``g`` [b, H] (<= 0; the log of the decay) and ``beta`` [b, H]
    (both 0: the lane's state stays as it is). All float32. Returns (o
    [b, H, V], the pool with the rows advanced)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if kernel:
        return _update_pallas(state, layer, rows, q, k, v, g, beta, interpret)
    return _update_plain(state, layer, rows, q, k, v, g, beta)


# -- the chunk scan -----------------------------------------------------------------


def _scan_kernel(rows_ref, fresh_ref, s_in, col_ref, row_ref, q_ref, k_ref,
                 v_ref, kt_ref, o_ref, s_out, s_scr, *, hb: int):
    from jax.experimental import pallas as pl

    del rows_ref
    i, c = pl.program_id(0), pl.program_id(2)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)
    nt = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=_HI)       # x y^T

    @pl.when(c == 0)
    def _start():
        s_scr[...] = jnp.where(fresh_ref[i] != 0, 0.0,
                               s_in[...].astype(jnp.float32))

    n = BLOCK
    t_i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    eye = (t_i == s_i).astype(jnp.float32)
    dk, dv = s_scr.shape[1], s_scr.shape[2]
    for h in range(hb):
        kcols, vcols = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        for blk in range(_STEP // n):
            toks = slice(blk * n, (blk + 1) * n)
            c_col, b_col = col_ref[h, toks, 0:1], col_ref[h, toks, 1:2]  # [n, 1]
            c_row = row_ref[h, :, toks]                           # [1, n]
            q, k, v = q_ref[toks, kcols], k_ref[toks, kcols], v_ref[toks, vcols]
            decay = jnp.exp(jnp.where(t_i >= s_i, c_col - c_row, -jnp.inf))
            a = jnp.where(t_i > s_i, b_col * nt(k, k) * decay, 0.0)
            p, power, reach = eye - a, a, 2
            while reach < n:                 # (I + A)^-1, A^n = 0
                power = dot(power, power)
                p, reach = p + dot(p, power), 2 * reach
            e_col = jnp.exp(c_col)
            s = s_scr[h]                                          # [K, V]
            v_new = dot(p, v * b_col) - dot(dot(p, k * (b_col * e_col)), s)
            o_ref[toks, vcols] = e_col * dot(q, s) + dot(nt(q, k) * decay, v_new)
            # the block's whole decay, as rows (a [1, 1] value is not
            # broadcast over sublanes and lanes at once on a chip)
            last = c_col[n - 1:n, :]
            s_scr[h] = jnp.exp(jnp.broadcast_to(last, (1, dv))) * s + dot(
                kt_ref[h, :, toks] * jnp.exp(jnp.broadcast_to(last, (1, n)) - c_row),
                v_new)

    @pl.when(c == pl.num_programs(2) - 1)
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
    nc = t // _STEP
    hb = _head_block(h, 8)
    # the running sum of g within each block of the chunked form
    cs = jnp.cumsum(g.reshape(b, t // BLOCK, BLOCK, h), axis=2).reshape(b, t, h)
    cs = jnp.moveaxis(cs, 1, 2)                                   # [b, H, T]
    col = jnp.stack([cs, jnp.moveaxis(beta, 1, 2)], axis=-1)      # [b, H, T, 2]
    at = jnp.asarray(rows, jnp.int32) + jnp.asarray(layer, jnp.int32) * n_rows

    def row(i, j, c, at_ref, fresh_ref):
        return (at_ref[i], j, 0, 0)

    def tokens(i, j, c, *_):
        return (i, c, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb, nc),
        in_specs=[
            pl.BlockSpec((None, hb, dk, dv), row),
            pl.BlockSpec((None, hb, _STEP, 2), lambda i, j, c, *_: (i, j, c, 0)),
            pl.BlockSpec((None, hb, 1, _STEP), lambda i, j, c, *_: (i, j, 0, c)),
            pl.BlockSpec((None, _STEP, hb * dk), tokens),
            pl.BlockSpec((None, _STEP, hb * dk), tokens),
            pl.BlockSpec((None, _STEP, hb * dv), tokens),
            pl.BlockSpec((None, hb, dk, _STEP), lambda i, j, c, *_: (i, j, 0, c)),
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
        name="gdn_chunk_scan",
    )(at, jnp.asarray(fresh, jnp.int32),
      state.reshape(layers * n_rows, h, dk, dv), col, cs[:, :, None, :],
      q.reshape(b, t, h * dk), k.reshape(b, t, h * dk), v.reshape(b, t, h * dv),
      jnp.transpose(k, (0, 2, 3, 1)))
    return o.reshape(b, t, h, dv)[:, :t - pad], pool.reshape(state.shape)


def gdn_chunk_scan(state, layer, rows, fresh, q, k, v, g, beta, *,
                   kernel: bool = False, interpret: bool = False):
    """A chunk of T tokens a row, from the row's state to the row's state.
    ``state``, ``layer``, ``rows`` [b] as ``gdn_state_update``; ``fresh``
    [b] bool: start from a zero state; ``q`` / ``k`` [b, T, H, K]; ``v``
    [b, T, H, V]; ``g`` / ``beta`` [b, T, H] (both 0 at a padded position:
    the state passes it by). Returns (o [b, T, H, V], the pool with the
    rows advanced)."""
    q, k, v, g, beta = (a.astype(jnp.float32) for a in (q, k, v, g, beta))
    if kernel:
        return _scan_pallas(state, layer, rows, fresh, q, k, v, g, beta, interpret)
    return _scan_plain(state, layer, rows, fresh, q, k, v, g, beta)
