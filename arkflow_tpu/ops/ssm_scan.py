"""The Mamba-2 recurrence over a pool of per-sequence states.

A state-space head carries a matrix ``S`` per sequence and overwrites it at
every token:

    S_t = exp(dt_t A) S_{t-1} + dt_t (B_t (x) x_t)        S: [d_state, d_head]
    y_t = C_t^T S_t                                        (the caller adds D x_t)

float32 arithmetic throughout, and the pool is float32 as served: ``S`` is
an accumulator over the whole sequence. The
states live in a pool ``[layers, rows, heads, d_state, d_head]`` beside the
K/V pages (``models/paged_decode.cache_spec``: kind ``ssm``); row 0 is
scratch, as page 0 is. ``d_state`` sits on the second-minor axis (sublanes)
and ``d_head`` on the minor one (lanes), so that the update needs ``x`` and
``y`` as rows and only the small ``B`` and ``C`` as columns: nothing is
relaid out on the way.

Two steps, each as a Pallas kernel that reads and writes the pool IN PLACE
(``input_output_aliases``; the layer and the rows ride in the block index,
so no layer's slab is sliced out) and as the plain ``jax.numpy`` form the
tests hold it to (and ``decode_kernel: gather`` serves with):

* ``ssm_state_update`` — one token a lane (a decode step): read ``S``,
  decay, add, contract with ``C``, write ``S``. Memory-bound: 2 x 4 MiB a
  lane a layer at Falcon-H1-34B's sizes.
* ``ssm_chunk_scan`` — a chunk of a prompt from the row's state to the
  row's state in Mamba-2's chunked form (Dao & Gu 2024, section 6): within
  a block of ``chunk`` tokens the output is a masked matrix product, between
  blocks the state is passed on.

A head NARROWER than the chip's 128 lanes (Nemotron-H: 64) is held
``heads_packed`` heads side by side on the pool's minor axis, ``[layers,
rows, heads / k, d_state, k * d_head]`` — a float32 array whose minor axis is
64 wide is tiled to 128 lanes anyway: twice the bytes, held and moved. The
heads of one row share B and C (``k`` divides a group's heads), so the decode
update's kernel runs on such a pool as it stands: its body decays, adds and
contracts lane by lane. The chunk scan multiplies a HEAD's own masked matrix,
so it takes the chunk's rows out of the pool a head each
(``unpack_state``), runs, and writes them back packed: a few MB a chunk.

``dt = 0`` leaves a state untouched and adds nothing: that is how a padded
position and an idle lane are told (the caller zeroes their ``dt``; an idle
lane also names row 0). ``fresh`` rows start from a zero state, whatever the
row held.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _by_head(m, heads: int):
    """[..., groups, n] -> [..., heads, n]: head h reads group h // (heads / groups)."""
    return jnp.repeat(m, heads // m.shape[-2], axis=-2)


def heads_packed(heads: int, groups: int, d_head: int) -> int:
    """Heads a row of the state pool holds side by side on its minor axis:
    as many as fill 128 lanes, where ``d_head`` divides them and a group's
    heads divide into such rows; else 1 (every head of 128 lanes or more)."""
    k = 128 // d_head if d_head < 128 and 128 % d_head == 0 else 1
    while k > 1 and (heads // groups) % k:
        k //= 2
    return k


def pack_state(s, k: int):
    """[..., H, N, P] -> [..., H / k, N, k P]: head ``h`` on lanes ``(h % k)
    P ..`` of row ``h // k`` (``heads_packed``); ``s`` itself where k is 1."""
    if k == 1:
        return s
    *lead, h, n, p = s.shape
    return jnp.moveaxis(s.reshape(*lead, h // k, k, n, p), -3, -2).reshape(
        *lead, h // k, n, k * p)


def unpack_state(s, k: int):
    """``pack_state``'s inverse: [..., H / k, N, k P] -> [..., H, N, P]."""
    if k == 1:
        return s
    *lead, hk, n, kp = s.shape
    return jnp.moveaxis(s.reshape(*lead, hk, n, k, kp // k), -2, -3).reshape(
        *lead, hk * k, n, kp // k)


# -- plain forms ----------------------------------------------------------------


def scan_from(s0, x, dt, a, bm, cmat, chunk: int):
    """The chunked form over explicit states: ``s0`` [b, H, N, P]; ``x``
    [b, T, H, P]; ``dt`` [b, T, H]; ``a`` [H] (negative); ``bm`` / ``cmat``
    [b, T, G, N]. Returns (y [b, T, H, P], the state after T tokens)."""
    b, t, h, p = x.shape
    q = chunk if t % chunk == 0 else t
    nc = t // q
    f32 = jnp.float32
    x, dt, bm, cmat = (v.astype(f32) for v in (x, dt, bm, cmat))
    acs = jnp.cumsum((dt * a).reshape(b, nc, q, h), axis=2)       # [b, c, q, h]
    xdt = (x * dt[..., None]).reshape(b, nc, q, h, p)
    bh = _by_head(bm, h).reshape(b, nc, q, h, -1)
    ch = _by_head(cmat, h).reshape(b, nc, q, h, -1)
    cb = jnp.einsum("bcqhn,bcshn->bchqs", ch, bh, precision=_HI)
    diff = acs[:, :, :, None, :] - acs[:, :, None, :, :]          # [b, c, q, s, h]
    causal = jnp.tril(jnp.ones((q, q), bool))[None, None, :, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    m = cb * jnp.moveaxis(decay, -1, 2)
    y = jnp.einsum("bchqs,bcshp->bcqhp", m, xdt, precision=_HI)
    # what each block adds to the state, and the state handed on
    w = jnp.exp(acs[:, :, -1:, :] - acs)                          # [b, c, q, h]
    add = jnp.einsum("bcqhn,bcqhp->bchnp", bh, xdt * w[..., None], precision=_HI)
    keep = jnp.exp(acs[:, :, -1, :])                              # [b, c, h]

    def carry(s, xs):
        add_c, keep_c = xs
        return keep_c[..., None, None] * s + add_c, s

    s_t, before = jax.lax.scan(
        carry, s0.astype(f32), (jnp.moveaxis(add, 1, 0), jnp.moveaxis(keep, 1, 0)))
    y = y + jnp.exp(acs)[..., None] * jnp.einsum(
        "bcqhn,cbhnp->bcqhp", ch, before, precision=_HI)
    return y.reshape(b, t, h, p), s_t


def _update_plain(state, layer, rows, x, dt, a, bm, cmat):
    h = x.shape[1]
    k = h // state.shape[2]
    s = unpack_state(state[layer, rows], k).astype(jnp.float32)   # [b, H, N, P]
    s = (jnp.exp(dt * a)[..., None, None] * s
         + _by_head(bm, h)[..., :, None] * (x * dt[..., None])[..., None, :])
    y = jnp.einsum("bhnp,bhn->bhp", s, _by_head(cmat, h), precision=_HI)
    return y, state.at[layer, rows].set(pack_state(s, k).astype(state.dtype))


def _scan_plain(state, layer, rows, fresh, x, dt, a, bm, cmat, chunk):
    s0 = jnp.where(fresh[:, None, None, None], 0.0,
                   state[layer, rows].astype(jnp.float32))
    y, s_t = scan_from(s0, x, dt, a, bm, cmat, chunk)
    return y, state.at[layer, rows].set(s_t.astype(state.dtype))


# -- the decode update ------------------------------------------------------------


def _head_block(heads: int, groups: int) -> int:
    """Heads a grid step takes: one group's (they share B and C), at most 16."""
    per = heads // groups
    return next(n for n in (16, 8, 4, 2, 1) if per % n == 0)


def _update_kernel(rows_ref, s_ref, keep_ref, xdt_ref, bc_ref, y_ref, o_ref, *,
                   hb: int):
    del rows_ref
    b_col, c_col = bc_ref[:, 0:1], bc_ref[:, 1:2]                 # [N, 1]
    for h in range(hb):
        s = (keep_ref[h:h + 1, :] * s_ref[h].astype(jnp.float32)
             + b_col * xdt_ref[h:h + 1, :])
        o_ref[h] = s.astype(o_ref.dtype)
        y_ref[h:h + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def _update_pallas(state, layer, rows, x, dt, a, bm, cmat, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers, n_rows, h, n, p = state.shape     # rows of ``heads_packed`` heads
    b, g = x.shape[0], bm.shape[1]
    hb = _head_block(h, g)
    per_group = h // g // hb
    keep = jnp.broadcast_to(jnp.exp(dt * a)[..., None], x.shape)
    bc = jnp.stack([bm, cmat], axis=-1)                           # [b, G, N, 2]
    # the layer rides in the row index: the pool is one run of layers * rows
    at = jnp.asarray(rows, jnp.int32) + jnp.asarray(layer, jnp.int32) * n_rows

    def lane(i, j, at_ref):
        return (i, j, 0)

    def row(i, j, at_ref):
        return (at_ref[i], j, 0, 0)

    lanes, lane_block = (b, h, p), pl.BlockSpec((None, hb, p), lane)
    if x.shape[1] != h and hb % 8 and hb != h:
        # packed rows, 4 a group: a block of fewer than 8 head rows does not
        # tile, so the rows ride on an axis of their own, whole (the block's
        # last two dims are then the array's); the kernel sees [hb, p] either
        # way. (A pool a head a row keeps the layout its programs were
        # recorded with: Falcon-H1's blocks are 16 rows.)
        lanes = (b, h // hb, hb, p)
        lane_block = pl.BlockSpec((None, None, hb, p),
                                  lambda i, j, at_ref: (i, j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h // hb),
        in_specs=[
            pl.BlockSpec((None, hb, n, p), row),
            lane_block,
            lane_block,
            pl.BlockSpec((None, None, n, 2),
                         lambda i, j, at_ref: (i, j // per_group, 0, 0)),
        ],
        out_specs=[lane_block,
                   pl.BlockSpec((None, hb, n, p), row)],
    )
    pool, xdt = state.reshape(layers * n_rows, h, n, p), x * dt[..., None]
    if keep.shape != lanes:  # heads side by side on the lanes: [b, H / k, k P]
        keep, xdt = keep.reshape(lanes), xdt.reshape(lanes)
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, hb=hb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(lanes, jnp.float32),
                   jax.ShapeDtypeStruct((layers * n_rows, h, n, p), state.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_state_update",
    )(at, pool, keep, xdt, bc)
    return y.reshape(x.shape), pool.reshape(state.shape)


def ssm_state_update(state, layer, rows, x, dt, a, bm, cmat, *,
                     kernel: bool = False, interpret: bool = False):
    """One token a lane. ``state`` [layers, rows, H, N, P] float32 (the whole
    pool); ``layer`` a scalar; ``rows`` [b] int32, the pool row of each lane
    (0: scratch); ``x`` [b, H, P]; ``dt`` [b, H] (0: the lane's state stays
    as it is); ``a`` [H] (negative); ``bm`` / ``cmat`` [b, G, N]. All
    float32. Returns (y [b, H, P], the pool with the rows advanced)."""
    f32 = jnp.float32
    x, dt, a, bm, cmat = (v.astype(f32) for v in (x, dt, a, bm, cmat))
    if kernel:
        return _update_pallas(state, layer, rows, x, dt, a, bm, cmat, interpret)
    return _update_plain(state, layer, rows, x, dt, a, bm, cmat)


# -- the chunk scan -----------------------------------------------------------------


def _scan_kernel(rows_ref, fresh_ref, s_in, acol, arow, keep_ref, xdt, xw, c_ref,
                 bt_ref, y_ref, s_out, s_scr, *, hb: int, q: int):
    from jax.experimental import pallas as pl

    del rows_ref
    i, c = pl.program_id(0), pl.program_id(2)
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=_HI)

    @pl.when(c == 0)
    def _start():
        s_scr[...] = jnp.where(fresh_ref[i] != 0, 0.0,
                               s_in[...].astype(jnp.float32))

    cmat, bt = c_ref[...], bt_ref[...]                            # [Q, N], [N, Q]
    cb = dot(cmat, bt)                                            # [Q, Q]
    t_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    p = s_scr.shape[2]
    for h in range(hb):
        a_col, a_row = acol[h], arow[h]                           # [Q, 1], [1, Q]
        cols = slice(h * p, (h + 1) * p)
        m = cb * jnp.exp(jnp.where(t_i >= s_i, a_col - a_row, -jnp.inf))
        s = s_scr[h]                                              # [N, P]
        y_ref[:, cols] = dot(m, xdt[:, cols]) + jnp.exp(a_col) * dot(cmat, s)
        s_scr[h] = keep_ref[h:h + 1, :] * s + dot(bt, xw[:, cols])

    @pl.when(c == pl.num_programs(2) - 1)
    def _end():
        s_out[...] = s_scr[...].astype(s_out.dtype)


def _scan_pallas(state, layer, rows, fresh, x, dt, a, bm, cmat, chunk, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    layers, n_rows, h, n, p = state.shape
    b, t, g = x.shape[0], x.shape[1], bm.shape[2]
    q = chunk if t % chunk == 0 else t
    nc = t // q
    hb = min(_head_block(h, g), 8)
    per_group = h // g // hb
    acs = jnp.cumsum((dt * a).reshape(b, nc, q, h), axis=2)
    w = jnp.exp(acs[:, :, -1:, :] - acs).reshape(b, t, h)
    keep = jnp.broadcast_to(jnp.exp(acs[:, :, -1, :])[..., None], (b, nc, h, p))
    acs = jnp.moveaxis(acs.reshape(b, t, h), 1, 2)                # [b, H, T]
    xdt = x * dt[..., None]
    at = jnp.asarray(rows, jnp.int32) + jnp.asarray(layer, jnp.int32) * n_rows

    def row(i, j, c, at_ref, fresh_ref):
        return (at_ref[i], j, 0, 0)

    def group(i, j, c, *_):
        return j // per_group

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hb, nc),
        in_specs=[
            pl.BlockSpec((None, hb, n, p), row),
            pl.BlockSpec((None, hb, q, 1), lambda i, j, c, *_: (i, j, c, 0)),
            pl.BlockSpec((None, hb, 1, q), lambda i, j, c, *_: (i, j, 0, c)),
            pl.BlockSpec((None, None, hb, p), lambda i, j, c, *_: (i, c, j, 0)),
            pl.BlockSpec((None, q, hb * p), lambda i, j, c, *_: (i, c, j)),
            pl.BlockSpec((None, q, hb * p), lambda i, j, c, *_: (i, c, j)),
            pl.BlockSpec((None, None, q, n),
                         lambda i, j, c, *_: (i, group(i, j, c), c, 0)),
            pl.BlockSpec((None, None, n, q),
                         lambda i, j, c, *_: (i, group(i, j, c), 0, c)),
        ],
        out_specs=[pl.BlockSpec((None, q, hb * p), lambda i, j, c, *_: (i, c, j)),
                   pl.BlockSpec((None, hb, n, p), row)],
        scratch_shapes=[pltpu.VMEM((hb, n, p), jnp.float32)],
    )
    y, pool = pl.pallas_call(
        functools.partial(_scan_kernel, hb=hb, q=q),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, t, h * p), jnp.float32),
                   jax.ShapeDtypeStruct((layers * n_rows, h, n, p), state.dtype)],
        input_output_aliases={2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
        name="ssm_chunk_scan",
    )(at, jnp.asarray(fresh, jnp.int32),
      state.reshape(layers * n_rows, h, n, p), acs[..., None],
      acs[:, :, None, :], keep, xdt.reshape(b, t, h * p),
      (xdt * w[..., None]).reshape(b, t, h * p), jnp.moveaxis(cmat, 1, 2),
      jnp.transpose(bm, (0, 2, 3, 1)))
    return y.reshape(b, t, h, p), pool.reshape(state.shape)


def ssm_chunk_scan(state, layer, rows, fresh, x, dt, a, bm, cmat, chunk: int, *,
                   kernel: bool = False, interpret: bool = False):
    """A chunk of T tokens a row, from the row's state to the row's state.
    ``state``, ``layer``, ``rows`` [b] as ``ssm_state_update``; ``fresh``
    [b] bool: start from a zero state; ``x`` [b, T, H, P]; ``dt`` [b, T, H]
    (0 at a padded position: the state passes it by); ``a`` [H]; ``bm`` /
    ``cmat`` [b, T, G, N]; ``chunk``: tokens a block of the chunked form
    (all T in one where it does not divide T). Returns (y [b, T, H, P],
    the pool with the rows advanced)."""
    f32 = jnp.float32
    k = x.shape[2] // state.shape[2]
    if k > 1:
        # packed rows: the chunk's rows a head each in a pool of their own
        # (``fresh`` zeroes them there), scanned, written back packed
        own = unpack_state(state[layer, rows], k)[None]           # [1, b, H, N, P]
        y, own = ssm_chunk_scan(own, 0, jnp.arange(x.shape[0]), fresh, x, dt, a,
                                bm, cmat, chunk, kernel=kernel,
                                interpret=interpret)
        return y, state.at[layer, rows].set(pack_state(own[0], k))
    x, dt, a, bm, cmat = (v.astype(f32) for v in (x, dt, a, bm, cmat))
    if kernel:
        return _scan_pallas(state, layer, rows, fresh, x, dt, a, bm, cmat,
                            chunk, interpret)
    return _scan_plain(state, layer, rows, fresh, x, dt, a, bm, cmat, chunk)
