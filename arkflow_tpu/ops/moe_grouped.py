"""The expert product above one token tile: grouped by expert.

``moe_experts.moe_expert_swiglu`` multiplies every row of a call by every
expert the call hit. Below the chip's ridge (about 240 rows on a v5e) that
is the optimum: the step waits on the weights and the rows that ride along
are free. A prefill chunk of 256 or 512 rows is above it. Cut into token
tiles it read every expert up to once a TILE; as one wide tile it would
multiply 512 rows by every expert, eight times the routed work.

``grouped_expert_product`` is the product for such a call, one Pallas kernel
over the same operands. The grid walks (hit expert, slice of the expert
width), as the one-tile kernel's does, so every hit expert's three matrices
cross HBM exactly once a call and an expert that no row chose is not read.
What differs is the rows an expert multiplies: its OWN, in expert order
("sorted by expert"), never the call's.

* Outside the kernel: ``rank[t, e]``, the number of rows before ``t`` that
  chose ``e`` (-1 where ``t`` did not): one cumulative sum, no sort, no
  scatter, no capacity. An expert's group is rows of rank 0 .. count - 1; it
  takes however many chose it, all of the call's if need be (dropless).
* At an expert's first slice the kernel gathers its group, 128 rows a row
  tile, by a one-hot product ``sel @ x`` (``sel[r, t] = rank[t, e] == r``;
  exact: each output is one bfloat16 input) into a scratch that stays for
  the expert's slices; row tiles past the group's count are skipped.
* Every slice: the same arithmetic a pair as the one-tile kernel — gate and
  up accumulate in float32, ``silu(gate) * up * w`` is cast to the
  activations' dtype once, the down product accumulates in float32 over the
  slices (per pair, in a second scratch).
* At its last slice the pairs' float32 outputs are added to their rows of
  the call's float32 accumulator by the transposed one-hot product, the
  float32 values split into three bfloat16 terms (8 + 8 + 8 bits: the MXU
  multiplies each by exactly 1 or 0 and sums in float32, so a row receives
  its pair's float32 output to the last bit or one rounding of it). Experts
  are walked in index order, so a row's additions keep that order. The
  accumulator is cast once, at the end, as the one-tile kernel's is.

Both one-hot products are MXU work the weights' read hides (a routed
expert's group is a few dozen rows; its matrices are 6–75 MB); nothing but
``rank`` is made outside the kernel and no (row, expert) pair ever exists in
HBM, so the program holds no temporary that grows with the pairs.

A call holds its rows, their float32 accumulator and the two group
scratches in VMEM, so its rows are bounded: ``GROUPED_ROWS`` (512: a 6,144
wide model's call is then x 6 MB + out 6 MB + accumulator 12.6 MB + group
scratches 6 + 12.6 MB + the weights' slices, inside the kernel's limit on a
v5e's 128 MB). More rows (a one-shot prefill of a long prompt) run block by
block, each a call of its own with its own hit list — unrolled, not under
``lax.map``, whose body is compiled under the loop's default fast-memory
limit and not the call's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from arkflow_tpu.ops.moe_experts import hit_list

#: rows of one grouped call (bounds VMEM: see the module docstring)
GROUPED_ROWS = 512
#: rows of one row tile of a group (one MXU pass; the one-hot's width)
_ROW_TILE = 128
#: lanes of the accumulator one scatter product covers (bounds its result)
_LANE_CHUNK = 1024
#: budget of the three double-buffered weight slices, bytes
_SLICE_BUDGET = 20 * 1024 * 1024
#: the kernel's fast-memory limit (a v5e holds 128 MB)
_VMEM_LIMIT = 100 * 1024 * 1024


def grouped_slice_width(d: int, f: int, itemsize: int = 2) -> int:
    """Slice of the expert width one grid step takes: the largest of 512,
    384, 256, 128 that divides ``f`` and whose three double-buffered
    [d, slice] blocks stay inside ``_SLICE_BUDGET`` (at d = 6,144: 256, 18.9
    MB; at 2,048: 512, 12.6 MB); 128 where none of them does, or all of a
    width that none divides."""
    fits = [tf for tf in (512, 384, 256, 128) if f % tf == 0]
    for tf in fits:
        if 3 * 2 * d * tf * itemsize <= _SLICE_BUDGET:
            return tf
    return fits[-1] if fits else f


def _grouped_kernel(layer_ref, ids_ref, cnt_ref, nhit_ref, x_ref, cwt_ref, rank_ref,
                    rankt_ref, wg_ref, wu_ref, wd_ref, o_ref, acc_ref, xs_ref,
                    ws_ref, ys_ref, *, n_slices: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    t, d = x_ref.shape
    dims = (((1,), (0,)), ((), ()))
    expert = ids_ref[i]
    # row tiles of this expert's group (none for an entry past the hit list)
    tiles = jnp.where(i < nhit_ref[0], pl.cdiv(cnt_ref[i], _ROW_TILE), 0)

    def _rows(s):
        return pl.ds(pl.multiple_of(s * _ROW_TILE, _ROW_TILE), _ROW_TILE)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == 0)
    def _gather():
        rank = rankt_ref[pl.ds(expert, 1), :]                     # [1, T]
        weight = cwt_ref[pl.ds(expert, 1), :]

        def tile(s, _):
            want = s * _ROW_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (_ROW_TILE, t), 0)
            sel = rank == want                                    # [rows, T]
            xs_ref[_rows(s), :] = jax.lax.dot_general(
                jnp.where(sel, 1.0, 0.0).astype(x_ref.dtype), x_ref[...], dims,
                preferred_element_type=jnp.float32).astype(xs_ref.dtype)
            ws_ref[_rows(s), :] = jnp.sum(jnp.where(sel, weight, 0.0), axis=1,
                                          keepdims=True)

        jax.lax.fori_loop(0, tiles, tile, None)

    def _product(s, _):
        xs = xs_ref[_rows(s), :]                                  # [rows, D]
        gate = jax.lax.dot_general(xs, wg_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        up = jax.lax.dot_general(xs, wu_ref[...], dims,
                                 preferred_element_type=jnp.float32)
        act = (jax.nn.silu(gate) * up * ws_ref[_rows(s), :]).astype(xs.dtype)
        down = jax.lax.dot_general(act, wd_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        ys_ref[_rows(s), :] = jnp.where(j == 0, 0.0, ys_ref[_rows(s), :]) + down

    jax.lax.fori_loop(0, tiles, _product, None)

    @pl.when(j == n_slices - 1)
    def _scatter():
        col = jax.lax.broadcasted_iota(jnp.int32, rank_ref.shape, 1)
        rank = jnp.sum(jnp.where(col == expert, rank_ref[...], 0), axis=1,
                       keepdims=True)                             # [T, 1]

        def tile(s, _):
            want = s * _ROW_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (t, _ROW_TILE), 1)
            sel = jnp.where(rank == want, 1.0, 0.0).astype(jnp.bfloat16)
            sel = jnp.concatenate([sel] * 3, axis=1)              # [T, 3 rows]
            for c in range(0, d, _LANE_CHUNK):
                lanes = pl.ds(c, min(_LANE_CHUNK, d - c))
                # float32 = three bfloat16 terms, summed by the one product
                rest, terms = ys_ref[_rows(s), lanes], []
                for _ in range(3):
                    terms.append(rest.astype(jnp.bfloat16))
                    rest = rest - terms[-1].astype(jnp.float32)
                acc_ref[:, lanes] += jax.lax.dot_general(
                    sel, jnp.concatenate(terms, axis=0), dims,
                    preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, tiles, tile, None)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1, j == n_slices - 1))
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_relu2_kernel(layer_ref, ids_ref, cnt_ref, nhit_ref, x_ref, cwt_ref,
                          rank_ref, rankt_ref, wu_ref, wd_ref, o_ref, acc_ref,
                          xs_ref, ws_ref, ys_ref, *, n_slices: int):
    """``_grouped_kernel`` for two-matrix experts (``relu(x W_up)^2 W_down``):
    the same gather of a group, the same sum back into the call's rows; a
    slice's product is one up product, its relu squared in float32."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    t, d = x_ref.shape
    dims = (((1,), (0,)), ((), ()))
    expert = ids_ref[i]
    # row tiles of this expert's group (none for an entry past the hit list)
    tiles = jnp.where(i < nhit_ref[0], pl.cdiv(cnt_ref[i], _ROW_TILE), 0)

    def _rows(s):
        return pl.ds(pl.multiple_of(s * _ROW_TILE, _ROW_TILE), _ROW_TILE)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j == 0)
    def _gather():
        rank = rankt_ref[pl.ds(expert, 1), :]                     # [1, T]
        weight = cwt_ref[pl.ds(expert, 1), :]

        def tile(s, _):
            want = s * _ROW_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (_ROW_TILE, t), 0)
            sel = rank == want                                    # [rows, T]
            xs_ref[_rows(s), :] = jax.lax.dot_general(
                jnp.where(sel, 1.0, 0.0).astype(x_ref.dtype), x_ref[...], dims,
                preferred_element_type=jnp.float32).astype(xs_ref.dtype)
            ws_ref[_rows(s), :] = jnp.sum(jnp.where(sel, weight, 0.0), axis=1,
                                          keepdims=True)

        jax.lax.fori_loop(0, tiles, tile, None)

    def _product(s, _):
        xs = xs_ref[_rows(s), :]                                  # [rows, D]
        up = jax.lax.dot_general(xs, wu_ref[...], dims,
                                 preferred_element_type=jnp.float32)
        act = (jnp.square(jnp.maximum(up, 0.0))
               * ws_ref[_rows(s), :]).astype(xs.dtype)
        down = jax.lax.dot_general(act, wd_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        ys_ref[_rows(s), :] = jnp.where(j == 0, 0.0, ys_ref[_rows(s), :]) + down

    jax.lax.fori_loop(0, tiles, _product, None)

    @pl.when(j == n_slices - 1)
    def _scatter():
        col = jax.lax.broadcasted_iota(jnp.int32, rank_ref.shape, 1)
        rank = jnp.sum(jnp.where(col == expert, rank_ref[...], 0), axis=1,
                       keepdims=True)                             # [T, 1]

        def tile(s, _):
            want = s * _ROW_TILE + jax.lax.broadcasted_iota(
                jnp.int32, (t, _ROW_TILE), 1)
            sel = jnp.where(rank == want, 1.0, 0.0).astype(jnp.bfloat16)
            sel = jnp.concatenate([sel] * 3, axis=1)              # [T, 3 rows]
            for c in range(0, d, _LANE_CHUNK):
                lanes = pl.ds(c, min(_LANE_CHUNK, d - c))
                # float32 = three bfloat16 terms, summed by the one product
                rest, terms = ys_ref[_rows(s), lanes], []
                for _ in range(3):
                    terms.append(rest.astype(jnp.bfloat16))
                    rest = rest - terms[-1].astype(jnp.float32)
                acc_ref[:, lanes] += jax.lax.dot_general(
                    sel, jnp.concatenate(terms, axis=0), dims,
                    preferred_element_type=jnp.float32)

        jax.lax.fori_loop(0, tiles, tile, None)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1, j == n_slices - 1))
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _grouped_block(x, cw, w_gate, w_up, w_down, layer, interpret: bool):
    """One call of at most ``GROUPED_ROWS`` rows (a multiple of the row
    tile); ``cw`` float32, the weights stacked, ``layer`` int32 [1];
    ``w_gate`` None: two-matrix (relu-squared) experts."""
    from jax.experimental.pallas import tpu as pltpu

    t, d = x.shape
    _, e, _, f = w_up.shape
    ups = [w_up] if w_gate is None else [w_gate, w_up]
    # the slices are budgeted for three matrices either way: one width, and
    # one set of programs' worth of VMEM, whatever the expert is made of
    tf = grouped_slice_width(d, f, jnp.dtype(w_up.dtype).itemsize)
    n_slices = f // tf
    routed = cw != 0.0                                            # [T, E]
    counts = routed.sum(axis=0).astype(jnp.int32)
    before = jnp.cumsum(routed, axis=0, dtype=jnp.int32) - routed
    rank = jnp.where(routed, before, -1)
    # ``rank`` rides in both layouts: a ROW of one is the gather's [rows, T]
    # one-hot, a COLUMN of the other the sum's [T, rows], and the kernel
    # transposes nothing
    ids, n_hit = hit_list(counts > 0)

    def _slice(i, j, nhit_ref):
        return jnp.where(i < nhit_ref[0], j, n_slices - 1)

    def _up_index(i, j, layer_ref, ids_ref, cnt_ref, nhit_ref):
        return (layer_ref[0], ids_ref[i], 0, _slice(i, j, nhit_ref))

    def _down_index(i, j, layer_ref, ids_ref, cnt_ref, nhit_ref):
        return (layer_ref[0], ids_ref[i], _slice(i, j, nhit_ref), 0)

    def whole(*shape):
        # the call's one block of it, so one buffer and no second copy
        return pl.BlockSpec(shape, lambda i, j, *_: (0, 0),
                            pipeline_mode=pl.Buffered(1))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(e, n_slices),
        in_specs=[
            whole(t, d), whole(e, t), whole(t, e), whole(e, t),
            *(pl.BlockSpec((None, None, d, tf), _up_index) for _ in ups),
            pl.BlockSpec((None, None, tf, d), _down_index),
        ],
        out_specs=whole(t, d),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32),      # the call's sums
                        pltpu.VMEM((t, d), x.dtype),          # a group's rows
                        pltpu.VMEM((t, 1), jnp.float32),      # their weights
                        pltpu.VMEM((t, d), jnp.float32)],     # their outputs
    )
    return pl.pallas_call(
        functools.partial(_grouped_kernel if w_gate is not None
                          else _grouped_relu2_kernel, n_slices=n_slices),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name=("moe_expert_grouped" if w_gate is not None
              else "moe_expert_relu2_grouped"),
    )(layer, ids, counts[ids], n_hit.reshape(1), x, cw.T, rank, rank.T,
      *ups, w_down)


def grouped_expert_product(x, cw, w_gate, w_up, w_down, layer, interpret: bool):
    """x: [T, D], T of any size; cw: [T, E] float32 (0 = not routed); the
    weights a stack of layers ([layers, E, ...]), ``layer`` int32 [1]: the
    operands ``moe_experts._expert_product`` has made of its own (``w_gate``
    None: two-matrix experts). Returns [T, D]."""
    t = x.shape[0]
    out = []
    for first in range(0, t, GROUPED_ROWS):
        xb, cwb = x[first:first + GROUPED_ROWS], cw[first:first + GROUPED_ROWS]
        rows = xb.shape[0]
        if rows % _ROW_TILE:
            # padding rows carry weight 0 everywhere: they join no group
            pad = ((0, -rows % _ROW_TILE), (0, 0))
            xb, cwb = jnp.pad(xb, pad), jnp.pad(cwb, pad)
        out.append(_grouped_block(xb, cwb, w_gate, w_up, w_down, layer,
                                  interpret)[:rows])
    return jnp.concatenate(out) if len(out) > 1 else out[0]


#: the gated product under the name it had before two-matrix experts came
grouped_expert_swiglu = grouped_expert_product
