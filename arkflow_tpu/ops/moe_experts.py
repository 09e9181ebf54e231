"""Dropless expert products: SwiGLU experts over the tokens routed to them.

A top-k routed MoE layer sends each token to ``k`` of ``E`` experts. With
few tokens in a step (16 decode lanes, one 128-token prefill chunk) the step
is bound by reading expert weights, and most experts receive no token at
all: 16 lanes x top-6 of 128 hit ~69. So the product that matters is "read
each expert that was hit exactly once, and no other".

``moe_expert_swiglu`` is that product. The caller hands it the combine
weights ``cw[t, e]`` (the routing weight of expert ``e`` for token ``t``, 0
where ``t`` was not routed to ``e``). WHICH kernel runs is decided by the
call's row count and nothing else. Up to ``_TOKEN_TILE`` rows (a decode
step's lanes, a 128-token chunk, the set-up probe) it is the one-tile kernel
of this file: the hit experts are compacted to the front of a scalar-
prefetched list; the grid walks (list entry, slice of the expert width);
every step reads one slice of one hit expert's three matrices and does
    out += ((silu(x W_gate[e]) * (x W_up[e])) * cw[:, e]) W_down[e]
for ALL rows of the tile — rows not routed to ``e`` carry weight 0. That is
exact and dropless at any load (there is no capacity), and below the chip's
ridge (about 240 rows on a v5e) the rows that ride along cost nothing: the
step waits on the weights either way. Entries past the last hit expert
repeat its block index (no copy) and ``pl.when`` skips the math. ABOVE one
tile the rows that ride along stop being free, so the product runs grouped
by expert (``ops/moe_grouped.py``): the same walk, each expert read once a
call, multiplying its own rows only. The threshold is the chip's ridge, a
property of the shapes: not a setting.

``expert_swiglu_dense`` is the same arithmetic in plain XLA over every
expert — the reference path (CPU, training forward, ``decode_kernel:
gather``), as ``paged_decode``'s gather path is to the paged kernel.

``moe_expert_relu2`` / ``expert_relu2_dense`` are the same two for an expert
of TWO matrices and no gate (``mlp_hidden_act`` relu2: Nemotron-H),
    out += ((relu(x W_up[e])^2) * cw[:, e]) W_down[e]
through the same hit list, grid and slices, a kernel body of its own
(``_expert_relu2_kernel``; grouped: ``moe_grouped._grouped_relu2_kernel``)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: rows of one kernel call (bounds VMEM: x, out and the float32 accumulator)
_TOKEN_TILE = 128
#: (more rows run grouped by expert, ``ops/moe_grouped.py``; this file's
#: kernel and its index maps keep their LINES: a Mosaic body carries them
#: into the compile cache's key, ``PERF.md`` §7 "From PR 41 (1)")


def expert_swiglu_dense(x, cw, w_gate, w_up, w_down):
    """x: [T, D]; cw: [T, E] float32 combine weights; w_gate / w_up:
    [E, D, F]; w_down: [E, F, D]. Every expert over every token, combined by
    ``cw`` in float32. Returns [T, D] in x's dtype."""
    dtype = x.dtype
    gate = jnp.einsum("td,edf->etf", x, w_gate.astype(dtype))
    up = jnp.einsum("td,edf->etf", x, w_up.astype(dtype))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
    out = jnp.einsum("etf,efd->etd", act, w_down.astype(dtype))
    return jnp.einsum("te,etd->td", cw, out.astype(jnp.float32)).astype(dtype)


def expert_relu2_dense(x, cw, w_up, w_down):
    """``expert_swiglu_dense`` for two-matrix experts: ``relu(x W_up)^2
    W_down`` of every expert over every token, combined by ``cw``."""
    dtype = x.dtype
    up = jnp.einsum("td,edf->etf", x, w_up.astype(dtype)).astype(jnp.float32)
    act = jnp.square(jax.nn.relu(up)).astype(dtype)
    out = jnp.einsum("etf,efd->etd", act, w_down.astype(dtype))
    return jnp.einsum("te,etd->td", cw, out.astype(jnp.float32)).astype(dtype)


def _expert_kernel(layer_ref, ids_ref, nhit_ref, x_ref, cw_ref, wg_ref, wu_ref,
                   wd_ref, o_ref, acc_ref, *, n_slices: int):
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nhit_ref[0])
    def _acc():
        x = x_ref[...]                                            # [T, D]
        dims = (((1,), (0,)), ((), ()))
        gate = jax.lax.dot_general(x, wg_ref[...], dims,
                                   preferred_element_type=jnp.float32)
        up = jax.lax.dot_general(x, wu_ref[...], dims,
                                 preferred_element_type=jnp.float32)
        cw = cw_ref[...]                                          # [T, E]
        col = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
        w = jnp.sum(jnp.where(col == ids_ref[i], cw, 0.0), axis=1,
                    keepdims=True)                                # [T, 1]
        act = (jax.nn.silu(gate) * up * w).astype(x.dtype)        # [T, tf]
        acc_ref[...] += jax.lax.dot_general(
            act, wd_ref[...], dims, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1, j == n_slices - 1))
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _expert_relu2_kernel(layer_ref, ids_ref, nhit_ref, x_ref, cw_ref, wu_ref,
                         wd_ref, o_ref, acc_ref, *, n_slices: int):
    """``_expert_kernel`` for two-matrix experts: one up product, its relu
    squared in float32, the same weighting, cast and accumulation."""
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nhit_ref[0])
    def _acc():
        x = x_ref[...]                                            # [T, D]
        dims = (((1,), (0,)), ((), ()))
        up = jax.lax.dot_general(x, wu_ref[...], dims,
                                 preferred_element_type=jnp.float32)
        cw = cw_ref[...]                                          # [T, E]
        col = jax.lax.broadcasted_iota(jnp.int32, cw.shape, 1)
        w = jnp.sum(jnp.where(col == ids_ref[i], cw, 0.0), axis=1,
                    keepdims=True)                                # [T, 1]
        act = (jnp.square(jnp.maximum(up, 0.0)) * w).astype(x.dtype)
        acc_ref[...] += jax.lax.dot_general(
            act, wd_ref[...], dims, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(i == pl.num_programs(0) - 1, j == n_slices - 1))
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _slice_width(f: int) -> int:
    """Slice of the expert width one grid step takes: the largest of 384,
    256, 128 that divides it (three [D, slice] blocks, double-buffered: at
    most 23.6 of the 48 MB limit as served, 5,120 x 384), else all of it."""
    for tf in (384, 256, 128):
        if f % tf == 0:
            return tf
    return f


def hit_list(hit):
    """The experts a call hit, compacted to the front of a list of all E
    (``hit`` [E] bool): (ids [E] int32, how many are hit). Entries past the
    last hit expert repeat it: same block, no copy."""
    e = hit.shape[0]
    n_hit = hit.sum().astype(jnp.int32)
    order = jnp.argsort(jnp.logical_not(hit), stable=True).astype(jnp.int32)
    return order[jnp.minimum(jnp.arange(e), jnp.maximum(n_hit - 1, 0))], n_hit


def _one_tile(x, cw, w_gate, w_up, w_down, layer, interpret: bool):
    """One token tile through the hit experts; ``w_gate`` None: two-matrix
    (relu-squared) experts, the same walk over one up matrix."""
    from jax.experimental.pallas import tpu as pltpu

    t, d = x.shape
    _, e, _, f = w_up.shape
    ups = [w_up] if w_gate is None else [w_gate, w_up]
    tf = _slice_width(f)
    n_slices = f // tf
    ids, n_hit = hit_list(jnp.any(cw != 0.0, axis=0))

    def _slice(i, j, nhit_ref):
        return jnp.where(i < nhit_ref[0], j, n_slices - 1)

    def _up_index(i, j, layer_ref, ids_ref, nhit_ref):
        return (layer_ref[0], ids_ref[i], 0, _slice(i, j, nhit_ref))

    def _down_index(i, j, layer_ref, ids_ref, nhit_ref):
        return (layer_ref[0], ids_ref[i], _slice(i, j, nhit_ref), 0)

    def _whole(i, j, *_):
        return (0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(e, n_slices),
        in_specs=[
            pl.BlockSpec((t, d), _whole),
            pl.BlockSpec((t, e), _whole),
            *(pl.BlockSpec((None, None, d, tf), _up_index) for _ in ups),
            pl.BlockSpec((None, None, tf, d), _down_index),
        ],
        out_specs=pl.BlockSpec((t, d), _whole),
        scratch_shapes=[pltpu.VMEM((t, d), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_expert_kernel if w_gate is not None
                          else _expert_relu2_kernel, n_slices=n_slices),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024),
        interpret=interpret,
        name="moe_expert_swiglu" if w_gate is not None else "moe_expert_relu2",
    )(layer, ids, n_hit.reshape(1), x, cw, *ups, w_down)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_expert_swiglu(x, cw, w_gate, w_up, w_down, layer=None, *,
                      interpret: bool = False):
    """The hit experts' SwiGLU products, combined: see the module docstring.

    x: [T, D] bfloat16; cw: [T, E] float32 (0 = not routed); w_gate / w_up:
    [E, D, F]; w_down: [E, F, D], in x's dtype — or a whole stack of layers
    ([layers, E, ...]) with ``layer`` the index of the one to use: the
    kernel's index maps pick the layer, so a layer loop never slices (and
    XLA never copies) a layer's 1.2 GB of experts. Returns [T, D]."""
    return _expert_product(x, cw, w_gate, w_up, w_down, layer, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_expert_relu2(x, cw, w_up, w_down, layer=None, *, interpret: bool = False):
    """``moe_expert_swiglu`` for two-matrix experts (``relu(x W_up)^2
    W_down``, no gate): the same operands without ``w_gate``, the same choice
    of kernel by the call's rows (``moe_expert_relu2`` /
    ``moe_expert_relu2_grouped`` in a trace)."""
    return _expert_product(x, cw, None, w_up, w_down, layer, interpret)


def _expert_product(x, cw, w_gate, w_up, w_down, layer, interpret: bool):
    """Either product's call: the stack made four-dimensional, the kernel
    picked by the rows, a tile padded to whole bfloat16 sublane tiles."""
    t = x.shape[0]
    cw = cw.astype(jnp.float32)
    if w_up.ndim == 3:
        w_gate = None if w_gate is None else w_gate[None]
        w_up, w_down, layer = w_up[None], w_down[None], 0
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    if runs_grouped(t):
        # above the ridge: each hit expert over its own rows only
        from arkflow_tpu.ops.moe_grouped import grouped_expert_product

        return grouped_expert_product(x, cw, w_gate, w_up, w_down, layer, interpret)
    tile = -(-t // 16) * 16                     # bf16 packs 16 rows a tile
    if tile != t:
        # padding rows carry weight 0 everywhere: they hit no expert
        x = jnp.pad(x, ((0, tile - t), (0, 0)))
        cw = jnp.pad(cw, ((0, tile - t), (0, 0)))
    tile_out = _one_tile(x, cw, w_gate, w_up, w_down,
                         layer, interpret)      # this call keeps its line too
    return tile_out[:t]


def runs_grouped(rows: int) -> bool:
    """Whether a call of ``rows`` rows runs grouped by expert: more rows than
    one token tile. The one predicate, for the product and for who counts it
    (``arkflow_gen_moe_grouped_products_total``)."""
    return rows > _TOKEN_TILE
