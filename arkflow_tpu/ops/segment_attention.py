"""Segment-masked flash attention for token-packed execution.

Packed rows (tpu/packing.py) hold several examples whose tokens must only
attend within their own segment. The XLA path materializes a [B, 1, S, S]
block-diagonal mask — O(S^2) HBM traffic per row that dwarfs the scores at
long sequence. This kernel keeps the online-softmax flash structure of
``ops/ragged_attention.py`` (chip-proven) and derives the mask on the fly
from two VMEM reads of the per-token ``segment_ids`` ([B, S] int32, 0 =
dead position), so nothing quadratic ever touches HBM.

Packed rows are ~fully dense (that is the point of packing), so there is no
tile-skipping: every K tile computes, masked by segment equality. Dead
positions (segment 0) emit zeros.

Opt-in for serving via ``ARKFLOW_PACKED_FLASH=1`` until it has been A/B'd
on real hardware — the XLA pair-mask path stays the default for packed
execution (models/bert.py::apply_packed).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _segment_kernel(q_ref, k_ref, v_ref, segq_ref, segk_ref, o_ref, *, tile_k: int):
    from arkflow_tpu.ops.ragged_attention import flash_softmax_loop

    q = q_ref[0, 0].astype(jnp.float32)  # [TQ, D]
    s = k_ref.shape[2]
    seg_q = segq_ref[0]  # [TQ, 1] int32

    def valid_at(t):
        seg_k = segk_ref[0, t]  # [1, TK]
        # block-diagonal mask from the segment ids: same segment AND live
        return jnp.logical_and(seg_q == seg_k, seg_q > 0)

    o, m, l = flash_softmax_loop(q, k_ref, v_ref, s // tile_k, tile_k, valid_at)
    # dead queries (segment 0) emit zeros; their fully-masked softmax is
    # uniform, so the accumulator alone cannot zero them
    o_ref[0, 0] = jnp.where(
        seg_q > 0, o / jnp.maximum(l[:, None], 1e-30), 0.0
    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_q", "tile_k", "interpret"))
def segment_flash_attention(q, k, v, segment_ids, *, tile_q: int = 128,
                            tile_k: int = 128, interpret: bool = False):
    """q/k/v: [B, H, S, D]; segment_ids: [B, S] int32 (0 = dead position).

    Tokens attend exactly within their segment (block-diagonal); dead
    positions output zeros. Non-causal (packed classification rows).
    """
    b, h, s, d = q.shape
    tile_q = min(tile_q, s)
    tile_k = min(tile_k, s)
    if s % tile_q or s % tile_k:
        raise ValueError(f"seq len {s} must divide tiles ({tile_q}, {tile_k})")
    from jax.experimental.pallas import tpu as pltpu

    grid = (b, h, s // tile_q)
    kernel = functools.partial(_segment_kernel, tile_k=tile_k)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, tile_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, s, d), lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((1, tile_q, 1), lambda bi, hi, qi: (bi, qi, 0)),
            pl.BlockSpec((1, s // tile_k, 1, tile_k),
                         lambda bi, hi, qi: (bi, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, tile_q, d), lambda bi, hi, qi: (bi, hi, qi, 0)),
    )
    # the ids ride twice, each in the shape its side of the mask broadcasts
    # from with no in-kernel relayout: a [TQ, 1] column for the queries and
    # [1, TK] rows (one per K tile, picked by a leading index) for the keys.
    # Every block's last two dims equal the array's or are (8k, 1) — the
    # layout the TPU tiling accepts, which (1, tile) blocks of [B, S] are not
    seg = jnp.asarray(segment_ids, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(q, k, v, seg[:, :, None], seg.reshape(b, s // tile_k, 1, tile_k))
