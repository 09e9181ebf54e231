"""EVA's chunk summariser: ``chunk`` keys and values become ONE row each.

A compacting window cache (``attention_class: eva``: Zheng et al., "Efficient
Attention via Control Variates", ICLR 2023; the EvaByte release) keeps exact
keys and values for the window a sequence is writing and, of every window
behind it, one key and one value a CHUNK of ``chunk`` tokens. With a layer's
learned ``phi`` and ``mu`` [kv heads, dk]:

    a_i = softmax_{i in chunk}(dk^-0.5 k_i . phi_h)
    K~  = sum_i a_i k_i + mu_h            V~ = sum_i a_i v_i

``eva_summarise_plain`` is that in ``jax.numpy``; ``eva_summarise`` the same
as one Pallas program a tile of summary rows: the chunk's rows cross HBM once,
the weights never leave VMEM, and nothing [rows, chunk, heads] is written
back. Statistics and sums are float32 in both; the rows come back in the
keys' dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

#: summary rows a program of ``eva_summarise`` makes: at 32 K/V heads of 128
#: and chunks of 16 its K and V blocks are 1 MB each (double-buffered: 4 MB)
#: and the float32 copies it pools another 4 MB of VMEM
_TILE_ROWS = 8


def eva_summarise_plain(k, v, phi, mu, chunk: int):
    """k [..., n, kv heads, dk], v [..., n, kv heads, dv] (``n`` a multiple of
    ``chunk``) -> (K~ [..., n / chunk, kv heads, dk], V~ likewise at dv)."""
    *lead, n, h, dk = k.shape
    kc = k.reshape(*lead, n // chunk, chunk, h, dk).astype(jnp.float32)
    vc = v.reshape(*lead, n // chunk, chunk, h, v.shape[-1]).astype(jnp.float32)
    logits = jnp.sum(kc * phi.astype(jnp.float32), axis=-1,
                     keepdims=True) * (dk ** -0.5)
    a = jax.nn.softmax(logits, axis=-3)                  # over the chunk's rows
    ks = jnp.sum(a * kc, axis=-3) + mu.astype(jnp.float32)
    return ks.astype(k.dtype), jnp.sum(a * vc, axis=-3).astype(v.dtype)


def _summarise_kernel(k_ref, v_ref, phi_ref, mu_ref, ko_ref, vo_ref):
    kf = k_ref[...].astype(jnp.float32)                  # [T, chunk, H, dk]
    vf = v_ref[...].astype(jnp.float32)
    scale = kf.shape[-1] ** -0.5
    logits = jnp.sum(kf * phi_ref[...][None, None], axis=-1,
                     keepdims=True) * scale              # [T, chunk, H, 1]
    top = jnp.max(logits, axis=1, keepdims=True)
    e = jnp.exp(logits - top)
    a = e / jnp.sum(e, axis=1, keepdims=True)
    ko_ref[...] = (jnp.sum(a * kf, axis=1)
                   + mu_ref[...][None]).astype(ko_ref.dtype)
    vo_ref[...] = jnp.sum(a * vf, axis=1).astype(vo_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def eva_summarise(k, v, phi, mu, *, chunk: int, interpret: bool = False):
    """``eva_summarise_plain`` as a Pallas program a tile of ``_TILE_ROWS``
    summary rows (same operands, same result to float32 rounding)."""
    *lead, n, h, dk = k.shape
    dv = v.shape[-1]
    rows = n // chunk
    for d in lead:
        rows *= d
    tile = min(_TILE_ROWS, rows)
    pad = -rows % tile
    kc = k.reshape(rows, chunk, h, dk)
    vc = v.reshape(rows, chunk, h, dv)
    if pad:  # zeros pool to zeros (a uniform softmax): cut off below
        kc, vc = (jnp.pad(a, ((0, pad),) + ((0, 0),) * 3) for a in (kc, vc))
    ks, vs = pl.pallas_call(
        _summarise_kernel,
        grid=((rows + pad) // tile,),
        in_specs=[pl.BlockSpec((tile, chunk, h, dk), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((tile, chunk, h, dv), lambda i: (i, 0, 0, 0)),
                  pl.BlockSpec((h, dk), lambda i: (0, 0)),
                  pl.BlockSpec((h, dk), lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tile, h, dk), lambda i: (i, 0, 0)),
                   pl.BlockSpec((tile, h, dv), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((rows + pad, h, dk), k.dtype),
                   jax.ShapeDtypeStruct((rows + pad, h, dv), v.dtype)],
        interpret=interpret,
        name="eva_summarise",
    )(kc, vc, phi.astype(jnp.float32), mu.astype(jnp.float32))
    return (ks[:rows].reshape(*lead, n // chunk, h, dk),
            vs[:rows].reshape(*lead, n // chunk, h, dv))
