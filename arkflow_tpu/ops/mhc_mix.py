"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): what mixes
a token's ``n`` residual streams around a sub-layer.

A token's residual is ``X`` [n, C]. Each sub-layer (attention; the MLP half)
has float32 leaves ``phi`` [n C, n n + 2 n], ``b`` [n n + 2 n] and ``alpha``
[3] (pre, post, res). With ``x = vec(X)`` in float32::

    m     = (x phi) * rsqrt(mean(x^2) + norm_eps)          # n n + 2 n values
    Hpre  = sigmoid(alpha_pre  m[0:n]   + b[0:n])
    Hpost = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])
    M     = exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]), lo, hi))   # row-major
    iters times:  M <- M / (colsum(M) + eps);  M <- M / (rowsum(M) + eps)
    u     = sum_j Hpre[j] X_j                              # the sub-layer's input
    X'_i  = sum_j M[i, j] X_j + Hpost[i] y                 # y: its output

The streams travel FLAT, ``x`` [..., n C] (stream ``j`` in columns ``j C ..
(j + 1) C``): lane-dense rows that the kernels view in place — an axis of n
= 4 before the last is tiled to 8 or 16 sublanes on a chip, and every view
of it as rows of n C was a copy of the streams (49 us a 512-token call,
PERF.md PR 53). ``mhc_pre`` gives ``(u, H)`` and ``mhc_post`` takes ``H`` back: ``H`` holds a
token's coefficients in its leading ``n n + 2 n`` columns (pre, post, res
row-major; ``mhc_split`` names them) — float32, never rounded. Two forms of
each, one arithmetic:

* plain XLA (``kernel=False``; the CPU tests' and the start-up probe's other
  side, under ``jax.named_scope("mhc_pre" / "mhc_post")`` so that a trace
  names it): the projection at ``highest`` precision, then the coefficients
  with the tokens on the minor axis ([n, n, T]) and every sum written as
  adds of slices — no reduction, so that XLA can fuse the twenty iterations
  into one loop and not into forty small ones;
* Pallas (``kernel=True``; names ``mhc_pre`` / ``mhc_post``), a tile of 128
  tokens a grid step, ONE pass over ``X`` each: ``mhc_pre`` reads the tile,
  multiplies it by ``phi`` on the MXU — ``phi``'s float32 values split
  exactly into three bfloat16 terms that sit side by side in one 128-lane
  operand (3 x 24 columns), so one bfloat16 pass with float32 sums gives the
  float32 product of bfloat16 streams —, normalises, runs the iterations on
  [1, 128] rows (a coefficient a row, tokens on lanes: two 128 x 128
  transposes) and writes ``u`` and 128 float32 lanes a token; ``mhc_post``
  reads ``X``, ``y`` and those lanes and writes ``X'``. Streams bfloat16; a
  call of fewer rows than a tile (a decode step's lanes) is ONE grid step of
  its own rows, a ragged last tile is padded by the wrapper.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: tokens a grid step of either kernel takes
TOKEN_TILE = 128
#: lanes of the kernels' coefficient rows (n n + 2 n <= 42 of them used: the
#: three bfloat16 terms of ``phi`` need 3 (n n + 2 n) <= 128)
_LANES = 128
#: columns of a stream the kernels mix at a time
_COLS = 512
_VMEM_LIMIT = 64 * 1024 * 1024


def n_coefficients(n: int) -> int:
    return n * n + 2 * n


def mhc_split(h, n: int):
    """``H`` [..., >= n n + 2 n] -> (Hpre [..., n], Hpost [..., n], Hres
    [..., n, n])."""
    return (h[..., :n], h[..., n:2 * n],
            h[..., 2 * n:n_coefficients(n)].reshape(h.shape[:-1] + (n, n)))


def _alpha_of(k: int, n: int) -> int:
    """Which of ``alpha``'s three a coefficient ``k`` is scaled by."""
    return 0 if k < n else 1 if k < 2 * n else 2


def _coefficients(h: list, *, n: int, iters: int, eps: float, clamp: tuple) -> list:
    """The ``n n + 2 n`` coefficients from their logits ``h`` (a list of
    arrays of one shape, a coefficient each), elementwise only: what the
    kernel runs on its [1, 128] rows (``_coefficients_rows`` is the same
    arithmetic over one array, for the plain form)."""
    lo, hi = clamp
    pre = [jax.nn.sigmoid(h[j]) for j in range(n)]
    post = [2.0 * jax.nn.sigmoid(h[n + j]) for j in range(n)]
    m = [[jnp.exp(jnp.clip(h[2 * n + i * n + j], lo, hi)) for j in range(n)]
         for i in range(n)]
    for _ in range(iters):
        col = [functools.reduce(lambda a, b: a + b, (m[i][j] for i in range(n)))
               + eps for j in range(n)]
        m = [[m[i][j] / col[j] for j in range(n)] for i in range(n)]
        row = [functools.reduce(lambda a, b: a + b, m[i]) + eps for i in range(n)]
        m = [[m[i][j] / row[i] for j in range(n)] for i in range(n)]
    return pre + post + [m[i][j] for i in range(n) for j in range(n)]


def _coefficients_rows(h, *, n: int, iters: int, eps: float, clamp: tuple):
    """``_coefficients`` over ``h`` [n n + 2 n, T] (a coefficient a row, the
    tokens minor) in a few hundred array operations where the list form
    takes a thousand: what the plain form traces and compiles."""
    lo, hi = clamp
    t = h.shape[1:]
    add = lambda parts: functools.reduce(lambda a, b: a + b, parts)  # noqa: E731
    m = jnp.exp(jnp.clip(h[2 * n:], lo, hi)).reshape((n, n) + t)
    for _ in range(iters):
        m = m / (add([m[i] for i in range(n)]) + eps)[None]
        m = m / (add([m[:, j] for j in range(n)]) + eps)[:, None]
    return jnp.concatenate([jax.nn.sigmoid(h[:n]), 2.0 * jax.nn.sigmoid(h[n:2 * n]),
                            m.reshape((n * n,) + t)])


# -- plain XLA ------------------------------------------------------------------


def _stream(x, j: int, n: int):
    """Stream ``j`` of the flat streams ``x`` [..., n C]."""
    c = x.shape[-1] // n
    return x[..., j * c:(j + 1) * c]


def _alpha_rows(alpha, n: int):
    """``alpha`` (pre, post, res) spread over the ``n n + 2 n`` coefficients
    (a product with a constant 0 / 1 table: a concatenation of 24 scalars is
    a 7 us program of its own on a chip)."""
    import numpy as np

    k = n_coefficients(n)
    table = np.zeros((k, 3), np.float32)
    table[np.arange(k), [_alpha_of(i, n) for i in range(k)]] = 1.0
    return (jnp.asarray(table) * alpha.astype(jnp.float32)).sum(-1)


def mhc_pre_xla(x, leaves: dict, *, n: int, iters: int, eps: float,
                clamp: tuple, norm_eps: float):
    """x: [..., n C] -> (u [..., C] in x's dtype, H [..., n n + 2 n] float32)."""
    k = n_coefficients(n)
    with jax.named_scope("mhc_pre"):
        xf = x.astype(jnp.float32)
        m = jnp.dot(xf, leaves["phi"].astype(jnp.float32),
                    precision=jax.lax.Precision.HIGHEST)
        m = m * jax.lax.rsqrt(jnp.square(xf).mean(-1, keepdims=True) + norm_eps)
        h = _alpha_rows(leaves["alpha"], n) * m + leaves["b"].astype(jnp.float32)
        coef = _coefficients_rows(h.reshape((-1, k)).T, n=n, iters=iters,
                                  eps=eps, clamp=clamp)   # a coefficient a row
        coef = coef.T.reshape(x.shape[:-1] + (k,))
        u = functools.reduce(lambda a, b: a + b, (
            coef[..., j, None] * _stream(xf, j, n) for j in range(n)))
        return u.astype(x.dtype), coef


def mhc_post_xla(x, y, h):
    """x: [..., n C], y: [..., C], H from ``mhc_pre*`` -> X' [..., n C]."""
    n = x.shape[-1] // y.shape[-1]
    with jax.named_scope("mhc_post"):
        xf, yf = x.astype(jnp.float32), y.astype(jnp.float32)
        out = [functools.reduce(lambda a, b: a + b, (
            h[..., 2 * n + i * n + j, None] * _stream(xf, j, n) for j in range(n)))
            + h[..., n + i, None] * yf for i in range(n)]
        return jnp.concatenate(out, axis=-1).astype(x.dtype)


# -- Pallas ------------------------------------------------------------------------


def split_phi(phi, n: int):
    """``phi`` [n C, K] float32 -> [n C, 128] bfloat16: its values' three
    bfloat16 terms (8 mantissa bits each: their sum IS the float32 value) in
    columns 0..K, K..2K, 2K..3K, zeros behind. Made once a trace, outside
    the kernel (43 KB a sub-layer at n 4)."""
    k = n_coefficients(n)
    if 3 * k > _LANES:
        raise ValueError(f"hc_mult {n}: 3 x {k} coefficient columns do not "
                         f"fit the kernel's {_LANES} lanes")
    rest = phi.astype(jnp.float32)
    terms = []
    for _ in range(3):
        terms.append(rest.astype(jnp.bfloat16))
        rest = rest - terms[-1].astype(jnp.float32)
    return jnp.pad(jnp.concatenate(terms, axis=1), ((0, 0), (0, _LANES - 3 * k)))


def _mix_columns(c: int):
    """(first, width) of the column blocks a stream is mixed in."""
    step = _COLS if c % _COLS == 0 else c
    return [(first, step) for first in range(0, c, step)]


def _pre_kernel(sc_ref, x_ref, phi_ref, u_ref, h_ref, mt_ref, ct_ref, *,
                n: int, c: int, iters: int, eps: float, clamp: tuple,
                norm_eps: float):
    # a tile of fewer than 128 tokens (a decode step's lanes) is transposed
    # as the head of a 128 x 128 block of zeros: the rows behind it compute
    # on zeros, each in its own lane, and are not written back
    f32 = jnp.float32
    k = n_coefficients(n)
    tt = x_ref.shape[0]
    acc = jnp.zeros((tt, _LANES), f32)
    ss = jnp.zeros((tt, 1), f32)
    for j in range(n):
        xj = x_ref[:, j * c:(j + 1) * c]
        acc = acc + jnp.dot(xj, phi_ref[j * c:(j + 1) * c, :],
                            preferred_element_type=f32)
        xf = xj.astype(f32)
        ss = ss + jnp.sum(xf * xf, axis=1, keepdims=True)
    acc = acc * jax.lax.rsqrt(ss / (n * c) + norm_eps)
    if tt < _LANES:
        ct_ref[...] = jnp.zeros_like(ct_ref)
        ct_ref[0:tt, :] = acc
        acc = ct_ref[...]
    mt_ref[...] = acc.T                       # a coefficient term a row
    h = []
    for i in range(k):                        # the three terms' sum, a row
        m = mt_ref[i:i + 1, :] + mt_ref[k + i:k + i + 1, :] \
            + mt_ref[2 * k + i:2 * k + i + 1, :]
        h.append(sc_ref[0, i] * m + sc_ref[1, i])
    coef = _coefficients(h, n=n, iters=iters, eps=eps, clamp=clamp)
    ct_ref[...] = jnp.zeros_like(ct_ref)
    for i, row in enumerate(coef):
        ct_ref[i:i + 1, :] = row
    out = ct_ref[...].T[0:tt]                 # a token a row again
    h_ref[...] = out
    for first, width in _mix_columns(c):
        u = out[:, 0:1] * x_ref[:, first:first + width].astype(f32)
        for j in range(1, n):
            u = u + out[:, j:j + 1] * x_ref[
                :, j * c + first:j * c + first + width].astype(f32)
        u_ref[:, first:first + width] = u.astype(u_ref.dtype)


def _post_kernel(x_ref, y_ref, h_ref, o_ref, *, n: int, c: int):
    # whole [tile, 512] blocks: sixteen tokens at a time with their
    # coefficients spread over a lane run once was tried and is TWICE as
    # slow alone (101 us against ~50 a 512-row call, PERF.md PR 53)
    f32 = jnp.float32
    coef = h_ref[...]
    for first, width in _mix_columns(c):
        yf = y_ref[:, first:first + width].astype(f32)
        xs = [x_ref[:, j * c + first:j * c + first + width].astype(f32)
              for j in range(n)]
        for i in range(n):
            at = 2 * n + i * n
            out = coef[:, at:at + 1] * xs[0]
            for j in range(1, n):
                out = out + coef[:, at + j:at + j + 1] * xs[j]
            out = out + coef[:, n + i:n + i + 1] * yf
            o_ref[:, i * c + first:i * c + first + width] = out.astype(o_ref.dtype)


def _tile(t: int) -> int:
    """Tokens a grid step takes of ``t`` rows: 128, or all of a shorter
    call's rows (whole bfloat16 sublane tiles of 16)."""
    return min(TOKEN_TILE, -(-t // 16) * 16)


def _padded(t: int) -> int:
    return -(-t // _tile(t)) * _tile(t)


def _rows(a, t: int):
    """[T, ...] padded with zero rows to whole token tiles."""
    return a if a.shape[0] == t else jnp.pad(
        a, ((0, t - a.shape[0]),) + ((0, 0),) * (a.ndim - 1))


def mhc_pre_kernel(x, leaves: dict, *, n: int, iters: int, eps: float,
                   clamp: tuple, norm_eps: float, interpret: bool = False):
    """``mhc_pre_xla``'s results through the kernel: (u [..., C], H [..., 128]
    float32, the coefficients in its leading columns). Streams bfloat16."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = x.shape[-1] // n
    lead = x.shape[:-1]
    flat = x.reshape((-1, n * c)).astype(jnp.bfloat16)
    t = flat.shape[0]
    tp, tile = _padded(t), _tile(t)
    sc = jnp.stack([_alpha_rows(leaves["alpha"], n),
                    leaves["b"].astype(jnp.float32)])
    u, h = pl.pallas_call(
        functools.partial(_pre_kernel, n=n, c=c, iters=iters, eps=eps,
                          clamp=clamp, norm_eps=norm_eps),
        grid=(tp // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, n * c), lambda i: (i, 0)),
            pl.BlockSpec((n * c, _LANES), lambda i: (0, 0),
                         pipeline_mode=pl.Buffered(1)),
        ],
        out_specs=[pl.BlockSpec((tile, c), lambda i: (i, 0)),
                   pl.BlockSpec((tile, _LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((tp, c), jnp.bfloat16),
                   jax.ShapeDtypeStruct((tp, _LANES), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_LANES, _LANES), jnp.float32),
                        pltpu.VMEM((_LANES, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mhc_pre",
    )(sc, _rows(flat, tp), split_phi(leaves["phi"], n))
    return (u[:t].reshape(lead + (c,)).astype(x.dtype),
            h[:t].reshape(lead + (_LANES,)))


def mhc_post_kernel(x, y, h, *, interpret: bool = False):
    """``mhc_post_xla``'s result through the kernel; ``h`` as
    ``mhc_pre_kernel`` returned it ([..., 128] float32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c = y.shape[-1]
    n = x.shape[-1] // c
    flat = x.reshape((-1, n * c)).astype(jnp.bfloat16)
    t = flat.shape[0]
    tp, tile = _padded(t), _tile(t)
    out = pl.pallas_call(
        functools.partial(_post_kernel, n=n, c=c),
        grid=(tp // tile,),
        in_specs=[pl.BlockSpec((tile, n * c), lambda i: (i, 0)),
                  pl.BlockSpec((tile, c), lambda i: (i, 0)),
                  pl.BlockSpec((tile, _LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, n * c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((tp, n * c), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="mhc_post",
    )(_rows(flat, tp), _rows(y.reshape((-1, c)).astype(jnp.bfloat16), tp),
      _rows(h.reshape((-1, h.shape[-1])), tp))
    return out[:t].reshape(x.shape).astype(x.dtype)


# -- what the layer loop calls -----------------------------------------------------


#: fewest rows a call needs for the kernels to take it (a bfloat16 sublane
#: tile: fewer would be padded by copies around every call)
KERNEL_ROWS = 16


def kernel_serves(x, n: int) -> bool:
    """Whether the kernels take these streams: bfloat16, a stream a whole
    number of 128-lane runs wide, and at least ``KERNEL_ROWS`` rows."""
    rows = 1
    for d in x.shape[:-1]:
        rows *= d
    return (x.dtype == jnp.bfloat16 and x.shape[-1] % (n * _LANES) == 0
            and rows >= KERNEL_ROWS)


def mhc_pre(x, leaves: dict, *, n: int, iters: int, eps: float, clamp: tuple,
            norm_eps: float, kernel: bool = False, interpret: bool = False):
    """(u, H) of the streams ``x`` [..., n C] under one sub-layer's leaves."""
    kw = dict(n=n, iters=iters, eps=eps, clamp=clamp, norm_eps=norm_eps)
    if kernel and kernel_serves(x, n):
        return mhc_pre_kernel(x, leaves, interpret=interpret, **kw)
    return mhc_pre_xla(x, leaves, **kw)


def mhc_post(x, y, h, *, kernel: bool = False, interpret: bool = False):
    """X' of the streams ``x``, the sub-layer's output ``y`` and ``H``."""
    if kernel and kernel_serves(x, x.shape[-1] // y.shape[-1]) \
            and h.shape[-1] == _LANES:
        return mhc_post_kernel(x, y, h, interpret=interpret)
    return mhc_post_xla(x, y, h)
