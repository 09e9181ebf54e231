"""The served path's own choices over one teacher-forced row.

``program_choices`` runs a row through ``models/paged_decode`` as the server
does — the prompt in chunks, every later token in a single-lane decode step
fed the row's own next token — and hands back, beside each step's logits,
what the program CHOSE on the way: the keys every query of an indexed layer
attended and the experts every token of an expert layer was routed to. The
plain reference takes them as ``forced`` (``benchmark/references/
sparse_window_mla_moe.py::decoder_logits``) and then differs from the
program in arithmetic alone: what is left of a disagreement after that
replay is not a near-tie resolved the other way, at this position or an
earlier one.

The choices are read where the program makes them: ``_index_select`` and
``route_topk`` are wrapped while the step is traced, and the layer scan is
unrolled for that trace so that what they return can leave the program.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from arkflow_tpu.models import decoder as dec
from arkflow_tpu.models import paged_decode
from arkflow_tpu.models.paged_decode import (init_page_pool, paged_decode_step,
                                             paged_prefill_chunk,
                                             window_ring_pages)


def _unrolled_scan(f, init, xs=None, length=None, reverse=False, unroll=1):
    n = length if xs is None else jax.tree_util.tree_leaves(xs)[0].shape[0]
    carry, ys = init, []
    for i in (range(n - 1, -1, -1) if reverse else range(n)):
        carry, y = f(carry, None if xs is None else jax.tree_util.tree_map(
            lambda a: a[i], xs))
        ys.append(y)
    if reverse:
        ys.reverse()
    return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


@contextlib.contextmanager
def _capturing(masks: list, routes: list):
    select, route, scan = paged_decode._index_select, dec.route_topk, jax.lax.scan

    def index_select(q_i, w, index_pages, layer, page_table, *a, **kw):
        sel, ok = select(q_i, w, index_pages, layer, page_table, *a, **kw)
        ctx = page_table.shape[1] * index_pages.shape[2]
        if sel.dtype == jnp.float32:          # the choice as a mask already
            masks.append(sel > 0)
        else:                                 # positions [B, S, K] and ``ok``
            b, s, _ = sel.shape
            hit = jnp.zeros((b, s, ctx + 1), bool).at[
                jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None],
                jnp.where(ok, sel, ctx)].set(True)
            masks.append(hit[..., :ctx])
        return sel, ok

    def route_topk(lp, y, cfg, token_mask=None):
        # the router's own expression once more: the same program computes
        # the same values, and the compiler folds the two into one
        scores = jax.nn.sigmoid(jnp.dot(
            y.astype(jnp.float32), lp["router"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
        routes.append(jax.lax.top_k(
            scores + lp["router_bias"].astype(jnp.float32),
            cfg.num_experts_per_tok)[1])
        return route(lp, y, cfg, token_mask)

    paged_decode._index_select, dec.route_topk = index_select, route_topk
    jax.lax.scan = _unrolled_scan
    try:
        yield
    finally:
        paged_decode._index_select, dec.route_topk = select, route
        jax.lax.scan = scan


def program_choices(params, cfg, row, n_prompt: int, page: int, chunk: int,
                    **kern):
    """``row`` [n] int32 (prompt then served tokens) through the paged path.
    Returns (logits [n - n_prompt + 1, vocab] float32: after the prompt's
    last token and after each later token fed; selections [indexed layers,
    n, n] bool; chosen experts [expert layers, n, k] int32)."""
    n = len(row)
    pages_per = -(-n // page)
    cols = window_ring_pages(cfg, page, chunk)
    kept = jnp.arange(1, 1 + pages_per, dtype=jnp.int32)[None]
    ring = jnp.arange(1, 1 + cols, dtype=jnp.int32)[None]
    kp, vp = init_page_pool(cfg, 1 + pages_per, page, 1 + cols)

    def captured(step):
        def fn(p, *a):
            masks, routes = [], []
            with _capturing(masks, routes):
                out = step(p, *a)
            return out[:3], masks, routes
        return jax.jit(fn)

    chunked = captured(lambda p, *a: paged_prefill_chunk(p, cfg, *a, **kern))
    decode = captured(lambda p, *a: paged_decode_step(
        p, cfg, *a, return_logits=True, **kern))
    sel = routed = None
    logits = []

    def keep(lo, c, masks, routes):
        nonlocal sel, routed
        if sel is None:
            sel = np.zeros((len(masks), n, n), bool)
            routed = np.zeros((len(routes), n, np.asarray(routes[0]).shape[-1]),
                              np.int32)
        for i, m in enumerate(masks):
            sel[i, lo:lo + c] = np.asarray(m)[0, :c, :n]
        for i, r in enumerate(routes):
            routed[i, lo:lo + c] = np.asarray(r)[:c]

    for off in range(0, n_prompt, chunk):
        c = min(chunk, n_prompt - off)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :c] = row[off:off + c]
        (last, kp, vp), masks, routes = chunked(
            params, jnp.asarray(ids), jnp.asarray([off]), jnp.asarray([c]),
            (kept, ring), kp, vp)
        keep(off, c, masks, routes)
    logits.append(np.asarray(last, np.float32)[0])
    for t in range(n_prompt, n):
        (out, kp, vp), masks, routes = decode(
            params, jnp.asarray(row[t:t + 1]), jnp.asarray([t]),
            jnp.asarray([True]), (kept, ring), kp, vp)
        keep(t, 1, masks, routes)
        logits.append(np.asarray(out, np.float32)[0])
    return np.stack(logits), sel, routed
