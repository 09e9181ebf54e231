"""``ops/topk_select.dsa_topk_select`` (interpret mode) against the lines it
took the place of in ``paged_decode._index_select``: ``jax.lax.top_k`` of the
scores masked past the query, and the mask rebuilt from its last entry. Those
lines stay here as the oracle.

Two oracles, because the old lines were not one rule everywhere: ``named`` is
the set of keys ``top_k`` names (what the plain-XLA branch gathers and the
benchmark's reference attends), ``rebuilt`` the served branch's old mask. They
are the same mask on every input but one kind: ``top_k`` orders ``-0.0``
below ``+0.0`` (the floats' total order, on the CPU and on a v5e: PERF.md
PR 47) while the rebuild compared as floats, so with both zeros AT the
threshold the rebuild named more or fewer than K keys. The kernel is held to
``named`` everywhere, and ``rebuilt`` to ``named`` wherever it was a rule.
"""

from __future__ import annotations

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from arkflow_tpu.ops import topk_select
from arkflow_tpu.ops.topk_select import dsa_topk_select


def _top_k(scores, positions, topk):
    ctx = scores.shape[-1]
    key_pos = jnp.arange(ctx)
    seen = key_pos <= positions[..., None]
    scores = jnp.where(seen, scores, -jnp.inf)
    top, idx = jax.lax.top_k(scores, min(topk, ctx))
    return scores, seen, key_pos, top, idx


def rebuilt(scores, positions, topk):
    """The served branch's mask as PR 46 left it, line for line."""
    scores, seen, key_pos, top, idx = _top_k(scores, positions, topk)
    # the last chosen's score and position say who else was chosen: the
    # sort is stable, so of its equals those before it
    kth, last = top[..., -1:], idx[..., -1:]
    chosen = (scores > kth) | ((scores == kth) & (key_pos <= last))
    return np.asarray((chosen & seen).astype(jnp.float32))


def named(scores, positions, topk):
    """The keys ``top_k`` names (``ok``: the entries that name one)."""
    scores, _, _, top, idx = _top_k(scores, positions, topk)
    ctx = scores.shape[-1]
    hit = jax.nn.one_hot(idx, ctx) * (top > -jnp.inf)[..., None]
    return np.asarray(hit.max(-2))


#: (rows, queries a row, context, K): a decode step's [B, 1, ctx], a chunk
#: tile's [1, 64, ctx], rows and a context that fill no block (iv), and a K
#: the context does not reach
SHAPES = {"decode": (5, 1, 256, 16), "chunk_tile": (1, 64, 384, 32),
          "ragged": (3, 3, 200, 16), "short_context": (2, 1, 40, 64)}


def _positions(rng, b, c, ctx, k):
    """Decode lanes anywhere past K; a chunk's queries in a run."""
    if c == 1:
        return rng.randint(min(k, ctx - 1), ctx, (b, 1)).astype(np.int32)
    first = rng.randint(0, ctx - c + 1, (b, 1))
    return (first + np.arange(c)[None, :]).astype(np.int32)


def _scores(rng, case, shape):
    if case == "random":
        return rng.randn(*shape)
    if case == "runs_of_equals":       # seven values: runs across the threshold
        return rng.randint(-3, 4, shape)
    if case == "all_equal":
        return np.full(shape, 1.5)
    if case == "relu":                 # what an index score is: many +0.0
        return np.maximum(rng.randn(*shape) - 0.8, 0.0)
    if case == "one_zero":             # -0.0 alone ties like any score
        return np.where(rng.rand(*shape) < 0.6, -0.0, rng.randn(*shape))
    raise KeyError(case)


@pytest.mark.parametrize("case", ["random", "runs_of_equals", "all_equal",
                                  "relu", "one_zero"])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_the_mask_is_the_one_rebuilt_from_top_k(shape, case):
    b, c, ctx, k = SHAPES[shape]
    rng = np.random.RandomState(len(shape) * 31 + len(case))
    scores = jnp.asarray(_scores(rng, case, (b, c, ctx)), jnp.float32)
    positions = jnp.asarray(_positions(rng, b, c, ctx, k))
    got = np.asarray(dsa_topk_select(scores, positions, k=k, interpret=True))
    want = rebuilt(scores, positions, k)
    assert got.dtype == np.float32 and got.shape == (b, c, ctx)
    assert (got == want).all()
    assert (want == named(scores, positions, k)).all()
    assert (got.sum(-1) == np.minimum(np.asarray(positions) + 1, min(k, ctx))).all()


@pytest.mark.parametrize("case", ["random", "runs_of_equals"])
@pytest.mark.parametrize("seen", ["fewer", "exactly", "one_more", "mixed"])
@pytest.mark.parametrize("shape", ["decode", "chunk_tile"])
def test_rows_that_have_seen_about_k_keys(shape, seen, case):
    """(iii): every row short of K keys (the search is skipped: each seen key
    is chosen), at exactly K, one past it (one key is left out: the search's
    smallest job), and a block that holds all three."""
    b, c, ctx, k = SHAPES[shape]
    rng = np.random.RandomState(7)
    at = {"fewer": k - 3, "exactly": k - 1, "one_more": k}
    n = b * c
    positions = (np.full(n, at[seen]) if seen != "mixed"
                 else np.asarray(list(at.values()) + [0, ctx - 1])[np.arange(n) % 5])
    positions = jnp.asarray(positions.reshape(b, c), jnp.int32)
    scores = jnp.asarray(_scores(rng, case, (b, c, ctx)), jnp.float32)
    got = np.asarray(dsa_topk_select(scores, positions, k=k, interpret=True))
    assert (got == rebuilt(scores, positions, k)).all()
    assert (got.sum(-1) == np.minimum(np.asarray(positions) + 1, k)).all()


@pytest.mark.parametrize("shape", list(SHAPES))
def test_both_zeros_at_the_threshold_are_chosen_as_top_k_names_them(shape):
    """(ii), ``+0.0`` and ``-0.0`` mixed: ``top_k`` takes every ``+0.0``
    before any ``-0.0`` and the kernel names the same keys; the old rebuild
    did not, where both sat at the threshold (it counted on one score)."""
    b, c, ctx, k = SHAPES[shape]
    rng = np.random.RandomState(11)
    zeros = np.where(rng.rand(b, c, ctx) < 0.5, 0.0, -0.0)
    scores = jnp.asarray(np.where(rng.rand(b, c, ctx) < 0.1,
                                  rng.randint(-1, 2, (b, c, ctx)), zeros), jnp.float32)
    positions = jnp.asarray(_positions(rng, b, c, ctx, k))
    got = np.asarray(dsa_topk_select(scores, positions, k=k, interpret=True))
    want = named(scores, positions, k)
    assert (got == want).all()
    assert (got.sum(-1) == np.minimum(np.asarray(positions) + 1, min(k, ctx))).all()
    if k < ctx:
        old = rebuilt(scores, positions, k)
        assert (old.sum(-1) != want.sum(-1)).any()   # why ``named`` is the oracle


def test_the_order_is_the_floats_total_order():
    """Largest first: +inf, finite, +0.0, -0.0, negative; equals by position.
    A key after the query is not read, whatever it holds."""
    row = [-5.0, -0.0, 0.0, 3.0, -2.0, np.inf, 0.0, -0.0, 3.0, np.nan]
    order = [5, 3, 8, 2, 6, 1, 7, 4, 0]               # position 9 is not seen
    scores = jnp.asarray([[row] * len(order)], jnp.float32)
    positions = jnp.full((1, len(order)), 8, jnp.int32)
    for k in range(1, len(order) + 1):
        got = np.asarray(dsa_topk_select(scores, positions, k=k, interpret=True))[0, 0]
        assert sorted(np.flatnonzero(got)) == sorted(order[:k]), k
        assert (got == named(scores, positions, k)[0, 0]).all(), k


def test_a_call_is_cut_into_blocks_of_whole_sublane_tiles(monkeypatch):
    """More rows than a block holds, and a last block of padding rows: the
    blocks are independent (a block of short rows skips its search beside
    one that does not)."""
    monkeypatch.setattr(topk_select, "_SELECT_ROWS", 8)  # read as the call is traced
    rng = np.random.RandomState(5)
    scores = jnp.asarray(rng.randint(-4, 5, (21, 1, 136)), jnp.float32)
    positions = jnp.asarray(np.r_[np.arange(8), rng.randint(8, 136, 13)]
                            .reshape(21, 1), jnp.int32)
    got = np.asarray(dsa_topk_select(scores, positions, k=24, interpret=True))
    assert (got == rebuilt(scores, positions, 24)).all()
