"""The expert product above one token tile: grouped by expert.

``ops/moe_grouped.grouped_expert_swiglu`` (what ``moe_expert_swiglu`` runs
for a call of more than ``_TOKEN_TILE`` rows) against the float32 loop over
experts that ``tests/test_mla_moe.py`` holds the one-tile kernel to, at its
tolerance (``4 * 2**-8`` of the largest output), interpreted on the CPU:
row counts around and above the tile, a block past one call's bound, every
load the router can make (one expert taking every row, experts with no row,
rows with no held expert, a call that hits no routed expert), shared
columns of weight 1 and of a sigmoid gate, a partial chunk's padded rows,
the stacked form, and the two kernels against each other at the threshold.
The server's counter of grouped products is counted by hand at the end.
"""

from __future__ import annotations

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from arkflow_tpu.obs import global_registry
from arkflow_tpu.ops import moe_experts, moe_grouped
from arkflow_tpu.ops.moe_experts import moe_expert_swiglu, runs_grouped

E, D, F = 10, 64, 256


def _weights(rng, e=E, d=D, f=F, layers=None):
    lead = () if layers is None else (layers,)
    wg, wu = (jnp.asarray(rng.normal(size=(*lead, e, d, f)) / 8, jnp.bfloat16)
              for _ in "gu")
    wd = jnp.asarray(rng.normal(size=(*lead, e, f, d)) / 16, jnp.bfloat16)
    return wg, wu, wd


def _loop_over_experts(x, cw, wg, wu, wd):
    """The float32 loop over experts (``tests/test_mla_moe.py``)."""
    want = np.zeros(x.shape, np.float32)
    xf = np.asarray(x, np.float32)
    for j in range(cw.shape[1]):
        rows = np.flatnonzero(cw[:, j])
        if rows.size:
            g = xf[rows] @ np.asarray(wg[j], np.float32)
            h = g / (1 + np.exp(-g)) * (xf[rows] @ np.asarray(wu[j], np.float32))
            want[rows] += cw[rows, j, None] * (h @ np.asarray(wd[j], np.float32))
    return want


def _routed(rng, tokens, e=E, among=(0, 1, 1, 1, 2, 7), k=2):
    """Top-``k`` of a skewed choice: uneven groups, experts 3-6, 8 empty."""
    cw = np.zeros((tokens, e), np.float32)
    for t in range(tokens):
        for j in rng.choice(among, k, replace=False):
            cw[t, j] = rng.uniform(0.1, 1.0)
    return cw


def _check(x, cw, wg, wu, wd, layer=None):
    stack = (wg, wu, wd) if layer is None else (wg[layer], wu[layer], wd[layer])
    want = _loop_over_experts(x, cw, *stack)
    got = np.asarray(moe_expert_swiglu(x, jnp.asarray(cw), wg, wu, wd, layer,
                                       interpret=True), np.float32)
    assert got.shape == want.shape
    tol = 4 * 2.0 ** -8 * max(np.abs(want).max(), 1e-6)
    assert np.abs(got - want).max() <= tol
    return got, want


def _x(rng, tokens, d=D):
    return jnp.asarray(rng.normal(size=(tokens, d)), jnp.bfloat16)


@pytest.mark.parametrize("tokens", [130, 256, 300, 512,
                                    moe_grouped.GROUPED_ROWS + 130])
def test_grouped_product_equals_loop_over_experts(tokens):
    """Uneven groups, empty groups, a shared column; 130 / 300 rows padded
    to row tiles, 642 rows two calls (the second a partial block)."""
    assert runs_grouped(tokens)
    rng = np.random.RandomState(tokens)
    cw = _routed(rng, tokens)
    cw[:, 9] = 1.0
    _check(_x(rng, tokens), cw, *_weights(rng))


@pytest.mark.parametrize("tokens", [256, 300])
def test_one_expert_takes_every_row(tokens):
    """Dropless at any load: expert 4 takes all rows (two, three row tiles
    of one group), the others what the router gave them."""
    rng = np.random.RandomState(tokens + 1)
    cw = _routed(rng, tokens)
    cw[:, 4] = rng.uniform(0.2, 1.0, tokens)
    got, _ = _check(_x(rng, tokens), cw, *_weights(rng))
    assert (np.abs(got).max(axis=-1) > 0).all()


def test_experts_with_no_row_are_skipped():
    """Only experts 2 and 7 are hit: NaN weights in every other expert's
    matrices never reach the output (an expert with no row multiplies
    nothing; the kernel's grid skips its math)."""
    rng = np.random.RandomState(5)
    tokens = 200
    cw = _routed(rng, tokens, among=(2, 7), k=2)
    wg, wu, wd = _weights(rng)
    dead = jnp.asarray([j not in (2, 7) for j in range(E)])[:, None, None]
    wg, wu, wd = (jnp.where(dead, jnp.nan, w) for w in (wg, wu, wd))
    x = _x(rng, tokens)
    got = np.asarray(moe_expert_swiglu(x, jnp.asarray(cw), wg, wu, wd,
                                       interpret=True), np.float32)
    assert np.isfinite(got).all()
    live = [np.asarray(w, np.float32)[[2, 7]] for w in (wg, wu, wd)]
    want = _loop_over_experts(x, cw[:, [2, 7]], *live)
    assert np.abs(got - want).max() <= 4 * 2.0 ** -8 * np.abs(want).max()


@pytest.mark.parametrize("shared", ["none", "one"])
def test_rows_with_no_held_expert(shared):
    """An ``experts_held`` cut: most rows carry no held routed expert (all
    zeros in ``cw``) and come out as zeros, plus the shared expert's part
    where there is one."""
    rng = np.random.RandomState(11)
    tokens = 384
    cw = _routed(rng, tokens)
    cw[rng.uniform(size=tokens) < 0.8] = 0.0
    if shared == "one":
        cw[:, 9] = 1.0
    got, _ = _check(_x(rng, tokens), cw, *_weights(rng))
    if shared == "none":
        assert (got[(cw == 0).all(axis=1)] == 0).all()
        assert (np.abs(got[(cw != 0).any(axis=1)]).max(axis=-1) > 0).all()


@pytest.mark.parametrize("shared", ["none", "one"])
def test_a_call_that_hits_no_routed_expert(shared):
    """No row of the call chose a held expert: zeros, or the shared
    expert's product alone."""
    rng = np.random.RandomState(12)
    tokens = 256
    cw = np.zeros((tokens, E), np.float32)
    if shared == "one":
        cw[:, 9] = 1.0
    got, _ = _check(_x(rng, tokens), cw, *_weights(rng))
    assert (got == 0).all() == (shared == "none")


@pytest.mark.parametrize("gate", ["one", "sigmoid"])
def test_shared_columns(gate):
    """Two shared experts behind the routed ones: weight 1 on every live
    row, or (Qwen3-Next) a sigmoid gate a row."""
    rng = np.random.RandomState(13)
    tokens = 300
    cw = _routed(rng, tokens)
    cw[:, 8:] = (1.0 if gate == "one" else
                 1 / (1 + np.exp(-rng.normal(size=(tokens, 2)))))
    _check(_x(rng, tokens), cw, *_weights(rng))


def test_a_partial_chunks_padded_rows():
    """A prompt's last chunk: the rows past its tokens carry weight 0 in
    every column, the shared ones too, and come out as zeros whatever they
    hold."""
    rng = np.random.RandomState(14)
    tokens, present = 256, 70
    cw = _routed(rng, tokens)
    cw[:, 9] = 1.0
    cw[present:] = 0.0
    x = _x(rng, tokens)
    got, _ = _check(x, cw, *_weights(np.random.RandomState(15)))
    assert (got[present:] == 0).all()
    other = x.at[present:].set(jnp.asarray(rng.normal(size=(tokens - present, D)) * 50,
                                           jnp.bfloat16))
    again, _ = _check(other, cw, *_weights(np.random.RandomState(15)))
    np.testing.assert_array_equal(got, again)


@pytest.mark.parametrize("layer", [0, 2])
def test_the_stacked_form(layer):
    """A stack of three layers rides whole; the index map picks the layer."""
    rng = np.random.RandomState(16 + layer)
    tokens = 200
    cw = _routed(rng, tokens)
    cw[:, 9] = 1.0
    _check(_x(rng, tokens), cw, *_weights(rng, layers=3), layer=layer)


def test_a_hidden_size_of_more_than_one_lane_chunk():
    """The per-row sum walks the hidden size in chunks of lanes: 1,152 is
    one whole chunk and a part."""
    d = moe_grouped._LANE_CHUNK + 128
    rng = np.random.RandomState(18)
    tokens = 150
    cw = _routed(rng, tokens, e=4, among=(0, 1, 1, 2), k=2)
    cw[:, 3] = 1.0
    _check(_x(rng, tokens, d), cw, *_weights(rng, e=4, d=d, f=128))


@pytest.mark.parametrize("tokens", [128, 129])
def test_the_two_kernels_agree_at_the_threshold(tokens):
    """128 rows take the one-tile kernel, 129 the grouped one; on the same
    inputs each agrees with the other kernel (128 rows grouped directly; the
    129 as a tile of 128 and a tile of one) within the tolerance both are
    held to."""
    assert runs_grouped(tokens) == (tokens == 129)
    rng = np.random.RandomState(tokens)
    cw = _routed(rng, tokens)
    cw[:, 9] = 1.0
    x, (wg, wu, wd) = _x(rng, tokens), _weights(rng)
    served, want = _check(x, cw, wg, wu, wd)
    cwj = jnp.asarray(cw)
    if tokens == 128:
        other = moe_grouped.grouped_expert_swiglu(
            x, cwj, wg[None], wu[None], wd[None], jnp.zeros(1, jnp.int32), True)
    else:
        other = jnp.concatenate([
            moe_expert_swiglu(x[a:b], cwj[a:b], wg, wu, wd, interpret=True)
            for a, b in ((0, 128), (128, 129))])
    other = np.asarray(other, np.float32)
    tol = 4 * 2.0 ** -8 * np.abs(want).max()
    assert np.abs(other - want).max() <= tol
    assert np.abs(other - served).max() <= tol


def test_the_grouped_slice_width_fits_its_budget():
    """The slice of the expert width a grid step takes, at the six widths
    served: three double-buffered blocks inside the budget."""
    for d, f in ((2048, 768), (5120, 1536), (6144, 2048), (4096, 2048),
                 (2048, 512), (2048, 1792)):
        tf = moe_grouped.grouped_slice_width(d, f)
        assert f % tf == 0 and tf % 128 == 0
        assert 3 * 2 * d * tf * 2 <= moe_grouped._SLICE_BUDGET
    assert moe_grouped.grouped_slice_width(64, 100) == 100  # no lane multiple


# -- the server's counter --------------------------------------------------------


def _grouped_metric(kind):
    return global_registry().counter(
        "arkflow_gen_moe_grouped_products_total",
        labels={"model": "decoder_lm", "kind": kind})


@pytest.mark.parametrize("chunk,grouped", [(16, False), (144, True)])
def test_server_counts_grouped_products(chunk, grouped):
    """A routed tiny model on the kernel path: a prompt of 150 tokens in
    chunks of 144 rows runs its two chunks' two expert layers grouped (4
    counted); in chunks of 16 none. Decode steps (4 lanes) never do."""
    from arkflow_tpu.components import Resource, build_component, ensure_plugins_loaded

    ensure_plugins_loaded()
    tiny = dict(vocab_size=128, dim=32, layers=3, heads=4, kv_heads=4, head_dim=8,
                ffn=64, max_seq=256, n_routed_experts=8, num_experts_per_tok=2,
                n_shared_experts=1, moe_intermediate_size=16,
                first_k_dense_replace=1)
    proc = build_component("processor", {
        "type": "tpu_generate", "model": "decoder_lm", "model_config": tiny,
        "serving": "continuous", "max_input": 160, "max_new_tokens": 3, "slots": 4,
        "page_size": 8, "seq_buckets": [160], "prefill_chunk": chunk, "eos_id": -1,
        "decode_kernel": "paged", "kernel_interpret": True, "seed": 3}, Resource())
    server = proc._server
    before = {k: _grouped_metric(k).value for k in ("decode", "chunk", "prefill")}
    prompt = np.random.RandomState(1).randint(1, 128, 150).tolist()
    out = asyncio.run(server.generate(prompt, 3))
    assert len(out) == 3
    delta = {k: _grouped_metric(k).value - before[k] for k in before}
    assert delta == {"decode": 0, "prefill": 0,
                     "chunk": 2 * 2 if grouped else 0}
