"""Needed bytes of a per-head (GQA) decoder with a layer pattern — sliding
window layers beside full layers — and a held share of routed experts
(K-EXAONE): the counts behind ``gqa_window_attn_hbm_pct``,
``gqa_full_attn_hbm_pct``, ``gqa_moe_expert_hbm_pct``,
``gqa_moe_decode_hbm_pct`` and ``kv_window_pool_live_pct``.

"Needed" as in ``lib/costs.py`` and ``lib/costs_mla_moe.py``: what a perfect
implementation has to move once — bf16 weights (the router float32, as it
is placed), of the routed experts HELD only those a step actually hit (the
program's counter), and of the cache the K and V rows a query may attend:
all of a lane's context on a full layer, the last ``sliding_window`` tokens
on a sliding one. A lower bound on what any implementation moves: a share
over 100 % means the count is wrong. The sizes are read under THIS
source's published keys (``num_experts``, ``num_shared_experts``,
``num_key_value_heads``, ``head_dim``), which is why the cell does not list
the ``moe_*`` readers of the DeepSeek-style files.
"""

from __future__ import annotations

from benchmark.lib.costs_mla_moe import expert_params


def kv_row_bytes(*, kv_heads: int, head_dim: int, value_bytes: int = 2) -> int:
    """One token's K and V of one layer: 8 x 128 x 2 x 2 B = 4,096 B."""
    return 2 * kv_heads * head_dim * value_bytes


def attention_params(*, hidden: int, heads: int, kv_heads: int, head_dim: int) -> int:
    """Matrix parameters of one GQA block: q and o (hidden x heads head_dim
    each) and k and v (hidden x kv_heads head_dim each). Norm scales are
    vectors and not counted. 113.2 M at the published sizes."""
    return 2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim


def attention_bytes(*, kv_heads: int, head_dim: int, heads: int, keys: float,
                    queries: float, layers: int, value_bytes: int = 2) -> float:
    """Bytes the paged attention of ONE step has to move over ``layers``
    layers of a kind: the K and V rows of the ``keys`` a query may attend
    (summed over the step's queries), each read once for its whole group of
    query heads, plus every query in and its output back."""
    row = kv_row_bytes(kv_heads=kv_heads, head_dim=head_dim, value_bytes=value_bytes)
    per_query = 2 * heads * head_dim * value_bytes
    return layers * (keys * row + queries * per_query)


def window_keys(*, context: float, queries: float, window: int) -> float:
    """Keys a sliding layer's queries may attend, summed: ``window`` each
    once the context is that long (the mix's shortest prompt, 64, is not:
    its first decode steps count their context)."""
    return min(context, queries * window)


def layer_counts(sizes: dict) -> tuple[int, int]:
    """(full layers, sliding layers) of a configuration as run: the first
    ``num_hidden_layers`` entries of its ``layer_types``."""
    kinds = list(sizes["layer_types"])[:int(sizes["num_hidden_layers"])]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def expert_product_bytes(*, hidden: int, moe_width: int, shared: int,
                         experts_hit: float, expert_layers: int,
                         weight_bytes: int = 2) -> float:
    """Bytes the routed + shared expert products of ONE step have to read:
    per expert layer the held experts hit (mean a layer) and the shared
    experts, each three ``hidden x moe_width`` matrices (75.5 MB)."""
    one = expert_params(hidden=hidden, width=moe_width) * weight_bytes
    return expert_layers * (experts_hit + shared) * one


def decode_step_bytes(*, hidden: int, layers: int, dense_layers: int,
                      heads: int, kv_heads: int, head_dim: int,
                      dense_width: int, moe_width: int, router_outputs: int,
                      shared: int, vocab: int, experts_hit: float,
                      full_layers: int, sliding_layers: int, window: int,
                      lanes: float, context: float, weight_bytes: int = 2,
                      router_bytes: int = 4) -> float:
    """Bytes one chip has to read for one lockstep decode step: the output
    head; per leading dense layer its attention and its dense SwiGLU; per
    expert layer its attention, the router (float32, all its outputs), the
    shared experts and the ``experts_hit`` held experts the step touched
    (mean a layer); and the cache rows its ``lanes`` queries may attend
    (``context``: their context lengths summed): all of them on a full
    layer, the last ``window`` on a sliding one. The embedding table is
    read one row a token: not counted."""
    attn = attention_params(hidden=hidden, heads=heads, kv_heads=kv_heads,
                            head_dim=head_dim) * weight_bytes
    expert_layers = layers - dense_layers
    weights = (hidden * vocab * weight_bytes
               + dense_layers * (attn + expert_params(
                   hidden=hidden, width=dense_width) * weight_bytes)
               + expert_layers * (attn + hidden * router_outputs * router_bytes)
               + expert_product_bytes(hidden=hidden, moe_width=moe_width,
                                      shared=shared, experts_hit=experts_hit,
                                      expert_layers=expert_layers,
                                      weight_bytes=weight_bytes))
    row = kv_row_bytes(kv_heads=kv_heads, head_dim=head_dim)
    cache = row * (full_layers * context + sliding_layers * window_keys(
        context=context, queries=lanes, window=window))
    return weights + cache


def sizes_of(view) -> dict:
    """The keyword sizes of ``decode_step_bytes`` from the cell's published
    keys as run; None where the file is not of this layout."""
    s = view.sizes
    if "num_shared_experts" not in s or "layer_types" not in s:
        return None
    full, sliding = layer_counts(s)
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                dense_layers=s["first_k_dense_replace"],
                heads=s["num_attention_heads"],
                kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"],
                router_outputs=s["router_outputs"],
                shared=s["num_shared_experts"], vocab=s["vocab_size"],
                full_layers=full, sliding_layers=sliding,
                window=s["sliding_window"])


def attention_share(view, op_re: str, sliding: bool):
    """Share (%) of the chip's HBM bandwidth the attention kernel matching
    ``op_re`` reaches in a decode step: the needed bytes of its kind's
    layers over the peak and over its device time in a ``_decode``
    execution. None where the trace has no such op."""
    from benchmark.lib.costs_mla_moe import decode_context, kernel_ms_per_decode

    ms, ctx, sizes = kernel_ms_per_decode(view, op_re), decode_context(view), sizes_of(view)
    if ms is None or ctx is None or sizes is None:
        return None
    lanes, context = ctx
    keys = window_keys(context=context, queries=lanes,
                       window=sizes["window"]) if sliding else context
    nbytes = attention_bytes(
        kv_heads=sizes["kv_heads"], head_dim=sizes["head_dim"],
        heads=sizes["heads"], keys=keys, queries=lanes,
        layers=sizes["sliding_layers" if sliding else "full_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
