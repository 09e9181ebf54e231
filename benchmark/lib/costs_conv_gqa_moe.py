"""Needed bytes of a decoder whose layers are gated short convolutions among
grouped-query attention layers of narrow heads, two leading dense SwiGLUs
and then routed experts held WHOLE, no shared expert (LFM2-8B-A1B): the
counts behind ``narrow_attn_hbm_pct``, ``conv_moe_decode_hbm_pct`` and
``conv_state_share_of_cache_pct``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
once — bf16 weights (the router float32, as it is placed), of the routed
experts only those a step actually hit (the program's counter), of the cache
the K and V rows a query may attend at their PUBLISHED width (8 K/V heads x
64 x K and V x 2 B = 2,048 B a token an attention layer; the row-major pools
hold exactly that: no padding to count against the kernel), and of the conv
windows a lane's two rows of 2,048 read AND written a conv layer. A conv
layer caches nothing a token. A lower bound on what any implementation
moves: a share over 100 % means the count is wrong. The sizes are read under
THIS source's published keys (``num_experts``, ``num_dense_layers``,
``conv_L_cache``; the head is ``hidden_size / num_attention_heads``).
"""

from __future__ import annotations

from benchmark.lib.costs_hetero_gqa_moe import expert_product_bytes
from benchmark.lib.costs_mla_moe import expert_params


def kv_row_bytes(*, kv_heads: int, head_dim: int, value_bytes: int = 2) -> int:
    """One token's K and V of one attention layer: 8 x 64 x 2 x 2 B = 2,048."""
    return kv_heads * 2 * head_dim * value_bytes


def slot_bytes(*, conv_layers: int, taps: int, hidden: int,
               value_bytes: int = 2) -> int:
    """What one busy slot holds of the conv pool, whatever its context: the
    last ``taps - 1`` gated inputs of every conv layer (9 x 2 x 2,048 x 2 B
    = 73,728)."""
    return conv_layers * (taps - 1) * hidden * value_bytes


def attention_params(*, hidden: int, heads: int, kv_heads: int,
                     head_dim: int) -> int:
    """Matrix parameters of one GQA mixer: q and o (hidden x heads head_dim
    each), k and v (hidden x kv_heads head_dim each): 10.49 M. The two
    per-head norm scales are vectors and not counted."""
    return 2 * hidden * head_dim * (heads + kv_heads)


def conv_params(*, hidden: int, taps: int) -> int:
    """Parameters of one conv mixer: the input projection (hidden x 3
    hidden), the depthwise taps (hidden x taps) and the output projection
    (hidden x hidden): 16.78 M."""
    return hidden * (4 * hidden + taps)


def attention_bytes(*, kv_heads: int, head_dim: int, heads: int, keys: float,
                    queries: float, layers: int, value_bytes: int = 2) -> float:
    """Bytes the paged attention of ONE step has to move over ``layers``
    attention layers: the K and V rows of the ``keys`` a query may attend
    (summed over the step's queries), each read once for its whole group of
    query heads, plus every query in and its output back (heads x head_dim
    each)."""
    row = kv_row_bytes(kv_heads=kv_heads, head_dim=head_dim,
                       value_bytes=value_bytes)
    return layers * (keys * row + queries * 2 * heads * head_dim * value_bytes)


def decode_step_bytes(*, hidden: int, layers: int, dense_layers: int,
                      attn_layers: int, heads: int, kv_heads: int,
                      head_dim: int, taps: int, dense_width: int,
                      moe_width: int, experts: int, vocab: int,
                      experts_hit: float, lanes: float, context: float,
                      weight_bytes: int = 2, router_bytes: int = 4) -> float:
    """Bytes one chip has to move for one lockstep decode step: the output
    head; every conv and attention mixer; the leading dense layers' SwiGLU;
    per expert layer the router (float32) and the ``experts_hit`` experts
    the step touched (mean a layer: ``costs_hetero_gqa_moe.
    expert_product_bytes``, 22.0 MB each, no shared expert); the K and V rows its ``lanes`` queries
    may attend on the attention layers (``context``: their context lengths
    summed); and each lane's conv windows, read and written. The embedding
    table is read one row a token: not counted."""
    conv_layers = layers - attn_layers
    expert_layers = layers - dense_layers
    weights = (hidden * vocab * weight_bytes
               + (conv_layers * conv_params(hidden=hidden, taps=taps)
                  + attn_layers * attention_params(
                      hidden=hidden, heads=heads, kv_heads=kv_heads,
                      head_dim=head_dim)) * weight_bytes
               + dense_layers * expert_params(hidden=hidden, width=dense_width)
               * weight_bytes
               + expert_layers * hidden * experts * router_bytes
               + expert_product_bytes(hidden=hidden, moe_width=moe_width,
                                      experts_hit=experts_hit,
                                      expert_layers=expert_layers,
                                      weight_bytes=weight_bytes))
    cache = (attn_layers * kv_row_bytes(kv_heads=kv_heads, head_dim=head_dim)
             * context
             + 2 * lanes * slot_bytes(conv_layers=conv_layers, taps=taps,
                                      hidden=hidden))
    return weights + cache


def sizes_of(view):
    """The keyword sizes of ``decode_step_bytes`` from the cell's published
    keys as run; None where the file is not of this layout."""
    s = view.sizes
    if "conv_L_cache" not in s or "layer_types" not in s:
        return None
    kinds = list(s["layer_types"])[:int(s["num_hidden_layers"])]
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                dense_layers=s["num_dense_layers"],
                attn_layers=kinds.count("full_attention"),
                heads=s["num_attention_heads"],
                kv_heads=s["num_key_value_heads"],
                head_dim=s["hidden_size"] // s["num_attention_heads"],
                taps=s["conv_L_cache"], dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"], experts=s["num_experts"],
                vocab=s["vocab_size"])


def attention_share(view):
    """Share (%) of the chip's HBM bandwidth the narrow-head attention
    kernel reaches in a decode step: the needed bytes of the attention
    layers over the peak and over the kernel's device time in a ``_decode``
    execution. None where the trace has no such op."""
    from benchmark.lib.costs_mla_moe import decode_context, kernel_ms_per_decode

    ms = kernel_ms_per_decode(view, r"paged_flash_attention")
    ctx, s = decode_context(view), sizes_of(view)
    if ms is None or ctx is None or s is None:
        return None
    lanes, context = ctx
    nbytes = attention_bytes(kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                             heads=s["heads"], keys=context, queries=lanes,
                             layers=s["attn_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
