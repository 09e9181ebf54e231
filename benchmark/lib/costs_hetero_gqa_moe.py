"""Needed bytes of a per-head (GQA) decoder whose layers' sizes go by kind —
full layers of 4 K/V heads beside sliding-window layers of 8, keys of 192
beside values of 128 — and a held share of routed experts with no shared
expert (MiMo-V2.5): the counts behind ``hetero_window_attn_hbm_pct``,
``hetero_full_attn_hbm_pct``, ``hetero_moe_expert_hbm_pct``,
``hetero_moe_decode_hbm_pct`` and ``kv_window_share_of_cache_pct``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
once — bf16 weights (the router float32, as it is placed), of the routed
experts HELD only those a step actually hit (the program's counter), and of
the cache the K and V rows a query may attend at their PUBLISHED widths (a
key of 192 and a value of 128: 640 B a K/V head a token): all of a lane's
context on a full layer, the last ``sliding_window`` tokens on a sliding one.
The pools HOLD a key in two parts of 128 lanes (768 B a head a token,
``paged_decode.cache_spec``): that padding is the implementation's, read by
the kernel and not needed, so it counts against the kernel's share. A lower
bound on what any implementation moves: a share over 100 % means the count
is wrong. The sizes are read under THIS source's published keys
(``swa_num_key_value_heads``, ``v_head_dim``, ``hybrid_layer_pattern``
through the file's ``layer_types``).
"""

from __future__ import annotations

from benchmark.lib.costs_mla_moe import expert_params
from benchmark.lib.costs_window_gqa_moe import layer_counts, window_keys


def kv_row_bytes(*, kv_heads: int, key_dim: int, value_dim: int,
                 value_bytes: int = 2) -> int:
    """One token's K and V of one layer at their published widths: 4 x
    (192 + 128) x 2 B = 2,560 B on a full layer, 8 x 320 x 2 B = 5,120 B on
    a sliding one."""
    return kv_heads * (key_dim + value_dim) * value_bytes


def held_row_bytes(*, kv_heads: int, key_dim: int, value_dim: int,
                   value_bytes: int = 2) -> int:
    """The same row as the page pools hold it: a key wider than 128 lanes
    and no multiple of them in parts of 128 (192 -> 256): 3,072 B and
    6,144 B."""
    held = key_dim if key_dim <= 128 or key_dim % 128 == 0 else -(-key_dim // 128) * 128
    return kv_heads * (held + value_dim) * value_bytes


def attention_params(*, hidden: int, heads: int, kv_heads: int, key_dim: int,
                     value_dim: int) -> int:
    """Matrix parameters of one GQA block of a kind: q (hidden x heads
    key_dim), k (hidden x kv_heads key_dim), v (hidden x kv_heads
    value_dim), o (heads value_dim x hidden). 89.13 M on a full layer, 94.37
    M on a sliding one. Norm scales and sinks are vectors and not counted."""
    return hidden * (heads * key_dim + kv_heads * (key_dim + value_dim)
                     + heads * value_dim)


def attention_bytes(*, kv_heads: int, key_dim: int, value_dim: int, heads: int,
                    keys: float, queries: float, layers: int,
                    value_bytes: int = 2) -> float:
    """Bytes the paged attention of ONE step has to move over ``layers``
    layers of a kind: the K and V rows of the ``keys`` a query may attend
    (summed over the step's queries), each read once for its whole group of
    query heads, plus every query in (heads x key_dim) and its output back
    (heads x value_dim)."""
    row = kv_row_bytes(kv_heads=kv_heads, key_dim=key_dim, value_dim=value_dim,
                       value_bytes=value_bytes)
    per_query = heads * (key_dim + value_dim) * value_bytes
    return layers * (keys * row + queries * per_query)


def expert_product_bytes(*, hidden: int, moe_width: int, experts_hit: float,
                         expert_layers: int, weight_bytes: int = 2) -> float:
    """Bytes the routed expert products of ONE step have to read: per
    expert layer the held experts hit (mean a layer), each three ``hidden x
    moe_width`` matrices (50.3 MB). No shared expert."""
    return expert_layers * experts_hit * expert_params(
        hidden=hidden, width=moe_width) * weight_bytes


def decode_step_bytes(*, hidden: int, layers: int, dense_layers: int,
                      heads: int, kv_heads: int, swa_kv_heads: int,
                      key_dim: int, value_dim: int, dense_width: int,
                      moe_width: int, router_outputs: int, vocab: int,
                      experts_hit: float, full_layers: int,
                      sliding_layers: int, window: int, lanes: float,
                      context: float, weight_bytes: int = 2,
                      router_bytes: int = 4) -> float:
    """Bytes one chip has to read for one lockstep decode step: the output
    head; every layer's attention at its kind's sizes; the leading dense
    layers' SwiGLU; per expert layer the router (float32, all its outputs)
    and the ``experts_hit`` held experts the step touched (mean a layer);
    and the cache rows its ``lanes`` queries may attend (``context``: their
    context lengths summed): all of them on a full layer, the last
    ``window`` on a sliding one, each at its kind's row. The embedding table
    is read one row a token: not counted."""
    def attn(kvh):
        return attention_params(hidden=hidden, heads=heads, kv_heads=kvh,
                                key_dim=key_dim, value_dim=value_dim)

    def row(kvh):
        return kv_row_bytes(kv_heads=kvh, key_dim=key_dim, value_dim=value_dim)

    expert_layers = layers - dense_layers
    weights = (hidden * vocab * weight_bytes
               + (full_layers * attn(kv_heads)
                  + sliding_layers * attn(swa_kv_heads)) * weight_bytes
               + dense_layers * expert_params(hidden=hidden, width=dense_width)
               * weight_bytes
               + expert_layers * hidden * router_outputs * router_bytes
               + expert_product_bytes(hidden=hidden, moe_width=moe_width,
                                      experts_hit=experts_hit,
                                      expert_layers=expert_layers,
                                      weight_bytes=weight_bytes))
    cache = (full_layers * row(kv_heads) * context
             + sliding_layers * row(swa_kv_heads) * window_keys(
                 context=context, queries=lanes, window=window))
    return weights + cache


def sizes_of(view):
    """The keyword sizes of ``decode_step_bytes`` from the cell's published
    keys as run; None where the file is not of this layout."""
    s = view.sizes
    if "swa_num_key_value_heads" not in s or "layer_types" not in s:
        return None
    full, sliding = layer_counts(s)
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                dense_layers=s["first_k_dense_replace"],
                heads=s["num_attention_heads"],
                kv_heads=s["num_key_value_heads"],
                swa_kv_heads=s["swa_num_key_value_heads"],
                key_dim=s["head_dim"], value_dim=s["v_head_dim"],
                dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"],
                router_outputs=s["router_outputs"], vocab=s["vocab_size"],
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
        kv_heads=sizes["swa_kv_heads" if sliding else "kv_heads"],
        key_dim=sizes["key_dim"], value_dim=sizes["value_dim"],
        heads=sizes["heads"], keys=keys, queries=lanes,
        layers=sizes["sliding_layers" if sliding else "full_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
