"""Needed bytes and FLOPs of what a layer pattern of latent layers adds to a
decode step — sparse-indexed full layers beside sliding-window layers — the
counts behind ``dsa_attn_hbm_pct``, ``dsa_index_hbm_pct`` and
``swa_attn_hbm_pct``.

"Needed" as in ``lib/costs.py`` and ``lib/costs_mla_moe.py``: what a perfect
implementation has to move once. A lower bound on what any implementation
moves: a share over 100 % means the count is wrong.
"""

from __future__ import annotations


def selected_attention_bytes(*, heads: int, kv_lora: int, rope: int,
                             selected: float, queries: float, layers: int,
                             value_bytes: int = 2) -> float:
    """Bytes the attention of the indexed layers of ONE step has to move:
    the latent row and rope key of every SELECTED token (``selected``: keys
    attended, summed over the step's queries: at most ``index_topk`` each,
    whatever the context) read once for all heads, plus each query's
    per-head latent and rope parts in and its per-head latent output back.
    2,048 rows x 1,152 B a lane a layer at the published sizes."""
    row = (kv_lora + rope) * value_bytes
    per_query = heads * ((kv_lora + rope) + kv_lora) * value_bytes
    return layers * (selected * row + queries * per_query)


def index_scores_bytes(*, index_heads: int, index_dim: int, context: float,
                       queries: float, layers: int, key_bytes: int = 2,
                       score_bytes: int = 4) -> float:
    """Bytes the indexer's scores of ONE step have to move: the index key
    of every token in context (``context``: summed over the step's queries;
    ``index_dim`` values: 256 B at the published size) read once for all
    index heads, each query's index heads and head weights in, and one
    float32 score a (query, key) out."""
    per_query = index_heads * index_dim * key_bytes + index_heads * 4
    return layers * (context * (index_dim * key_bytes + score_bytes)
                     + queries * per_query)


def window_attention_bytes(*, heads: int, kv_lora: int, rope: int, window: int,
                           context: float, queries: float, layers: int,
                           value_bytes: int = 2) -> float:
    """Bytes the sliding layers' attention of ONE step has to move: of each
    query's context only the last ``window`` tokens' latent rows and rope
    keys (``context``: the queries' context lengths summed; every one of
    them at least ``window`` long counts ``window``), read once for all
    heads, plus the queries in and the per-head latent outputs back.
    513 x 2,176 B a lane a layer at the published sizes."""
    row = (kv_lora + rope) * value_bytes
    per_query = heads * ((kv_lora + rope) + kv_lora) * value_bytes
    keys = min(context, queries * window)
    return layers * (keys * row + queries * per_query)


def selected_attention_flops(*, heads: int, kv_lora: int, rope: int,
                             selected: float, layers: int) -> float:
    """FLOPs of the absorbed attention over the selected keys: per (query
    head, key) a score over ``kv_lora + rope`` values and a value sum over
    ``kv_lora``, 2 FLOPs a multiply-add."""
    return layers * heads * selected * 2 * ((kv_lora + rope) + kv_lora)


def index_scores_flops(*, index_heads: int, index_dim: int, context: float,
                       layers: int) -> float:
    """FLOPs of the index scores: per (index head, query, key) a dot over
    ``index_dim`` values, then the relu and the weighted sum over heads."""
    return layers * context * index_heads * (2 * index_dim + 3)


def layer_counts(sizes: dict) -> tuple[int, int]:
    """(indexed full layers, sliding layers) of a configuration as run: the
    first ``num_hidden_layers`` entries of its ``layer_types``."""
    kinds = list(sizes["layer_types"])[:int(sizes["num_hidden_layers"])]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


# -- what the readers share -----------------------------------------------------


def dsa_decode_step(view):
    """(queries, keys selected, keys in context) of a mean decode step over
    the window, per indexed layer, from the program's counters; None where
    the program has none (a parent without indexed layers)."""
    selected = view.counter("arkflow_gen_dsa_selected_total", kind="decode")
    context = view.counter("arkflow_gen_dsa_context_total", kind="decode")
    _, steps = view.hist("arkflow_gen_moe_experts_hit", kind="decode")
    busy = view.gauge("arkflow_gen_slots_busy")
    full, _ = layer_counts(view.sizes) if "layer_types" in view.sizes else (0, 0)
    if steps <= 0 or context <= 0 or not busy or not full:
        return None
    return (sum(busy) / len(busy), selected / steps / full, context / steps / full)
