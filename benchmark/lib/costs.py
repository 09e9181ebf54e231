"""Needed operations and bytes, computed from shapes, and the table of peaks.

"Needed" means what the algorithm requires at the shapes dispatched: a
multiply-add counts two operations; padding tokens that are dispatched are
counted (the device really multiplies them); bytes are those a perfect
implementation has to move once (bf16 weights, the K/V it attends over) —
not what today's program happens to move (it reads float32 masters and casts
them every step, which is why its share of the roofline is low).
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_peaks(device_kind: str) -> dict:
    """Peaks of one chip, keyed by the EXACT ``device_kind``; a kind that is
    not in ``benchmark/peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks on record for device_kind {device_kind!r} "
                       f"(known: {sorted(table)}); add it to "
                       "benchmark/peaks.json with its source")
    return table[device_kind]


def bert_forward_flops(*, hidden: int, layers: int, ffn: int, batch: int,
                       seq: int, num_labels: int = 2) -> float:
    """One encoder forward over ``batch`` rows padded to ``seq`` tokens.

    Per layer and token: Q, K, V and the attention output projection
    (4 x hidden^2 multiply-adds), the two feed-forward products
    (2 x hidden x ffn), and attention itself — scores and the weighted sum,
    each ``seq x hidden`` multiply-adds per token over all heads. Then the
    pooler (hidden^2) and classifier on the [CLS] row. Embedding lookups,
    layer norms, GELU and softmax are not matrix products and not counted.
    """
    tokens = batch * seq
    per_token_layer = 4 * hidden * hidden + 2 * hidden * ffn + 2 * seq * hidden
    macs = tokens * layers * per_token_layer
    macs += batch * (hidden * hidden + hidden * num_labels)
    return 2.0 * macs


def decoder_weight_params(*, dim: int, layers: int, heads: int, kv_heads: int,
                          ffn: int, vocab: int) -> int:
    """Matrix parameters one decode step has to read: per layer Q and O
    (dim x heads*dh each), K and V (dim x kv_heads*dh each) and the three
    SwiGLU matrices; plus the output head. The embedding table is read one
    row per token and norms are vectors: neither is counted."""
    dh = dim // heads
    per_layer = (2 * dim * heads * dh + 2 * dim * kv_heads * dh
                 + 3 * dim * ffn)
    return layers * per_layer + dim * vocab


def decode_step_bytes(*, dim: int, layers: int, heads: int, kv_heads: int,
                      ffn: int, vocab: int, kv_tokens: float,
                      chips: int = 1, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes ONE chip has to read for one lockstep decode step: its share of
    the bf16 weights plus its share of the K and V of every token the active
    slots attend over (``kv_tokens``: the sum of context lengths over active
    slots). Under tensor parallelism weights and KV heads split over
    ``chips``."""
    dh = dim // heads
    weights = decoder_weight_params(
        dim=dim, layers=layers, heads=heads, kv_heads=kv_heads, ffn=ffn,
        vocab=vocab) * weight_bytes
    kv = kv_tokens * layers * 2 * kv_heads * dh * kv_bytes
    return (weights + kv) / chips


def decode_step_flops(*, dim: int, layers: int, heads: int, kv_heads: int,
                      ffn: int, vocab: int, slots: int,
                      kv_tokens: float) -> float:
    """Operations of one lockstep decode step over ``slots`` lanes (inactive
    lanes are computed too): every weight once per lane, plus scores and
    weighted sum over the attended tokens."""
    dh = dim // heads
    macs = slots * decoder_weight_params(
        dim=dim, layers=layers, heads=heads, kv_heads=kv_heads, ffn=ffn,
        vocab=vocab)
    macs += kv_tokens * layers * 2 * heads * dh
    return 2.0 * macs
