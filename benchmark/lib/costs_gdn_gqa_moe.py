"""Needed bytes and operations of a decoder whose layers are Gated DeltaNet
mixers among gated grouped-query attention layers of 256-wide heads, every
layer with softmax-routed experts of which a share is held and one shared
expert (Qwen3-Next-80B-A3B): the counts behind ``gdn_update_hbm_pct``,
``gdn_scan_roofline_pct``, ``gdn_full_attn_hbm_pct``, ``gdn_moe_expert_hbm_pct``,
``gdn_moe_decode_hbm_pct`` and ``gdn_state_share_of_cache_pct``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
or multiply once — bf16 weights (the routers and the shared gate float32, as
they are placed), of the routed experts only those a step actually hit (the
program's counter) plus the shared one, of the K/V cache the rows a query
may attend at their published width (2 K/V heads x 256 x K and V x 2 B =
2,048 B a token a full layer), a lane's float32 state read AND written once a
token a linear layer (it is overwritten by every token: there is no way
round either) and its conv window. A lower bound on what any implementation
moves: a share over 100 % means the count is wrong. The sizes are read under
THIS source's published keys (``linear_*``, ``num_experts`` = the experts
held, ``layer_types``).
"""

from __future__ import annotations

from benchmark.lib.costs_conv_gqa_moe import attention_bytes
from benchmark.lib.costs_hybrid_ssm import roofline_seconds  # noqa: F401  (the readers' and the tools')
from benchmark.lib.costs_mla_moe import expert_params

#: tokens of one block of the chunked (WY) form
BLOCK = 64


def conv_channels(*, key_heads: int, value_heads: int, key_dim: int,
                  value_dim: int) -> int:
    """Channels the mixer's causal conv runs over: q | k | v (8,192)."""
    return 2 * key_heads * key_dim + value_heads * value_dim


def state_bytes(*, value_heads: int, key_dim: int, value_dim: int,
                itemsize: int = 4) -> int:
    """One sequence's state in one linear layer: a [key dim, value dim]
    matrix a value head, float32: 32 x 128 x 128 x 4 B = 2,097,152."""
    return value_heads * key_dim * value_dim * itemsize


def window_bytes(*, conv_channels: int, taps: int, itemsize: int = 2) -> int:
    """The conv's last ``taps - 1`` inputs of one sequence in one linear
    layer, bfloat16: 3 x 8,192 x 2 B = 49,152."""
    return (taps - 1) * conv_channels * itemsize


def slot_bytes(*, linear_layers: int, value_heads: int, key_dim: int,
               value_dim: int, conv_channels: int, taps: int) -> int:
    """What one busy slot holds of the ``gdn`` pool, whatever its context:
    6 x (2,097,152 + 49,152) = 12,877,824 B."""
    return linear_layers * (
        state_bytes(value_heads=value_heads, key_dim=key_dim, value_dim=value_dim)
        + window_bytes(conv_channels=conv_channels, taps=taps))


def update_bytes(*, lanes: float, layers: int, value_heads: int, key_dim: int,
                 value_dim: int, conv_channels: int, taps: int) -> float:
    """Bytes the delta rule's update of ONE decode step has to move: per
    lane decoding and linear layer the state read and written (2 x 2 MiB)
    and the conv window."""
    return lanes * layers * (
        2 * state_bytes(value_heads=value_heads, key_dim=key_dim,
                        value_dim=value_dim)
        + window_bytes(conv_channels=conv_channels, taps=taps))


def chunk_scan_flops(*, tokens: int, layers: int, value_heads: int, key_dim: int,
                     value_dim: int, block: int = BLOCK) -> float:
    """Operations of the chunked delta rule over ``tokens`` positions of one
    sequence (padding dispatched is counted), a value head a token: the causal
    halves of ``K K^T`` and ``Q K^T`` (block x key dim / 2 each), the
    triangular solve for W and U (block / 2 x (key + value dim)), the causal
    half of ``(Q K^T) V'`` (block x value dim / 2), and three key dim x value
    dim products with the state (``W S``, ``Q S``, ``K^T V'``). The ten
    64-cubed products the kernel inverts ``I + A`` by are NOT needed (forward
    substitution does without) and not counted. A multiply-add counts two."""
    per_head = (block * key_dim + block * (key_dim + value_dim) / 2
                + block * value_dim / 2 + 3 * key_dim * value_dim)
    return 2.0 * layers * tokens * value_heads * per_head


def chunk_scan_bytes(*, tokens: int, layers: int, value_heads: int, key_dim: int,
                     value_dim: int, key_heads: int = 0) -> float:
    """Bytes the scan of one chunk has to move: the row's state in and out,
    q and k in (a KEY head each: ``key_heads``, the value heads' where not
    given), v in and the output back, the two gates, float32."""
    key_heads = key_heads or value_heads
    per_token = 4 * (2 * key_heads * key_dim + 2 * value_heads * value_dim
                     + 2 * value_heads)
    return layers * (2.0 * state_bytes(value_heads=value_heads, key_dim=key_dim,
                                       value_dim=value_dim)
                     + tokens * per_token)


def linear_params(*, hidden: int, key_heads: int, value_heads: int, key_dim: int,
                  value_dim: int, taps: int) -> int:
    """Matrix parameters of one Gated DeltaNet mixer: the q | k | v | z
    projection (hidden x 12,288), b | a (hidden x 64), the depthwise taps
    (8,192 x 4) and the output projection (4,096 x hidden): 33.72 M."""
    conv = conv_channels(key_heads=key_heads, value_heads=value_heads,
                         key_dim=key_dim, value_dim=value_dim)
    wide = value_heads * value_dim
    return hidden * (conv + wide) + hidden * 2 * value_heads + conv * taps \
        + wide * hidden


def attention_params(*, hidden: int, heads: int, kv_heads: int,
                     head_dim: int) -> int:
    """Matrix parameters of one gated attention mixer: queries AND their
    gate (hidden x 2 x heads head_dim), k and v (hidden x kv_heads head_dim
    each), o (heads head_dim x hidden): 27.26 M."""
    return hidden * head_dim * (3 * heads + 2 * kv_heads)


def expert_bytes(*, hidden: int, moe_width: int, experts_hit: float,
                 shared: int, layers: int, weight_bytes: int = 2) -> float:
    """Bytes the expert products of ONE step have to read: per layer the
    held experts hit (mean a layer) and the shared one, each three ``hidden
    x moe_width`` matrices (6.29 MB)."""
    return layers * (experts_hit + shared) * expert_params(
        hidden=hidden, width=moe_width) * weight_bytes


def decode_step_bytes(*, hidden: int, layers: int, linear_layers: int,
                      heads: int, kv_heads: int, head_dim: int, key_heads: int,
                      value_heads: int, key_dim: int, value_dim: int, taps: int,
                      moe_width: int, router_outputs: int, shared: int,
                      vocab: int, experts_hit: float, lanes: float,
                      context: float, weight_bytes: int = 2,
                      router_bytes: int = 4) -> float:
    """Bytes one chip has to move for one lockstep decode step: the output
    head; every mixer; per layer the router (float32, all its outputs), the
    shared gate and the ``experts_hit`` held experts the step touched plus
    the shared one; the K and V rows its ``lanes`` queries may attend on the
    full layers (``context``: their context lengths summed); each lane's
    state read and written and its conv window read and written on the
    linear layers. The embedding table is read one row a token: not
    counted."""
    full_layers = layers - linear_layers
    conv = conv_channels(key_heads=key_heads, value_heads=value_heads,
                         key_dim=key_dim, value_dim=value_dim)
    weights = (hidden * vocab * weight_bytes
               + (linear_layers * linear_params(
                   hidden=hidden, key_heads=key_heads, value_heads=value_heads,
                   key_dim=key_dim, value_dim=value_dim, taps=taps)
                  + full_layers * attention_params(
                      hidden=hidden, heads=heads, kv_heads=kv_heads,
                      head_dim=head_dim)) * weight_bytes
               + layers * hidden * (router_outputs + shared) * router_bytes
               + expert_bytes(hidden=hidden, moe_width=moe_width,
                              experts_hit=experts_hit, shared=shared,
                              layers=layers, weight_bytes=weight_bytes))
    cache = (full_layers * kv_heads * 2 * head_dim * 2 * context
             + lanes * linear_layers * 2 * (
                 state_bytes(value_heads=value_heads, key_dim=key_dim,
                             value_dim=value_dim)
                 + window_bytes(conv_channels=conv, taps=taps)))
    return weights + cache


# -- what the readers share -----------------------------------------------------


def sizes_of(view):
    """The keyword sizes of the counts above from the cell's published keys
    as run; None where the file is not of this layout."""
    s = view.sizes
    if "linear_num_value_heads" not in s or "layer_types" not in s:
        return None
    kinds = list(s["layer_types"])[:int(s["num_hidden_layers"])]
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                linear_layers=kinds.count("linear_attention"),
                heads=s["num_attention_heads"], kv_heads=s["num_key_value_heads"],
                head_dim=s["head_dim"], key_heads=s["linear_num_key_heads"],
                value_heads=s["linear_num_value_heads"],
                key_dim=s["linear_key_head_dim"],
                value_dim=s["linear_value_head_dim"],
                taps=s["linear_conv_kernel_dim"],
                moe_width=s["moe_intermediate_size"],
                router_outputs=s["router_outputs"],
                shared=s["n_shared_experts"], vocab=s["vocab_size"])


def mixer_of(s: dict) -> dict:
    """The delta rule's sizes out of ``sizes_of``'s."""
    return dict(value_heads=s["value_heads"], key_dim=s["key_dim"],
                value_dim=s["value_dim"])


def full_attention_share(view):
    """Share (%) of the chip's HBM bandwidth the paged attention kernel
    reaches on the full layers of a decode step: their needed bytes (the
    lanes' live keys x 2,048 B a layer, queries in and outputs back) over the
    peak and over the kernel's device time in a ``_decode`` execution. None
    where the trace has no such op or the file is not of this layout."""
    from benchmark.lib.costs_mla_moe import decode_context, kernel_ms_per_decode

    ms = kernel_ms_per_decode(view, r"paged_flash_attention")
    ctx, s = decode_context(view), sizes_of(view)
    if ms is None or ctx is None or s is None:
        return None
    lanes, context = ctx
    nbytes = attention_bytes(kv_heads=s["kv_heads"], head_dim=s["head_dim"],
                             heads=s["heads"], keys=context, queries=lanes,
                             layers=s["layers"] - s["linear_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
