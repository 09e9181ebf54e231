"""Needed bytes and operations of a decoder whose layers are Kimi Delta
Attention mixers (a float32 matrix state a sequence, decayed a key CHANNEL)
beside position-free latent (MLA) layers, a leading dense SwiGLU and
sigmoid-routed experts of which a share is held plus one shared expert
(Kimi-Linear-48B-A3B): the counts behind ``kda_update_hbm_pct``,
``kda_scan_roofline_pct``, ``kda_mla_attn_hbm_pct`` and
``kda_moe_decode_hbm_pct`` (and, by hand or a tool, the expert kernel's share
and the state pool's share of the cache: ``expert_bytes``, ``slot_bytes``).

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
or multiply once, whatever implements it — bf16 weights (the routers float32,
as they are placed), of the routed experts only those a step actually hit
(the program's counter) plus the shared one, of the latent cache the rows a
query may attend at their PUBLISHED width (512 + 64 values x 2 B = 1,152 B a
token a latent layer: the 64 lanes of zeros the pools hold behind the key
are the implementation's), a lane's float32 state read AND written once a
token a linear layer and its conv window. A lower bound on what any
implementation moves: a share over 100 % means the count is wrong. The sizes
are read under THIS source's published keys (``linear_attn_config``,
``num_experts`` = the experts held, ``layer_types``).
"""

from __future__ import annotations

from benchmark.lib.costs_hybrid_ssm import roofline_seconds  # noqa: F401  (the readers' and the tools')
from benchmark.lib.costs_mla_moe import (attention_params, expert_params,
                                         latent_attention_bytes)

#: tokens of one block of the chunked (WY) form
BLOCK = 64


def conv_channels(*, heads: int, head_dim: int) -> int:
    """Channels the three causal convs run over: q | k | v (12,288)."""
    return 3 * heads * head_dim


def state_bytes(*, heads: int, head_dim: int, itemsize: int = 4) -> int:
    """One sequence's state in one linear layer: a [head dim, head dim]
    matrix a head, float32: 32 x 128 x 128 x 4 B = 2,097,152."""
    return heads * head_dim * head_dim * itemsize


def window_bytes(*, heads: int, head_dim: int, taps: int, itemsize: int = 2) -> int:
    """The convs' last ``taps - 1`` inputs of one sequence in one linear
    layer, bfloat16: 3 x 12,288 x 2 B = 73,728."""
    return (taps - 1) * conv_channels(heads=heads, head_dim=head_dim) * itemsize


def slot_bytes(*, linear_layers: int, heads: int, head_dim: int, taps: int) -> int:
    """What one busy slot holds of the ``kda`` pool, whatever its context:
    6 x (2,097,152 + 73,728) = 13,025,280 B."""
    return linear_layers * (state_bytes(heads=heads, head_dim=head_dim)
                            + window_bytes(heads=heads, head_dim=head_dim, taps=taps))


def latent_row_bytes(*, latent_layers: int, kv_lora: int, rope: int,
                     held: bool = False, itemsize: int = 2) -> int:
    """What one cached token costs over the latent layers: at published
    widths 2 x (512 + 64) x 2 B = 2,304 B; AS HELD (the shared key in a
    whole 128-lane row, zeros behind it) 2 x (512 + 128) x 2 B = 2,560 B."""
    key = -(-rope // 128) * 128 if held else rope
    return latent_layers * (kv_lora + key) * itemsize


def update_bytes(*, lanes: float, layers: int, heads: int, head_dim: int,
                 taps: int) -> float:
    """Bytes the delta rule's update of ONE decode step has to move: per
    lane decoding and linear layer the state read and written (2 x 2 MiB)
    and the conv window."""
    return lanes * layers * (2 * state_bytes(heads=heads, head_dim=head_dim)
                             + window_bytes(heads=heads, head_dim=head_dim, taps=taps))


def chunk_scan_flops(*, tokens: int, layers: int, heads: int, head_dim: int,
                     block: int = BLOCK) -> float:
    """Operations of the chunked delta rule over ``tokens`` positions of one
    sequence (padding dispatched is counted), a head a token — the count of
    ``costs_gdn_gqa_moe.chunk_scan_flops`` at key dim = value dim = head dim:
    the causal halves of ``K K^T`` and ``Q K^T`` (block x head dim / 2 each),
    the triangular solve for W and U (block / 2 x 2 head dim), the causal
    half of ``(Q K^T) V'`` (block x head dim / 2), and three head dim x head
    dim products with the state. A decay a key CHANNEL adds multiplies by
    decays (a few a value: not counted) and, in THIS kernel, one product a
    halving level of the block where a decay a head needs one in all; the
    levels and the inverse's ten products are the implementation's and NOT
    counted (the same work whatever implements it). A multiply-add counts
    two."""
    per_head = (block * head_dim + block * head_dim + block * head_dim / 2
                + 3 * head_dim * head_dim)
    return 2.0 * layers * tokens * heads * per_head


def chunk_scan_bytes(*, tokens: int, layers: int, heads: int, head_dim: int) -> float:
    """Bytes the scan of one chunk has to move: the row's state in and out;
    q, k, v and the log-decay (a key channel) in, the output back, and beta,
    float32."""
    per_token = 4 * (5 * heads * head_dim + heads)
    return layers * (2.0 * state_bytes(heads=heads, head_dim=head_dim)
                     + tokens * per_token)


def linear_params(*, hidden: int, heads: int, head_dim: int, taps: int) -> int:
    """Matrix parameters of one KDA mixer: q | k | v (hidden x 12,288), the
    depthwise taps (12,288 x 4), the decay's and the gate's low-rank pairs (2
    x (hidden x 128 + 128 x 4,096)), beta (hidden x 32) and the output
    projection (4,096 x hidden): 39.51 M."""
    wide = heads * head_dim
    return (hidden * 3 * wide + 3 * wide * taps
            + 2 * (hidden * head_dim + head_dim * wide) + hidden * heads
            + wide * hidden)


def expert_bytes(*, hidden: int, moe_width: int, experts_hit: float,
                 shared: int, expert_layers: int, weight_bytes: int = 2) -> float:
    """Bytes the expert products of ONE step have to read: per expert layer
    the held experts hit (mean a layer) and the shared one, each three
    ``hidden x moe_width`` matrices (14.16 MB)."""
    return expert_layers * (experts_hit + shared) * expert_params(
        hidden=hidden, width=moe_width) * weight_bytes


def weight_bytes_held(*, hidden: int, layers: int, linear_layers: int,
                      dense_layers: int, heads: int, nope: int, rope: int,
                      v: int, kv_lora: int, kda_heads: int, kda_dim: int,
                      taps: int, dense_width: int, moe_width: int, held: int,
                      router_outputs: int, shared: int, vocab: int,
                      weight_bytes: int = 2, router_bytes: int = 4) -> float:
    """Bytes of every matrix this chip holds: table and head, the mixers,
    the dense MLPs, per expert layer the router (float32), the held experts
    and the shared one: 4.19 GB at the cell's sizes."""
    expert_layers = layers - dense_layers
    mixers = (linear_layers * linear_params(hidden=hidden, heads=kda_heads,
                                            head_dim=kda_dim, taps=taps)
              + (layers - linear_layers) * attention_params(
                  hidden=hidden, heads=heads, nope=nope, rope=rope, v=v,
                  kv_lora=kv_lora))
    mlps = (dense_layers * expert_params(hidden=hidden, width=dense_width)
            + expert_layers * (held + shared) * expert_params(
                hidden=hidden, width=moe_width))
    return ((2 * hidden * vocab + mixers + mlps) * weight_bytes
            + expert_layers * hidden * router_outputs * router_bytes)


def decode_step_bytes(*, hidden: int, layers: int, linear_layers: int,
                      dense_layers: int, heads: int, nope: int, rope: int,
                      v: int, kv_lora: int, kda_heads: int, kda_dim: int,
                      taps: int, dense_width: int, moe_width: int,
                      router_outputs: int, shared: int, vocab: int,
                      experts_hit: float, lanes: float, context: float,
                      weight_bytes: int = 2, router_bytes: int = 4) -> float:
    """Bytes one chip has to move for one lockstep decode step: the output
    head; every mixer; the dense MLP; per expert layer the router (float32,
    all its outputs) and the ``experts_hit`` held experts the step touched
    plus the shared one; the latent rows and shared keys its ``lanes``
    queries may attend on the latent layers (``context``: their context
    lengths summed), with the queries in and the outputs back; each lane's
    state read and written and its conv window read and written on the
    linear layers. The embedding table is read one row a token: not
    counted."""
    latent_layers = layers - linear_layers
    expert_layers = layers - dense_layers
    weights = (hidden * vocab * weight_bytes
               + (linear_layers * linear_params(hidden=hidden, heads=kda_heads,
                                                head_dim=kda_dim, taps=taps)
                  + latent_layers * attention_params(
                      hidden=hidden, heads=heads, nope=nope, rope=rope, v=v,
                      kv_lora=kv_lora)
                  + dense_layers * expert_params(hidden=hidden, width=dense_width)
                  ) * weight_bytes
               + expert_layers * hidden * router_outputs * router_bytes
               + expert_bytes(hidden=hidden, moe_width=moe_width,
                              experts_hit=experts_hit, shared=shared,
                              expert_layers=expert_layers,
                              weight_bytes=weight_bytes))
    cache = (latent_attention_bytes(heads=heads, kv_lora=kv_lora, rope=rope,
                                    kv_tokens=context, queries=lanes,
                                    layers=latent_layers)
             + lanes * linear_layers * 2 * (
                 state_bytes(heads=kda_heads, head_dim=kda_dim)
                 + window_bytes(heads=kda_heads, head_dim=kda_dim, taps=taps)))
    return weights + cache


# -- what the readers share -----------------------------------------------------


def sizes_of(view):
    """The keyword sizes of ``decode_step_bytes`` from the cell's published
    keys as run; None where the file is not of this layout."""
    s = view.sizes
    if "linear_attn_config" not in s or "layer_types" not in s:
        return None
    kda = s["linear_attn_config"]
    kinds = list(s["layer_types"])[:int(s["num_hidden_layers"])]
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                linear_layers=kinds.count("linear_attention"),
                dense_layers=s["first_k_dense_replace"],
                heads=s["num_attention_heads"], nope=s["qk_nope_head_dim"],
                rope=s["qk_rope_head_dim"], v=s["v_head_dim"],
                kv_lora=s["kv_lora_rank"], kda_heads=kda["num_heads"],
                kda_dim=kda["head_dim"], taps=kda["short_conv_kernel_size"],
                dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"],
                router_outputs=s["router_outputs"],
                shared=s["num_shared_experts"], vocab=s["vocab_size"])


def mixer_of(s: dict) -> dict:
    """The delta rule's sizes out of ``sizes_of``'s."""
    return dict(heads=s["kda_heads"], head_dim=s["kda_dim"])


def decode_routing(view):
    """Mean distinct held experts hit an expert layer over the window's
    decode steps, from the program's counters (``costs_mla_moe.
    decode_routing`` reads ``n_shared_experts``-style keys this file does not
    have); None where the program has none."""
    hit_sum, steps = view.hist("arkflow_gen_moe_experts_hit", kind="decode")
    return None if steps <= 0 else hit_sum / steps


def latent_attention_share(view):
    """Share (%) of the chip's HBM bandwidth the latent attention kernel
    reaches on the latent layers of a decode step: their needed bytes (the
    lanes' live rows x 1,152 B a layer at published widths, the 32 heads'
    absorbed queries in and latent outputs back) over the peak and over the
    kernel's device time in a ``_decode`` execution. None where the trace
    has no such op or the file is not of this layout."""
    from benchmark.lib.costs_mla_moe import decode_context, kernel_ms_per_decode

    ms = kernel_ms_per_decode(view, r"mla_paged_attention")
    ctx, s = decode_context(view), sizes_of(view)
    if ms is None or ctx is None or s is None:
        return None
    lanes, context = ctx
    nbytes = latent_attention_bytes(
        heads=s["heads"], kv_lora=s["kv_lora"], rope=s["rope"],
        kv_tokens=context, queries=lanes,
        layers=s["layers"] - s["linear_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
