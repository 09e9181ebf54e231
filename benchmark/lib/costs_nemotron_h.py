"""Needed bytes and operations of a decoder of one-mixer blocks — a Mamba-2
layer, a routed-expert layer of two-matrix relu-squared experts, or a
position-free GQA layer (Nemotron-H) — computed from shapes: the counts
behind the ``nh_*`` readers.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
or multiply once, whatever implements it — bf16 weights read once a step, of
the experts the ones a step HIT (at the PUBLISHED width: the zero columns the
program's stack holds behind it are the program's own), a lane's float32
state read and written once a token on the MAMBA layers only, the K/V of the
tokens attended on the ATTENTION layers only. A lower bound: a share over
100 % means the count is wrong. ``lib/costs_hybrid_ssm`` counts a state in
every layer (Falcon-H1 has one) and would read 13 / 6 too high here; its
per-layer functions are this file's too, called with the mamba layers.
"""

from __future__ import annotations

from benchmark.lib.costs_hybrid_ssm import (chunk_scan_bytes, chunk_scan_flops,
                                            conv_window_bytes, roofline_seconds,
                                            state_bytes)

__all__ = ["roofline_seconds"]


def kinds(pattern: str, layers: int) -> dict:
    """How many of the first ``layers`` blocks are of each kind."""
    held = pattern[:layers]
    return {"mamba": held.count("M"), "moe": held.count("E"),
            "attention": held.count("*")}


def slot_bytes(*, mamba_layers: int, **mixer) -> int:
    """What one busy slot holds in the state pool over the mamba layers."""
    return mamba_layers * (
        state_bytes(d_ssm=mixer["d_ssm"], d_state=mixer["d_state"])
        + conv_window_bytes(**mixer))


def update_bytes(*, lanes: float, mamba_layers: int, **mixer) -> float:
    """Bytes the recurrent update of ONE decode step has to move: per lane
    decoding and mamba layer the state read and written, and the window."""
    return lanes * mamba_layers * (
        2 * state_bytes(d_ssm=mixer["d_ssm"], d_state=mixer["d_state"])
        + conv_window_bytes(**mixer))


def scan_counts(*, tokens: int, block: int, mamba_layers: int, d_ssm: int,
                groups: int, d_state: int, mixer_heads: int) -> tuple:
    """(operations, bytes) of the chunked scan of one chunk over the mamba
    layers (``costs_hybrid_ssm``'s counts a layer)."""
    shape = dict(tokens=tokens, layers=mamba_layers, d_ssm=d_ssm, groups=groups,
                 d_state=d_state)
    return (chunk_scan_flops(block=block, **shape),
            chunk_scan_bytes(mixer_heads=mixer_heads, **shape))


def expert_params(*, hidden: int, width: int) -> int:
    """One two-matrix expert: up and down, no gate."""
    return 2 * hidden * width


def expert_bytes(*, hidden: int, moe_width: int, shared_width: int,
                 experts_hit: float, moe_layers: int, weight_bytes: int = 2) -> float:
    """Bytes the expert products of ONE step have to read: per expert layer
    the held experts the step hit and the shared expert (its own width)."""
    per_layer = (experts_hit * expert_params(hidden=hidden, width=moe_width)
                 + expert_params(hidden=hidden, width=shared_width))
    return moe_layers * per_layer * weight_bytes


def mamba_params(*, hidden: int, d_ssm: int, groups: int, d_state: int,
                 mixer_heads: int, d_conv: int) -> int:
    """Matrix parameters of a Mamba-2 block: in_proj to z | x | B | C | dt,
    the depthwise conv (with its bias), out_proj."""
    conv = d_ssm + 2 * groups * d_state
    return hidden * (d_ssm + conv + mixer_heads) + conv * (d_conv + 1) + d_ssm * hidden


def attention_params(*, hidden: int, heads: int, kv_heads: int, head_dim: int) -> int:
    return hidden * head_dim * (2 * heads + 2 * kv_heads)


def attention_bytes(*, kv_tokens: float, attention_layers: int, kv_heads: int,
                    head_dim: int, kv_bytes: int = 2) -> float:
    """K and V of the ``kv_tokens`` attended over (context lengths summed
    over the lanes), on the attention layers."""
    return kv_tokens * attention_layers * 2 * kv_heads * head_dim * kv_bytes


def decode_step_bytes(*, lanes: float, kv_tokens: float, experts_hit: float,
                      hidden: int, vocab: int, heads: int, kv_heads: int,
                      head_dim: int, mamba_layers: int, moe_layers: int,
                      attention_layers: int, router_outputs: int,
                      moe_width: int, shared_width: int, d_ssm: int,
                      groups: int, d_state: int, mixer_heads: int, d_conv: int,
                      weight_bytes: int = 2) -> float:
    """Bytes one chip has to move for one lockstep decode step: the mamba and
    attention blocks' weights and the output head once (bf16), the routers
    (float32), the experts hit and the shared one, the state of the lanes
    decoding read and written, the K/V attended over."""
    mixer = dict(d_ssm=d_ssm, groups=groups, d_state=d_state, d_conv=d_conv)
    weights = (mamba_layers * mamba_params(hidden=hidden, mixer_heads=mixer_heads,
                                           **mixer)
               + attention_layers * attention_params(
                   hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim)
               + hidden * vocab) * weight_bytes
    routers = moe_layers * hidden * router_outputs * 4
    return (weights + routers
            + expert_bytes(hidden=hidden, moe_width=moe_width,
                           shared_width=shared_width, experts_hit=experts_hit,
                           moe_layers=moe_layers, weight_bytes=weight_bytes)
            + update_bytes(lanes=lanes, mamba_layers=mamba_layers, **mixer)
            + attention_bytes(kv_tokens=kv_tokens, attention_layers=attention_layers,
                              kv_heads=kv_heads, head_dim=head_dim))


# -- what the readers share -----------------------------------------------------


def sizes_of(view):
    """The sizes the counts read, from a cell's published keys as run; None
    where the configuration is not of one-mixer blocks."""
    s = view.sizes
    pattern = s.get("hybrid_override_pattern")
    if not pattern or not s.get("mamba_num_heads"):
        return None
    n = kinds(pattern, s["num_hidden_layers"])
    return dict(
        hidden=s["hidden_size"], vocab=s["vocab_size"],
        heads=s["num_attention_heads"], kv_heads=s["num_key_value_heads"],
        head_dim=s["head_dim"], mamba_layers=n["mamba"], moe_layers=n["moe"],
        attention_layers=n["attention"],
        router_outputs=s.get("router_outputs", s["n_routed_experts"]),
        moe_width=s["moe_intermediate_size"],
        shared_width=s["n_shared_experts"] * s["moe_shared_expert_intermediate_size"],
        d_ssm=s["mamba_num_heads"] * s["mamba_head_dim"], groups=s["n_groups"],
        d_state=s["ssm_state_size"], mixer_heads=s["mamba_num_heads"],
        d_conv=s["conv_kernel"])


def mixer_of(s: dict) -> dict:
    return {k: s[k] for k in ("d_ssm", "groups", "d_state", "d_conv")}


def held_experts(view) -> int:
    s = view.sizes
    return (s.get("experts_held") or (0, s["n_routed_experts"]))[1]


def step_routing(view, kind: str):
    """(mean distinct held experts hit a moe layer, steps) over the window's
    steps of ``kind`` (``decode`` / ``chunk``), from the program's histogram
    (already a mean over the expert layers: ``tpu/serving.py::_note_moe``)."""
    hit_sum, steps = view.hist("arkflow_gen_moe_experts_hit", kind=kind)
    return None if steps <= 0 else (hit_sum / steps, steps)
