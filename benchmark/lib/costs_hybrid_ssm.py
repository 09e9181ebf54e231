"""Needed bytes and operations of a hybrid decoder — GQA attention and a
Mamba-2 mixer side by side in every layer, a dense SwiGLU after them —
computed from shapes: the counts behind ``ssm_update_hbm_pct``,
``ssm_scan_roofline_pct``, ``hybrid_decode_hbm_pct`` and
``ssm_state_share_of_cache_pct``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
or multiply once, whatever implements it — bf16 weights read once a step, a
lane's float32 state read and written once a token (it is overwritten by
every token: there is no way round either), the K/V of the tokens attended
over. A lower bound on what any implementation moves: a share over 100 %
means the count is wrong. ``lib/costs.decode_step_bytes`` counts a plain
dense layer (``head_dim = hidden / heads``, no mixer) and is wrong here.
"""

from __future__ import annotations


def conv_channels(*, d_ssm: int, groups: int, d_state: int) -> int:
    """Channels the mixer's causal conv runs over: x | B | C."""
    return d_ssm + 2 * groups * d_state


def state_bytes(*, d_ssm: int, d_state: int, state_itemsize: int = 4) -> int:
    """One sequence's recurrent state in one layer: heads x d_head x d_state
    = d_ssm x d_state values, float32."""
    return d_ssm * d_state * state_itemsize


def conv_window_bytes(*, d_ssm: int, groups: int, d_state: int, d_conv: int,
                      itemsize: int = 2) -> int:
    """The conv's last ``d_conv - 1`` inputs of one sequence in one layer."""
    return (d_conv - 1) * conv_channels(
        d_ssm=d_ssm, groups=groups, d_state=d_state) * itemsize


def slot_bytes(*, layers: int, **mixer) -> int:
    """What one busy slot holds in the state pool over all layers."""
    return layers * (state_bytes(d_ssm=mixer["d_ssm"], d_state=mixer["d_state"])
                     + conv_window_bytes(**mixer))


def ssm_update_bytes(*, lanes: float, layers: int, **mixer) -> float:
    """Bytes the recurrent update of ONE decode step has to move: per lane
    decoding and layer the state read and written, and the conv window."""
    return lanes * layers * (
        2 * state_bytes(d_ssm=mixer["d_ssm"], d_state=mixer["d_state"])
        + conv_window_bytes(**mixer))


def layer_params(*, hidden: int, heads: int, kv_heads: int, head_dim: int,
                 ffn: int, d_ssm: int, groups: int, d_state: int,
                 mixer_heads: int, d_conv: int) -> int:
    """Matrix parameters of one layer: attention (q, k, v, o), the mixer
    (in_proj to z | x | B | C | dt, the depthwise conv, out_proj) and the
    SwiGLU. Norm scales and the per-head vectors are not counted."""
    attn = hidden * head_dim * (2 * heads + 2 * kv_heads)
    conv = conv_channels(d_ssm=d_ssm, groups=groups, d_state=d_state)
    mixer = hidden * (d_ssm + conv + mixer_heads) + conv * d_conv + d_ssm * hidden
    return attn + mixer + 3 * hidden * ffn


def decode_step_bytes(*, lanes: float, kv_tokens: float, layers: int,
                      vocab: int, hidden: int, heads: int, kv_heads: int,
                      head_dim: int, ffn: int, d_ssm: int, groups: int,
                      d_state: int, mixer_heads: int, d_conv: int,
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """Bytes one chip has to move for one lockstep decode step: every layer's
    weights and the output head once, the state of the ``lanes`` decoding
    read and written, and the K and V of the ``kv_tokens`` attended over
    (context lengths summed over those lanes). The embedding table is read
    one row a token: not counted."""
    mixer = dict(d_ssm=d_ssm, groups=groups, d_state=d_state, d_conv=d_conv)
    weights = (layers * layer_params(
        hidden=hidden, heads=heads, kv_heads=kv_heads, head_dim=head_dim,
        ffn=ffn, mixer_heads=mixer_heads, **mixer) + hidden * vocab) * weight_bytes
    kv = kv_tokens * layers * 2 * kv_heads * head_dim * kv_bytes
    return weights + ssm_update_bytes(lanes=lanes, layers=layers, **mixer) + kv


def chunk_scan_flops(*, tokens: int, block: int, layers: int, d_ssm: int,
                     groups: int, d_state: int) -> float:
    """Operations of the chunked scan over ``tokens`` positions of one
    sequence (padding dispatched is counted), ``block`` tokens a block: C B^T
    within a block (a group), the masked product with x, the carried state's
    part of the output and the state's update (each d_ssm x d_state a
    token). A multiply-add counts two."""
    q = min(block, tokens)
    macs = tokens * (q * d_state * groups + q * d_ssm + 2 * d_ssm * d_state)
    return 2.0 * layers * macs


def chunk_scan_bytes(*, tokens: int, layers: int, d_ssm: int, groups: int,
                     d_state: int, mixer_heads: int) -> float:
    """Bytes the scan of one chunk has to move: the row's state in and out,
    x, the step, B and C in and the output back, float32."""
    per_token = 4 * (2 * d_ssm + mixer_heads + 2 * groups * d_state)
    return layers * (2.0 * state_bytes(d_ssm=d_ssm, d_state=d_state)
                     + tokens * per_token)


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(the least time the chip could take, which roof binds)."""
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), ("flops" if by_flops > by_bytes else "bytes")


# -- what the readers share -----------------------------------------------------


def mixer_of(view) -> dict:
    """The mixer's sizes from a cell's published keys as run; None where the
    configuration has no mixer."""
    s = view.sizes
    if not s.get("mamba_d_ssm"):
        return None
    return dict(d_ssm=s["mamba_d_ssm"], groups=s["mamba_n_groups"],
                d_state=s["mamba_d_state"], d_conv=s["mamba_d_conv"])


def layer_sizes_of(view) -> dict:
    s = view.sizes
    return dict(hidden=s["hidden_size"], heads=s["num_attention_heads"],
                kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                ffn=s["intermediate_size"], mixer_heads=s["mamba_n_heads"],
                vocab=s["vocab_size"], layers=s["num_hidden_layers"])


def kernel_ms_per_chunk(view, op_re: str):
    """Device ms a ``_chunk`` execution spends in the ops matching ``op_re``
    (device 0 of the trace); None where there is no such op. The ``_decode``
    programs' reader is ``costs_mla_moe.kernel_ms_per_decode``: the readers
    of decode steps call that one."""
    from benchmark.lib.xtrace import ops_inside

    t = view.trace
    if not t or "first_device" not in t:
        return None
    dev = t["first_device"]
    total, steps = ops_inside(dev["ops"], dev["modules"], r"jit__chunk", op_re)
    return None if not steps or total <= 0 else total / steps * 1e3


def lanes_decoding(view):
    """Mean lanes a decode step advanced, from the program's counter over
    its decode steps; None where the program counts none."""
    tokens = view.counter("arkflow_gen_ssm_tokens_total", kind="decode")
    steps = view.counter("arkflow_gen_decode_steps_total")
    return None if tokens <= 0 or steps <= 0 else tokens / steps
