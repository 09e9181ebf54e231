"""moe_expert_hbm_pct — share of the chip's HBM bandwidth the expert kernel reaches.

Needed bytes of the expert products of one decode step
(``lib/costs_mla_moe.expert_product_bytes``: per expert layer the routed
experts the step hit — the program's counter — and the shared experts, three
bf16 matrices each) over 819 GB/s (``peaks.json``) and over the
``moe_expert_swiglu`` kernel's device time in a ``_decode`` execution
(``moe_expert_ms_per_step``).
"""

from benchmark.lib.costs_mla_moe import (decode_routing, expert_product_bytes,
                                         kernel_ms_per_decode)


def read(view):
    ms = kernel_ms_per_decode(view, r"moe_expert_swiglu")
    routing = decode_routing(view)
    if ms is None or routing is None:
        return None
    s = view.sizes
    nbytes = expert_product_bytes(
        hidden=s["hidden_size"], moe_width=s["moe_intermediate_size"],
        shared=s["n_shared_experts"], experts_hit=routing[0],
        expert_layers=s["num_hidden_layers"] - s["first_k_dense_replace"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
