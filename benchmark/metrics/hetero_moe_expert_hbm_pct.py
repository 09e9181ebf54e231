"""hetero_moe_expert_hbm_pct — share of the chip's HBM bandwidth the expert kernel reaches.

``moe_expert_hbm_pct`` for a file whose source has no shared expert
(``n_shared_experts`` null, which that reader adds to a number). Needed
bytes of the expert products of one decode step
(``lib/costs_hetero_gqa_moe.expert_product_bytes``: per expert layer the held
experts the step hit — the program's counter —, three bf16 matrices of 4,096
x 2,048 each) over 819 GB/s (``peaks.json``) and over the
``moe_expert_swiglu`` kernel's device time in a ``_decode`` execution
(``moe_expert_ms_per_step``).
"""

from benchmark.lib.costs_hetero_gqa_moe import expert_product_bytes, sizes_of
from benchmark.lib.costs_mla_moe import decode_routing, kernel_ms_per_decode


def read(view):
    ms = kernel_ms_per_decode(view, r"moe_expert_swiglu")
    routing, s = decode_routing(view), sizes_of(view)
    if ms is None or routing is None or s is None:
        return None
    nbytes = expert_product_bytes(
        hidden=s["hidden"], moe_width=s["moe_width"], experts_hit=routing[0],
        expert_layers=s["layers"] - s["dense_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
