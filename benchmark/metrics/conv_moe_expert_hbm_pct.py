"""conv_moe_expert_hbm_pct — share of the chip's HBM bandwidth the expert kernel reaches (LFM2's keys).

``moe_expert_hbm_pct`` for a file that names its experts ``num_experts``,
its leading dense layers ``num_dense_layers`` and has no shared expert (the
accepted readers read ``n_routed_experts`` / ``n_shared_experts`` or an
``experts_held`` share: none reads this source). Needed bytes of the expert
products of one decode step (``lib/costs_hetero_gqa_moe.
expert_product_bytes``: per expert layer the experts the step HIT — the
program's counter, mean a layer —, three bf16 matrices of 2,048 x 1,792
each, 22.02 MB an expert, ten expert layers) over 819 GB/s (``peaks.json``)
and over the ``moe_expert_swiglu`` kernel's device time in a ``_decode``
execution (``moe_expert_ms_per_step``). The step's first device cost: 73 %
of it (PERF.md section 5).
"""

from benchmark.lib.costs_conv_gqa_moe import sizes_of
from benchmark.lib.costs_hetero_gqa_moe import expert_product_bytes
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
