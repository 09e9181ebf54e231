"""nh_expert_hbm_pct — share of the chip's HBM bandwidth the two-matrix expert product reaches.

Needed bytes of the expert products of one decode step (``lib/
costs_nemotron_h.expert_bytes``: per expert layer the held experts the step
HIT — the program's histogram, mean a layer — at the published 2 x 2,688 x
1,856 bf16 each, plus the shared expert at 3,712; 5 layers) over 819 GB/s
(``peaks.json``) and over the ``moe_expert_relu2`` kernels' device time in a
``_decode`` execution (``nh_expert_ms_per_step``).
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode
from benchmark.lib.costs_nemotron_h import expert_bytes, sizes_of, step_routing


def read(view):
    ms, s = kernel_ms_per_decode(view, r"moe_expert_relu2"), sizes_of(view)
    routing = step_routing(view, "decode")
    if ms is None or s is None or routing is None or not view.peaks:
        return None
    nbytes = expert_bytes(hidden=s["hidden"], moe_width=s["moe_width"],
                          shared_width=s["shared_width"], experts_hit=routing[0],
                          moe_layers=s["moe_layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
