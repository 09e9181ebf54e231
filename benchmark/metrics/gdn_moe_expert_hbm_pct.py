"""gdn_moe_expert_hbm_pct — share of the chip's HBM bandwidth the expert kernel reaches (Qwen3-Next's keys).

Needed bytes of the expert products of one decode step (``lib/
costs_gdn_gqa_moe.expert_bytes``: per layer the held experts the step HIT —
the program's counter, mean a layer — plus the shared one, three bf16
matrices of 2,048 x 512 each, 6.29 MB an expert, eight layers) over 819 GB/s
(``peaks.json``) and over the ``moe_expert_swiglu`` kernel's device time in a
``_decode`` execution (``moe_expert_ms_per_step``). 512 experts of 3.1 M
parameters: the "many small experts" end of the expert kernel's range.
"""

from benchmark.lib.costs_gdn_gqa_moe import expert_bytes, sizes_of
from benchmark.lib.costs_mla_moe import decode_routing, kernel_ms_per_decode


def read(view):
    ms = kernel_ms_per_decode(view, r"moe_expert_swiglu")
    routing, s = decode_routing(view), sizes_of(view)
    if ms is None or routing is None or s is None:
        return None
    nbytes = expert_bytes(hidden=s["hidden"], moe_width=s["moe_width"],
                          experts_hit=routing[0], shared=s["shared"],
                          layers=s["layers"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
