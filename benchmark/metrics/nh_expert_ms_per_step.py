"""nh_expert_ms_per_step — device ms a decode step spends in the two-matrix expert product.

Seconds of the ``moe_expert_relu2`` kernels (``ops/moe_experts.py`` up to 128
rows, ``ops/moe_grouped.py`` — ``moe_expert_relu2_grouped`` — above: the
cell's 192 lanes) inside executions of the ``_decode`` program on device 0 in
the profiler's trace, over those executions: the 5 expert layers of a step
together. (``moe_expert_ms_per_step`` reads ``moe_expert_swiglu``, another
kernel's name.)
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"moe_expert_relu2")
