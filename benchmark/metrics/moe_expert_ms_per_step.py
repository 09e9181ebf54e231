"""moe_expert_ms_per_step — device time of the expert products in a decode step.

Seconds of the ``moe_expert_swiglu`` kernel (routed and shared experts in
one product, ``ops/moe_experts.py``) that ran inside executions of the
``_decode`` program on device 0 in the profiler's trace, over the number of
those executions: all expert layers of a step together.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"moe_expert_swiglu")
