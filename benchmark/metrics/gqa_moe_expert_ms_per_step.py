"""gqa_moe_expert_ms_per_step — device time of the expert products in a decode step (K-EXAONE's keys).

Seconds of the ``moe_expert_swiglu`` kernel (held routed experts and the
shared expert in one product, ``ops/moe_experts.py``) that ran inside
executions of the ``_decode`` program on device 0 in the profiler's trace,
over the number of those executions: the four expert layers of a step. The
same reading as ``moe_expert_ms_per_step``, listed under a name of its own
beside its two kin, which read this file's keys.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"moe_expert_swiglu")
