"""mla_attn_ms_per_step — device time of the latent paged attention in a decode step.

Seconds of the ``mla_paged_attention`` kernel (``ops/ragged_attention.py``)
that ran inside executions of the ``_decode`` program on device 0 in the
profiler's trace, over the number of those executions: all layers of a step.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"mla_paged_attention")
