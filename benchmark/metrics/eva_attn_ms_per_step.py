"""eva_attn_ms_per_step — device time of attention in a decode step of a compacting window cache.

Seconds of the ``paged_flash_attention`` kernel (the per-head paged kernel,
here over a table whose columns are a lane's summary pages, then its open
window's: one softmax over both) that ran inside executions of the
``_decode`` program on device 0 in the profiler's trace, over the number of
those executions: all layers of a step.
"""

from benchmark.lib.costs_eva import sizes_of
from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    if sizes_of(view) is None:
        return None
    return kernel_ms_per_decode(view, r"paged_flash_attention")
