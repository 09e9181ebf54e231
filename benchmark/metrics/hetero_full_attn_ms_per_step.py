"""hetero_full_attn_ms_per_step — device time of the full layers' attention in a decode step.

Seconds of the ``paged_flash_attention*`` kernel (the per-head paged kernel
over the kept pages of 4 K/V heads, 16 query heads a K/V head, keys in two
parts of 128 lanes and values of 128) that ran inside executions of the
``_decode`` program on device 0 in the profiler's trace, over the number of
those executions: the two full layers of a step.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"paged_flash_attention")
