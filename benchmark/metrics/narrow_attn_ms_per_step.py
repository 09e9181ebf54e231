"""narrow_attn_ms_per_step — device time of the narrow-head attention in a decode step.

Seconds of the ``paged_flash_attention*`` kernel (``ops/ragged_attention``:
for a head of 64 lanes the walk over ROW-MAJOR pools, ``_narrow_kernel``)
that ran inside executions of the ``_decode`` program on device 0 in the
profiler's trace, over the number of those executions: the three attention
layers of a step summed, 128 lanes walking their own contexts.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"paged_flash_attention")
