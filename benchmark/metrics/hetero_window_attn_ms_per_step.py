"""hetero_window_attn_ms_per_step — device time of the sliding layers' window attention in a decode step.

Seconds of the ``paged_window_attention*`` kernel (the per-head paged kernel
with its lower bound and its sink, over the ring of window pages of 8 K/V
heads, keys in two parts of 128 lanes and values of 128, ``ops/
ragged_attention.py::paged_flash_attention(window=, sink=)``) that ran inside
executions of the ``_decode`` program on device 0 in the profiler's trace,
over the number of those executions: the five sliding layers of a step.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"paged_window_attention")
