"""gqa_window_attn_ms_per_step — device time of the sliding layers' window attention in a decode step.

Seconds of the ``paged_window_attention*`` kernel (the per-head paged kernel
with its lower bound, over the ring of window pages, ``ops/
ragged_attention.py::paged_flash_attention(window=)``) that ran inside
executions of the ``_decode`` program on device 0 in the profiler's trace,
over the number of those executions: the four sliding layers of a step.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"paged_window_attention")
