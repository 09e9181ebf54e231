"""gdn_full_attn_ms_per_step — device time of the full layers' paged attention in a decode step (Qwen3-Next's keys).

Seconds of the ``paged_flash_attention`` kernel — the shared per-head walk at
a head of 256, called a K/V head at a time over pools that hold a head a
layer (``GqaSpec.split_heads``) — inside executions of the ``_decode``
program on device 0 in the profiler's trace, over the number of those
executions: both full layers, both K/V heads together.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    if "linear_num_value_heads" not in view.sizes:
        return None
    return kernel_ms_per_decode(view, r"paged_flash_attention")
