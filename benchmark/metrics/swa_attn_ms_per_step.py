"""swa_attn_ms_per_step — device time of the sliding layers' window attention in a decode step.

Seconds of the ``swa_latent_attention*`` kernel (the latent attention kernel
with its lower bound, over the ring of window pages, ``ops/
ragged_attention.py::mla_paged_attention``) that ran inside executions of
the ``_decode`` program on device 0 in the profiler's trace, over the number
of those executions: the three sliding layers of a step.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"swa_latent_attention")
