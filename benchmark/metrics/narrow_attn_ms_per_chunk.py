"""narrow_attn_ms_per_chunk — device time of the narrow-head attention in a prefill chunk.

Seconds of the ``paged_flash_attention*`` kernel that ran inside executions
of the ``_chunk`` program on device 0 in the profiler's trace, over the
number of those executions: the three attention layers of a 256-token
chunk, whose eight query tiles of 32 positions each walk the row's pages up
to their own last query. It grows with the chunk's offset in its prompt;
the trace's chunks sit at the offsets the mix gives (mean prompt ~520).
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    return kernel_ms_per_chunk(view, r"paged_flash_attention")
