"""hetero_full_attn_ms_per_chunk — device time of the full layers' attention in a prefill chunk.

Seconds of the ``paged_flash_attention*`` kernel that ran inside executions
of the ``_chunk`` program on device 0 in the profiler's trace, over the
number of those executions: the two full layers of a 512-token chunk, whose
32 query tiles of 1,024 folded rows each walk the row's kept pages up to
their own last query. It grows with the chunk's offset in its prompt; the
trace's chunks sit at the offsets the mix gives (mean prompt 4.9k).
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    return kernel_ms_per_chunk(view, r"paged_flash_attention")
