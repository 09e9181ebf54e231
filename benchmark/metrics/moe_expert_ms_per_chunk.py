"""moe_expert_ms_per_chunk — device time of the expert products in a prefill chunk.

Seconds of the ``moe_expert*`` kernels that ran inside executions of the
``_chunk`` program on device 0 in the profiler's trace, over the number of
those executions: every expert layer's product of a chunk, whichever kernel
ran it — ``moe_expert_swiglu`` a token tile at a time (one call a 128-row
tile, each with its own hit list: four calls a layer of a 512-row chunk
before PR 52, and ``kanana2_l6``'s one-tile chunks still) or
``moe_expert_grouped`` once a layer (``ops/moe_grouped.py``). The part of
``prefill_chunk_ms`` that the grouped product moves.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    return kernel_ms_per_chunk(view, r"moe_expert")
