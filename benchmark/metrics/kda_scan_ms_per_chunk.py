"""kda_scan_ms_per_chunk — device time of the chunked per-channel delta rule in a prefill chunk.

Seconds of the ``kda_chunk_scan`` kernel (``ops/kda_scan.py``: the WY form in
blocks of 64 tokens with per-channel cumulative decays, every pairwise decay
formed level by level of a halving of the block so that no exponent above 0
is taken, from the slot's state to the slot's state) that ran inside
executions of the ``_chunk`` program on device 0 in the profiler's trace,
over the number of those executions: all six linear layers of a chunk
together.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    return kernel_ms_per_chunk(view, r"kda_chunk_scan")
