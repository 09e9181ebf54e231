"""ssm_scan_ms_per_chunk — device time of the chunked state scan in a prefill chunk.

Seconds of the ``ssm_chunk_scan`` kernel (``ops/ssm_scan.py``: Mamba-2's
chunked form over a chunk of a prompt, from the slot's state to the slot's
state) that ran inside executions of the ``_chunk`` program on device 0 in
the profiler's trace, over the number of those executions: all layers of a
chunk together. What feeds the kernel (the projections, the conv, the
cumulative steps: plain XLA) is not in this time.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    return kernel_ms_per_chunk(view, r"ssm_chunk_scan")
