"""mhc_mix_ms_per_chunk — device time of the residual streams' mixing in a prefill chunk.

Seconds of the ``mhc_pre`` and ``mhc_post`` kernels (``ops/mhc_mix.py``)
that ran inside executions of the ``_chunk`` program on device 0 in the
profiler's trace, over the number of those executions: both kernels of all
twenty sub-layers of a 512-token chunk together — the part of
``prefill_chunk_ms`` the streams cost. A program that has no such kernel
reads nothing.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk

#: an event is named by its whole HLO line: anchored, so that an op which
#: only READS a kernel's result (``reshape(... %mhc_post.35)``) is not counted
KERNELS = r"^%?mhc_(pre|post)[.\d]* ="


def read(view):
    return kernel_ms_per_chunk(view, KERNELS)
