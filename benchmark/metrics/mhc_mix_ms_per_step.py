"""mhc_mix_ms_per_step — device time of the residual streams' mixing in a decode step.

Seconds of the ``mhc_pre`` and ``mhc_post`` kernels (``ops/mhc_mix.py``: a
token's four residual streams read into a sub-layer's input, and written
back from its output, under Sinkhorn-normalised coefficients) that ran
inside executions of the ``_decode`` program on device 0 in the profiler's
trace, over the number of those executions: both kernels of all twenty
sub-layers of a step together. A program that has no such kernel (one
residual stream; a parent that predates it) reads nothing.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode

#: an event is named by its whole HLO line: anchored, so that an op which
#: only READS a kernel's result (``reshape(... %mhc_post.35)``) is not counted
KERNELS = r"^%?mhc_(pre|post)[.\d]* ="


def read(view):
    return kernel_ms_per_decode(view, KERNELS)
