"""kda_update_ms_per_step — device time of the per-channel delta rule's state update in a decode step.

Seconds of the ``kda_state_update`` kernel (``ops/kda_scan.py``: a lane's
float32 state read once and written once, the decay a key channel applied as
a column, both contractions on the block in VMEM) that ran inside executions
of the ``_decode`` program on device 0 in the profiler's trace, over the
number of those executions: all six linear layers of a step together. What
feeds the kernel (projections, convs, gates: plain XLA) is not in it.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"kda_state_update")
