"""ssm_update_ms_per_step — device time of the recurrent-state update in a decode step.

Seconds of the ``ssm_state_update`` kernel (``ops/ssm_scan.py``: one token a
lane — read the lane's float32 state, decay, add, contract with C, write it
back) that ran inside executions of the ``_decode`` program on device 0 in
the profiler's trace, over the number of those executions: all layers of a
step together. A program without the kernel reads nothing.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"ssm_state_update")
