"""collective_ms_per_step — device time of the collectives in one decode step.

Seconds of the all-reduce (and all-gather / reduce-scatter / collective-
permute) operations that ran inside executions of the ``_decode`` program on
device 0 in the profiler's trace, over the number of those executions.
"""

from benchmark.lib.xtrace import ops_inside


def read(view):
    t = view.trace
    if not t or "first_device" not in t:
        return None
    dev = t["first_device"]
    total, steps = ops_inside(
        dev["ops"], dev["modules"], r"jit__decode",
        r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")
    return None if not steps else total / steps * 1e3
