"""decode_step_ms — device time of one lockstep decode step.

Median duration of the executions of the compiled ``_decode`` program
(``tpu/serving.py::_build_jitted``) on device 0 in the profiler's trace.
Device time only: the host's fetch of the tokens and its bookkeeping are
not in it (they are the idle gaps of the breakdown).
"""

from benchmark.lib.readers import module_ms


def read(view):
    return module_ms(view, r"jit__decode")
