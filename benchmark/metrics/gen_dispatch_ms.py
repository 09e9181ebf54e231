"""gen_dispatch_ms — host time of one generate step's jitted call.

Seconds added over the window to ``arkflow_stage_seconds{stage=gen_dispatch}``
summed over ``kind`` (``tpu/serving.py::_run_device_step``, on the executor
thread, annotation ``gen_dispatch:<kind>``: argument flattening, the upload
of the step's one packed array, the runtime's enqueue, any output
allocation) over its observations, one a device step. It lies inside
``gen_device_wait``. Host clock inside the program.
"""

from benchmark.lib.hop import stage_mean_ms


def read(view):
    return stage_mean_ms(view, "gen_dispatch")
