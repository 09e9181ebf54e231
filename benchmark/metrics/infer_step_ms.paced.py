"""infer_step_ms.paced — one classify step as the host sees it.

Median over the window of the program's ``device_step`` span
(``tpu/runner.py::infer``): host clock from dispatch to the end of
``device_get``, so it INCLUDES the fetch of the outputs and any wait behind
another step already on the device. The device's own time for the step is
in ``bert_step_mxu_pct``'s denominator.

The same reading as ``infer_step_ms``, in the paced cell, where it moves the
median latency.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "device_step")
