"""device_dispatch_wait_p50_ms — how long a classify step waits for its turn.

Median over the window's classify steps of the program's
``device_dispatch_wait`` span (``tpu/runner.py::infer``): a worker holding a
prepared batch waits for a permit of the in-flight window. The program
records the span only for a wait over 0.5 ms, so a step without one counts
as 0: the median is over every ``device_step`` of the window. It reads 0
while the window admits every worker (a step then queues on the device,
inside ``device_step``, which is why ``infer_step_ms`` reads twice the
device's step time) and about one step once the window is the limit.
"""

from benchmark.lib.stats import median


def read(view):
    steps = len(view.spans("device_step"))
    if not steps:
        return None
    waits = list(view.spans("device_dispatch_wait"))
    return median(waits + [0.0] * max(0, steps - len(waits))) * 1e3
