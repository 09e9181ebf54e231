"""gen_queue_wait_p50_ms — how long a request waits for a decode slot.

Median over the window of the program's ``gen_queue_wait`` span
(``tpu/serving.py::_admit_pending``): from ``generate()`` queueing the
request to the serve loop reserving its pages and giving it a slot.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "gen_queue_wait")
