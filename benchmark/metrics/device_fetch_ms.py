"""device_fetch_ms — copying a classify step's outputs to the host.

Median over the window of the program's ``device_fetch`` span
(``tpu/runner.py::_wait_and_fetch``): ``device_get`` and the host conversion
alone, after ``block_until_ready`` returned; the step and the wait for it are
not in it.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "device_fetch")
