"""gen_uploads_per_step — host arrays handed to the device for one generate step.

Increase over the window of the counter ``arkflow_gen_step_uploads_total``
(every ``kind``; ``tpu/serving.py::_build_jitted``, counted where a step's
operands are handed to its jitted program: each operand that is not on the
device already is one upload) over the device steps of the window (the
observations of ``gen_device_wait``, one a step of any kind). 1.0 while
every step's tokens, lengths, mask and page table go up packed in one array
and keys and counters stay on the device; more says some path feeds its
step by piecemeal puts again, each an allocation and a transfer the chip
waits for. A program that predates the counter reads nothing.
"""


def read(view):
    uploads = view.counter("arkflow_gen_step_uploads_total")
    _, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    return None if steps <= 0 or uploads <= 0 else uploads / steps
