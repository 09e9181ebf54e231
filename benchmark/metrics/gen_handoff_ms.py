"""gen_handoff_ms — host time a generation step loses in thread hops.

Seconds added over the window to ``arkflow_stage_seconds{stage=gen_handoff}``
(``tpu/serving.py::_Hop``: the serve loop hands the blocking device call to an
executor thread -> the thread starts, and the thread ends -> the coroutine
resumes, whatever else the event loop ran in between included) over the
device steps of the window (the observations of ``gen_device_wait``, one a
step of any kind). Host clock inside the program.
"""


def read(view):
    stage_s, _ = view.hist("arkflow_stage_seconds", stage="gen_handoff")
    _, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    return None if steps <= 0 else stage_s / steps * 1e3
