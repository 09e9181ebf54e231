"""gen_apply_ms — host time a generation step takes to apply its result.

Seconds added over the window to ``arkflow_stage_seconds{stage=gen_apply}``
(``tpu/serving.py``, the step methods: the token fetch, the first token of a
finished prefill, the bookkeeping of every slot, finished requests) over the
device steps of the window (the observations of ``gen_device_wait``, one a
step of any kind). Host clock inside the program.
"""


def read(view):
    stage_s, _ = view.hist("arkflow_stage_seconds", stage="gen_apply")
    _, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    return None if steps <= 0 else stage_s / steps * 1e3
