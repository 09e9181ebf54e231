"""gen_prepare_ms — host time a generation step takes to prepare.

Seconds added over the window to ``arkflow_stage_seconds{stage=gen_prepare}``
(``tpu/serving.py``, the step methods: page reservation, the page table, the
host-to-device copies of tokens / lengths / mask, the RNG split, a chunk's
ids and table, up to the hand-off) over the device steps of the window (the
observations of ``gen_device_wait``, one a step of any kind). Host clock
inside the program.
"""


def read(view):
    stage_s, _ = view.hist("arkflow_stage_seconds", stage="gen_prepare")
    _, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    return None if steps <= 0 else stage_s / steps * 1e3
