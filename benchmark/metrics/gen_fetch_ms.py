"""gen_fetch_ms — copying a generate step's tokens to the host.

Seconds added over the window to ``arkflow_stage_seconds{stage=gen_fetch}``
summed over ``kind`` (``tpu/serving.py::_run_device_step``, on the executor
thread, annotation ``gen_fetch:<kind>``: ``np.asarray`` of the step's token
array after ``block_until_ready`` returned) over its OWN observations: a
prompt's chunk before its last leaves its token on the device and observes
none, so the steps of the window are the wrong divisor. Host clock inside
the program.
"""

from benchmark.lib.hop import stage_mean_ms


def read(view):
    return stage_mean_ms(view, "gen_fetch")
