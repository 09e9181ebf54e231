"""gen_ready_wait_ms — what the host waits for one decode step.

Mean over the window of ``arkflow_stage_seconds{stage=gen_ready_wait,
kind=decode}`` (``tpu/serving.py::_run_device_step``, on the executor
thread, annotation ``gen_ready_wait:decode``): ``copy_to_host_async`` +
``block_until_ready`` behind the dispatch, so launch latency + the device's
decode step + the wake of the thread. Decode alone: beside
``decode_step_ms`` it reads launch + wake by eye, which a histogram that
mixed a decode step with a chunk could not. Host clock inside the program.
"""

from benchmark.lib.hop import stage_mean_ms


def read(view):
    return stage_mean_ms(view, "gen_ready_wait", kind="decode")
