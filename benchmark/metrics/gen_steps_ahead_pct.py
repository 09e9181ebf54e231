"""gen_steps_ahead_pct — share of generate steps enqueued ahead of the device.

Increase over the window of the counter ``arkflow_gen_steps_ahead_total``
(every ``kind``; ``tpu/serving.py::_run_ahead``: a step of any kind — decode,
chunk, one-shot prefill — that was enqueued while another step of the same
server was still in flight, so the device finds its successor queued when a
step ends) over the device steps of the window (the observations of
``gen_device_wait``, one a step of any kind), in percent. 0 on a server that
serves in lockstep (``dispatch_depth: 1``, sampling, speculation, a block or
state on which a lane riding one step too long is not exact); what is left
of 100 is one bubble per prompt's last chunk with nothing to enqueue behind
it, cold programs and steps under page pressure. A program that predates
the counter reads nothing, and so does a window in which no step ran ahead
(as ``gen_uploads_per_step`` does: a counter that did not move and one that
is not there look the same from here).
"""


def read(view):
    ahead = view.counter("arkflow_gen_steps_ahead_total")
    _, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    return None if steps <= 0 or ahead <= 0 else ahead / steps * 100.0
