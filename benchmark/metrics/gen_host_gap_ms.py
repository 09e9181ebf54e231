"""gen_host_gap_ms — how long the device waits between two generation steps.

Mean over the window of ``arkflow_tpu_device_idle_gap_seconds{path=generate}``
(``tpu/serving.py::_track_gen_dispatch``): host clock from the moment one
device step's result reached the host to the moment the next step is handed
to the device, measured where it happens. The stages ``gen_apply``,
``gen_admit``, ``gen_prepare`` and the first hop of ``gen_handoff`` should
add up to it; a gap they do not explain is a phase nobody named.
"""


def read(view):
    gap_s, gaps = view.hist("arkflow_tpu_device_idle_gap_seconds",
                            path="generate")
    return None if gaps <= 0 else gap_s / gaps * 1e3
