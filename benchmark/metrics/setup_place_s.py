"""setup_place_s — set-up spent putting the weights on the device.

Seconds under ``arkflow_stage_seconds{stage=setup_place}`` at the window's
open: transfer and cast, leaf by leaf, to where the tree is on the device
(``tpu_generate.py::_place_params``; ``tpu/runner.py``'s construction, with
its serving-dtype cast on the host). Nothing on a program without the stage.
"""

from benchmark.lib.setup import stage_s


def read(view):
    return stage_s(view, "setup_place")
