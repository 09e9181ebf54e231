"""setup_probe_s — the kernel parity probe at construction.

Seconds under ``arkflow_stage_seconds{stage=setup_probe}`` at the window's
open (``tpu/serving.py::_paged_kernel_parity``: a program of its own that
compiles and runs every kernel both ways). 0 in a cell whose server runs no
probe (classify; a mesh); nothing on a program without the stages.
"""

from benchmark.lib.setup import stage_s


def read(view):
    return stage_s(view, "setup_probe")
