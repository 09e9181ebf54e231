"""setup_init_params_s — set-up spent making the host tree of weights.

Seconds under ``arkflow_stage_seconds{stage=setup_init_params}`` (the
family's ``init``: the benchmark's random float32 masters, op by op on the
host; ``tpu/runner.py::init_host_params``) plus ``{stage=setup_restore}`` (a
deployment's ``checkpoint.restore``; no cell has one), at the window's open.
Nothing on a program without the stages.
"""

from benchmark.lib.setup import stage_s


def read(view):
    return stage_s(view, "setup_init_params", "setup_restore")
