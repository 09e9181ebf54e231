"""setup_build_s — the processor's construction, less what is named inside it.

Seconds under ``arkflow_stage_seconds{stage=setup_build}`` at the window's
open: the whole of ``tpu_generate``'s / ``tpu_inference``'s builder —
tokenizer, mesh, page pools and tables, the jitted steps, staging pools —
LESS the stages nested in it (init, restore, placement, the probe), which
observe their own time. Nothing on a program without the stage.
"""

from benchmark.lib.setup import stage_s


def read(view):
    return stage_s(view, "setup_build")
