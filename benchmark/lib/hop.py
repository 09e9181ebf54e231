"""What the readers of the generate step's executor hop share. The program
divides the hop's ``gen_device_wait`` (``tpu/serving.py::_Hop``) into three
stages stamped on the executor thread and observed, once a device step, into
``arkflow_stage_seconds{stage, kind}``: ``gen_dispatch`` (the jitted call),
``gen_ready_wait`` (``copy_to_host_async`` + ``block_until_ready``) and
``gen_fetch`` (``np.asarray``, only on a step that fetches). A program that
has no such stage (an older commit) gives every reader nothing to read."""

from __future__ import annotations

from typing import Optional

HOP_STAGES = ("gen_dispatch", "gen_ready_wait", "gen_fetch")


def stage_mean_ms(view, stage: str, **labels) -> Optional[float]:
    """Mean, in ms, of the window's observations of ``stage`` (over every
    ``kind`` unless ``labels`` names one); None where there are none."""
    stage_s, n = view.hist("arkflow_stage_seconds", stage=stage, **labels)
    return None if n <= 0 else stage_s / n * 1e3
