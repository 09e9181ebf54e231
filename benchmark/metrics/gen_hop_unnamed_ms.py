"""gen_hop_unnamed_ms — executor-thread time of a generate step under no name.

(``gen_device_wait`` - ``gen_dispatch`` - ``gen_ready_wait`` - ``gen_fetch``),
the window's sums of ``arkflow_stage_seconds`` (the three inner stages summed
over ``kind``), over the device steps of the window (the observations of
``gen_device_wait``): what the thread did inside the hop outside the three
stages that divide it (``core.apply_chaos``, tuple handling, the stamps
themselves). Expected under 0.05 ms; growth means work entered the hop
unnamed. None where the program has no inner stage (an older commit).
"""

from benchmark.lib.hop import HOP_STAGES


def read(view):
    wait_s, steps = view.hist("arkflow_stage_seconds", stage="gen_device_wait")
    inner = [view.hist("arkflow_stage_seconds", stage=s) for s in HOP_STAGES]
    if steps <= 0 or inner[0][1] <= 0:
        return None
    return (wait_s - sum(s for s, _ in inner)) / steps * 1e3
