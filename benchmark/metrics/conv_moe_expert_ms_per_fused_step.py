"""conv_moe_expert_ms_per_fused_step — device time of the expert products in a fused step of the conv / routed model.

Seconds of the ``moe_expert*`` kernels that ran inside executions of the
``_fused`` program on device 0 in the profiler's trace, over the number of
those executions: every expert layer's ONE product over the block's 384
rows — 128 lanes and a 256-token chunk, three token tiles, so the product
runs grouped by expert (``moe_expert_grouped``: ``ops/moe_grouped.py``) and
each expert that lanes OR chunk hit crosses HBM once. What
``moe_expert_ms_per_step`` + ``moe_expert_ms_per_chunk`` cost in two
programs, in one. A program that does not fuse (the parent) and a run
without a trace read nothing.
"""

from benchmark.lib.xtrace import ops_inside


def read(view):
    t = getattr(view, "trace", None)
    if not t or "first_device" not in t:
        return None
    dev = t["first_device"]
    total, steps = ops_inside(dev["ops"], dev["modules"], r"jit__fused",
                              r"moe_expert_swiglu|moe_expert_grouped")
    return None if not steps or total <= 0 else total / steps * 1e3
