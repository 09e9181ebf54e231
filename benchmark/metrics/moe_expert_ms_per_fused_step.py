"""moe_expert_ms_per_fused_step — device time of the expert products in a fused step.

Seconds of the ``moe_expert*`` kernels that ran inside executions of the
``_fused`` program on device 0 in the profiler's trace, over the number of
those executions: every expert layer's ONE product over the block's rows —
the lanes' and the chunk's, so each expert that either hit crosses HBM once
—, whichever kernel ran it: ``moe_expert_grouped`` where the block is wider
than one token tile (16 lanes + 128 chunk rows: ``ops/moe_grouped.py``),
``moe_expert_swiglu`` where it is not. What ``moe_expert_ms_per_step`` +
``moe_expert_ms_per_chunk`` cost in two programs, in one. A program that
does not fuse (the parent) and a run without a trace read nothing.
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
