"""gen_launch_wake_ms — a decode step's launch latency + thread wake.

Mean ``gen_ready_wait{kind=decode}`` over the whole window (what the host
waits for one decode step, ``gen_ready_wait_ms``) less the MEAN device time
of the executions of ``jit__decode`` in the profiler's trace (device 0; a
mean, not ``decode_step_ms``'s median: a mean is what subtracts from a
mean). What is left is the time between the end of the dispatch and the
program's start on the device plus the time from its end to the return of
``block_until_ready``: host time the device idles for and no span names.

It subtracts a trace of a few seconds from a window of 45, so it is sound
only where the decode step is stationary: 16 slots at staggered phases under
a standing backlog (``mistral_l6``, ``mistral_tp4``, ``kanana2_l6``). It is
not listed for ``falconh1_l4``, whose 128 lanes fill and finish in waves of
~16.7 s, so that the traced steps are one phase of a context that runs 30 ->
1,024, nor for ``dots3_l5``, which is prefill-bound and hardly idles.

It reads the program's histogram AND the device trace; ``BENCHMARK.json``
lists it under ``device_trace``, the source without which it reads nothing.

None without a module line (a rehearsal has no device plane), without the
stage (an older program), or where the difference is negative beyond
-0.05 ms (the two means are then not of the same steps).
"""

from benchmark.lib.hop import stage_mean_ms


def read(view):
    wait_ms = stage_mean_ms(view, "gen_ready_wait", kind="decode")
    modules = (view.trace or {}).get("modules")
    if wait_ms is None or not modules:
        return None
    durs = [d for name, ds in modules.items() if "jit__decode" in name
            for d in ds]
    if not durs:
        return None
    left = wait_ms - sum(durs) / len(durs) * 1e3
    return None if left < -0.05 else left
