"""dsa_topk_ms_per_step — device time of the indexer's top-k (a full sort) in a decode step.

Seconds of the ``%sort.N`` ops (plain XLA: what ``lax.top_k`` of 2,048 lowers
to, one full sort of each lane's float32 index scores over its context,
``models/paged_decode.py::_index_select``) that ran inside executions of the
``_decode`` program on device 0 in the profiler's trace, over the number of
those executions: both indexed layers of a step. The router's top 8 of 256
sorts too and is in this time (under 1 % of it: PERF.md §5). With
``dsa_index_ms_per_step`` (the scores) this is the whole indexer.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    # the trace names an op by its whole HLO line: "%sort.27 = (f32[32,1,
    # 12544], s32[...]) sort(...)"; anchored, so that an op that only
    # CONSUMES a sort's result does not count
    return kernel_ms_per_decode(view, r"^%?sort\.")
