"""dsa_index_ms_per_step — device time of the indexer's scores in a decode step.

Seconds of the ``dsa_index_topk*`` kernel (the index scores over every key
in context, ``ops/ragged_attention.py::dsa_index_scores``) that ran inside
executions of the ``_decode`` program on device 0 in the profiler's trace,
over the number of those executions: both indexed layers of a step. The sort
that picks the top 2,048 of the scores is plain XLA and not in this time: it
is ``dsa_topk_ms_per_step``'s, the other half of the indexer.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"dsa_index_topk")
