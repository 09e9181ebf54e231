"""dsa_attn_ms_per_step — device time of the attention over the selected keys in a decode step.

Seconds of the ``dsa_sparse_attention*`` kernel (the latent attention kernel
in place over each lane's context, the indexer's choice of 2,048 keys as its
mask, ``models/paged_decode.py::_attend_selected``) that ran inside
executions of the ``_decode`` program on device 0 in the profiler's trace,
over the number of those executions: both indexed layers of a step. The
sort that makes the choice is ``dsa_topk_ms_per_step``'s.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    return kernel_ms_per_decode(view, r"dsa_sparse_attention")
