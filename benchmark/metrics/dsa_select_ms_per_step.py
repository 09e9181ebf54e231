"""dsa_select_ms_per_step — device time of the indexer's choice (the threshold kernel) in a decode step.

Seconds of the ``dsa_topk_select*`` kernel (``ops/topk_select.py``: each
lane's K-th largest index score found bit by bit over a tile held in VMEM,
and the choice written as the mask the latent kernel reads; since PR 47 what
``models/paged_decode.py::_index_select`` serves with in place of
``lax.top_k``'s full sort) that ran inside executions of the ``_decode``
program on device 0 in the profiler's trace, over the number of those
executions: both indexed layers of a step. With ``dsa_index_ms_per_step``
(the scores) this is the whole indexer. Nothing to read — the line leaves the
metric out — where no such kernel ran inside ``jit__decode``: a program that
still sorts (the parent of PR 47), a server on ``decode_kernel: gather``, a
model without an indexer.
"""

from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    # the trace names an op by its whole HLO line, operands by name: anchored
    # (as ``dsa_topk_ms_per_step`` is), so that the latent kernel, which
    # CONSUMES the mask, does not count
    return kernel_ms_per_decode(view, r"^%?dsa_topk_select")
