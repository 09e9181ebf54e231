"""conv_moe_chunks_fused_pct — share of the conv / routed model's prefill chunks that rode a decode step.

Increase over the window of the counter ``arkflow_gen_chunks_total{mode}``
(``tpu/serving.py``: incremented once a chunk is issued, by the step that
carries it — ``mode="fused"`` in ``_step``, where a decode step was due and
the chunk went through its pass over the weights, so the 32 experts a layer
were read once for lanes and chunk; ``mode="alone"`` in ``_prefill_step``, a
step of its own: no lane was decoding (the first fill), the server does not
fuse, or the chunk is the last of a prompt that stops after prefill): the
``fused`` chunks over all chunks, in percent. How often the mechanism of PR
58 engages on a model whose block carries rows of a state pool; 0 on a
server that alternates (the parent). A window without chunks reads nothing.
"""


def read(view):
    fused = view.counter("arkflow_gen_chunks_total", mode="fused")
    total = view.counter("arkflow_gen_chunks_total")
    return None if total <= 0 else 100.0 * fused / total
