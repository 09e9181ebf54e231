"""dsa_selected_pct — share of the keys in context that the indexed layers attend.

Keys attended over keys in context, summed over the window's decode steps
and prefill chunks, their queries and the indexed full layers (counters
``arkflow_gen_dsa_selected_total`` / ``arkflow_gen_dsa_context_total``,
counted on the device inside each step from the indexer's choice and fetched
with its tokens, ``tpu/serving.py``). 100 % while every context is within
``index_topk``; at a context of 4.8k and top-2,048 about 43 %. What the
selection saves the attention, and what the indexer has to earn back.
"""


def read(view):
    selected = view.counter("arkflow_gen_dsa_selected_total")
    context = view.counter("arkflow_gen_dsa_context_total")
    return None if context <= 0 else 100.0 * selected / context
