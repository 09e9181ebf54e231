"""moe_chunk_experts_hit_pct — share of a layer's routed experts a prefill chunk reads.

Mean over the window's prefill chunks of the distinct experts hit, itself
the mean over the expert layers (histogram ``arkflow_gen_moe_experts_hit``
``{kind=chunk}``: computed on the device inside each chunk from the routing
of its unpadded tokens, summed on the device from chunk to chunk and fetched
with the prompt's first token, ``tpu/serving.py``), over
``n_routed_experts``. A 128-token chunk routes 768 pairs a layer; a router
that spreads them evenly reads every expert (100 %). What a chunk reads of
the expert weights scales with this share, and so does ``prefill_chunk_ms``.
"""


def read(view):
    hit_sum, chunks = view.hist("arkflow_gen_moe_experts_hit", kind="chunk")
    if chunks <= 0:
        return None
    return 100.0 * hit_sum / chunks / view.sizes["n_routed_experts"]
