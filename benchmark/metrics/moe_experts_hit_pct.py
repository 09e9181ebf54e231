"""moe_experts_hit_pct — share of a layer's routed experts a decode step reads.

Mean over the window's decode steps of the distinct experts hit, itself the
mean over the expert layers (histogram ``arkflow_gen_moe_experts_hit``
``{kind=decode}``, computed on the device inside the step from the routing
of the active lanes and fetched with the tokens, ``tpu/serving.py``), over
``n_routed_experts``. Uniform routing of 16 lanes x top-6 of 128 gives 54 %;
a lower share means fewer weight bytes a step, a higher one more.
"""

from benchmark.lib.costs_mla_moe import decode_routing


def read(view):
    r = decode_routing(view)
    return None if r is None else 100.0 * r[0] / view.sizes["n_routed_experts"]
