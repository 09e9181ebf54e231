"""gqa_moe_experts_hit_pct — share of the HELD routed experts a decode step reads (K-EXAONE's keys).

Mean over the window's decode steps of the distinct held experts hit, itself
the mean over the expert layers (histogram ``arkflow_gen_moe_experts_hit``
``{kind=decode}``: with a held share the experts hit are of the experts
held, ``decoder.py::moe_step_stats``), over ``num_experts`` — the experts
held here, 16. Uniform routing of 48 lanes x top-8 of 128 hits 15.2 of 16.
"""

from benchmark.lib.costs_mla_moe import decode_routing


def read(view):
    r = decode_routing(view)
    held = view.sizes.get("num_experts")
    return None if r is None or not held else 100.0 * r[0] / held
