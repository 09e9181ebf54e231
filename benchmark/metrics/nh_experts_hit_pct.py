"""nh_experts_hit_pct — share of the HELD routed experts a decode step reads (Nemotron-H's keys).

Mean over the window's decode steps of the distinct held experts hit, itself
the mean over the expert layers (histogram ``arkflow_gen_moe_experts_hit
{kind=decode}``), over the experts held here (``experts_held``: 64 of 128).
192 lanes x top-6 of 128 give each held expert 9 tokens a step: all 64 are
hit. (``gqa_moe_experts_hit_pct`` reads ``num_experts``; this family's
published key is ``n_routed_experts``.)
"""

from benchmark.lib.costs_nemotron_h import held_experts, sizes_of, step_routing


def read(view):
    routing = step_routing(view, "decode")
    if routing is None or sizes_of(view) is None:
        return None
    return 100.0 * routing[0] / held_experts(view)
