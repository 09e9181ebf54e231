"""moe_load_max_over_mean — the busiest expert's load over the mean load.

Per decode step the largest number of tokens any expert of any expert layer
received (histogram ``arkflow_gen_moe_max_load{kind=decode}``), over the
mean load of an expert: the (token, expert) pairs of a layer
(``arkflow_gen_moe_assignments_total{kind=decode}`` over steps and expert
layers) over ``n_routed_experts``. 1 is perfectly even; routing is dropless,
so an uneven load costs time, never tokens.
"""

from benchmark.lib.costs_mla_moe import decode_routing


def read(view):
    r = decode_routing(view)
    if r is None or r[2] <= 0:
        return None
    return r[1] / (r[2] / view.sizes["n_routed_experts"])
