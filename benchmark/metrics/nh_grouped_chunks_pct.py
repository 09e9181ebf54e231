"""nh_grouped_chunks_pct — share of the steps' expert products that ran grouped by expert (Nemotron-H's keys).

Increase over the window of ``arkflow_gen_moe_grouped_products_total`` (every
``kind``: an expert layer of a device step whose product took more rows than
one token tile and so ran ``moe_expert_relu2_grouped``) over the window's
routed steps (the observations of ``arkflow_gen_moe_experts_hit``) times the
5 expert layers. The cell's 192 lanes and its 512-row chunks are both above
a tile: 100. (``moe_grouped_chunks_pct`` counts every layer as an expert
layer: 5 / 13 of the truth here.)
"""

from benchmark.lib.costs_nemotron_h import sizes_of

GROUPED = "arkflow_gen_moe_grouped_products_total"


def read(view):
    s = sizes_of(view)
    snap = getattr(view, "_close", None) or {}
    if s is None or not any(name == GROUPED for name, _ in snap):
        return None
    _, steps = view.hist("arkflow_gen_moe_experts_hit")
    if steps <= 0 or s["moe_layers"] <= 0:
        return None
    return 100.0 * view.counter(GROUPED) / (steps * s["moe_layers"])
