"""moe_grouped_chunks_pct — share of the prefill chunks whose expert products ran grouped by expert.

Increase over the window of the counter ``arkflow_gen_moe_grouped_products_
total{kind=chunk}`` (``tpu/serving.py::_note_moe``: per chunk, the expert
layers whose product took more rows than one token tile and so ran the
grouped kernel, ``ops/moe_grouped.py`` — each hit expert read once a chunk,
multiplying its own rows only), over the window's chunks (the observations
of ``arkflow_gen_moe_experts_hit{kind=chunk}``, fed beside it) times the
expert layers. A step's row count decides, so it reads 100 where the chunk
is wider than a tile (512 rows, LFM2's 256) and 0 where it is one tile
(``kanana2_l6``'s 128: the bypass). A program that predates the counter, a
model that routes nothing and a window without chunks read nothing.
"""

GROUPED = "arkflow_gen_moe_grouped_products_total"


def read(view):
    snap = getattr(view, "_close", None) or {}
    if not any(name == GROUPED for name, _ in snap):
        return None
    _, chunks = view.hist("arkflow_gen_moe_experts_hit", kind="chunk")
    s = view.sizes
    layers = s.get("num_hidden_layers", 0) - s.get("first_k_dense_replace", 0)
    if chunks <= 0 or layers <= 0:
        return None
    return 100.0 * view.counter(GROUPED, kind="chunk") / (chunks * layers)
