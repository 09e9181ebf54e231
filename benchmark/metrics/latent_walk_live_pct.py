"""latent_walk_live_pct — share of the kept page table's columns the latent attention kernel walked.

The latent kernel (``ops/ragged_attention.mla_paged_attention``; in a trace
``mla_paged_attention``, ``dsa_sparse_attention``, ``swa_latent_attention``)
walks a row's live pages itself since PR 44: a (row, query tile) program
copies the pages up to its last query and no further, where the grid before
it took a step for every column of the table, live or not. The server counts,
a layer, from lengths on the host (``tpu/serving.py::_note_walk``, kept pool,
no fetch): ``arkflow_gen_attn_pages_walked_total{kind}`` — pages up to each
row's last query, an idle lane its one scratch page — and
``arkflow_gen_attn_table_columns_total{kind}`` — the table's columns of the
rows the kernel was called with. This reader: pages walked over columns,
decode steps and chunks together, in percent: the share of the old grid that
was live, and what the walk's cost now follows. A program that predates the
count for latent models (the parent), a server on ``decode_kernel: gather``
and a per-head model (whose walk has counted since PR 41, under the same
names) read nothing.
"""


def read(view):
    if "kv_lora_rank" not in view.sizes:
        return None
    walked = view.counter("arkflow_gen_attn_pages_walked_total")
    columns = view.counter("arkflow_gen_attn_table_columns_total")
    return None if columns <= 0 else 100.0 * walked / columns
