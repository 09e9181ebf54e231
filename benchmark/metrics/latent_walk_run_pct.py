"""latent_walk_run_pct — share of the pages the latent attention kernel walked that it moved in runs.

Since PR 54 the latent kernel (``ops/ragged_attention.mla_paged_attention``;
in a trace ``mla_paged_attention``, ``dsa_sparse_attention``) walks a group
in aligned stretches of ``PAGE_RUN`` pages: a stretch whose table entries
name consecutive physical pages moves as ONE copy a pool, any other a page
at a time — and the server's allocator (``tpu/serving.py::_FreePages``) hands
a slot its pages in such blocks. A copy's issue, not its bytes, is what the
walk waits for, so the walk's cost follows this share. The server counts, a
layer, on the host (``tpu/serving.py::_note_walk``, kept pool, no fetch):
``arkflow_gen_attn_pages_walked_total{kind}`` — pages up to each row's last
query — and ``arkflow_gen_attn_pages_in_runs_total{kind}`` — those of them
in whole stretches of neighbours, by the kernel's own predicate
(``ops/ragged_attention.pages_in_runs``) over the rows of the page table the
step carries. This reader: pages in runs over pages walked, decode steps and
chunks together, in percent. A row's last pages past a whole stretch, an
idle lane's scratch page and pages shared through the prefix cache out of
step with the blocks are walked a page at a time. A program without the
count (the parent), a server on ``decode_kernel: gather`` and a per-head
model (whose kernel takes no runs) read nothing.
"""


IN_RUNS = "arkflow_gen_attn_pages_in_runs_total"


def read(view):
    snap = getattr(view, "_close", None) or {}
    if "kv_lora_rank" not in view.sizes or not any(name == IN_RUNS for name, _ in snap):
        return None
    walked = view.counter("arkflow_gen_attn_pages_walked_total")
    return None if walked <= 0 else 100.0 * view.counter(IN_RUNS) / walked
