"""attn_per_head_tiles_pct — share of a chunk's attention tiles multiplied a K/V head at a time.

The per-head paged kernel (``ops/ragged_attention.paged_flash_attention``)
cuts a call into (row, query tile) programs and makes a tile's two products
either once for all heads, masking every column whose K/V head is not the
row's own (three of four, seven of eight: what a decode step's few rows
take), or a K/V head at a time over that head's own query rows and keys
(a chunk's tile, since PR 43). The server counts the programs of every
step, summed over layers, from the step's shapes on the host by the
kernel's own predicate (``per_kv_head``): counter
``arkflow_gen_attn_tiles_total{kind, product}`` (``tpu/serving.py::
_note_walk``). This reader: the ``kind="chunk"`` programs whose product
was ``per_kv_head`` over all of them, in percent. 100 where every prefill
chunk's attention leaves the masked product; 0 where the chunk's rows a
K/V head are no multiple of a sublane tile. A program that predates the
counter, a server on ``decode_kernel: gather`` and a latent model (another
kernel) read nothing.
"""


def read(view):
    tiles = {product: view.counter("arkflow_gen_attn_tiles_total",
                                   kind="chunk", product=product)
             for product in ("per_kv_head", "all_heads")}
    total = sum(tiles.values())
    return None if total <= 0 else 100.0 * tiles["per_kv_head"] / total
