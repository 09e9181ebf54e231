"""Needed bytes and operations of a decoder whose attention is EVA's (a
blocked exact window beside chunk summaries; EvaByte), computed from shapes:
the counts behind ``eva_attn_hbm_pct``, ``eva_summarise_hbm_pct``,
``eva_decode_hbm_pct`` and ``tools/profile_eva.py``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
once. A cached row is K and V of every K/V head (32 x 128 x 2 x 2 B =
16,384 B a layer at EvaByte's widths); a query attends the rows its slot
HOLDS — the summaries of its closed windows and its open window up to
itself —, not one row a position. Of the eight prediction heads a decode
step needs head 0 (the served one). A lower bound on what any implementation
moves: a share over 100 % means the count is wrong.
"""

from __future__ import annotations


def row_bytes(*, kv_heads: int, head_dim: int, kv_bytes: int = 2) -> int:
    """One cached row of one layer: K and V of every K/V head."""
    return 2 * kv_heads * head_dim * kv_bytes


def cached_rows(position: int, *, window: int, chunk: int) -> int:
    """Rows a slot holds once positions 0..``position`` - 1 are written:
    ``window / chunk`` a closed window, then the open window's."""
    return position // window * (window // chunk) + position % window


def layer_params(*, hidden: int, heads: int, kv_heads: int, head_dim: int,
                 ffn: int) -> int:
    """One layer: q and o (hidden x heads x head_dim), k and v (hidden x
    kv_heads x head_dim), the three SwiGLU matrices, ``eva_phi`` and
    ``eva_mu`` (kv_heads x head_dim each) and two norm scales."""
    return (2 * hidden * heads * head_dim + 2 * hidden * kv_heads * head_dim
            + 3 * hidden * ffn + 2 * kv_heads * head_dim + 2 * hidden)


def model_params(*, hidden: int, layers: int, heads: int, kv_heads: int,
                 head_dim: int, ffn: int, vocab: int, pred_heads: int) -> int:
    """Every parameter: the layers, the table, ``pred_heads`` output heads
    and the final norm."""
    return (layers * layer_params(hidden=hidden, heads=heads, kv_heads=kv_heads,
                                  head_dim=head_dim, ffn=ffn)
            + vocab * hidden + pred_heads * vocab * hidden + hidden)


def summarise_bytes(*, window: int, chunk: int, kv_heads: int, head_dim: int,
                    kv_bytes: int = 2) -> tuple[int, int]:
    """(read, written) by ONE close of one layer: the window's rows in, its
    ``window / chunk`` summary rows out (``phi`` and ``mu`` are 32 KB)."""
    row = row_bytes(kv_heads=kv_heads, head_dim=head_dim, kv_bytes=kv_bytes)
    return window * row, window // chunk * row


def summarise_flops(*, window: int, kv_heads: int, head_dim: int) -> int:
    """Operations of one close of one layer: a row's pooling logit (2 d a
    head), its weighted sums into K~ and V~ (4 d a head)."""
    return window * kv_heads * 6 * head_dim


def attention_bytes(*, rows: float, queries: float, layers: int, heads: int,
                    kv_heads: int, head_dim: int, kv_bytes: int = 2) -> float:
    """Bytes the attention calls of one step have to move over ``layers``:
    the ``rows`` cached rows attended (summed over the step's lanes; a
    chunk's rows are read once for all its queries), and each query's heads
    in and out."""
    return layers * (rows * row_bytes(kv_heads=kv_heads, head_dim=head_dim,
                                      kv_bytes=kv_bytes)
                     + queries * 2 * heads * head_dim * kv_bytes)


def attention_flops(*, pairs: float, layers: int, heads: int,
                    head_dim: int) -> float:
    """Operations of attention over ``pairs`` (query, cached row) pairs a
    layer: a score and a weighted sum, 4 d a head."""
    return layers * pairs * heads * 4 * head_dim


def decode_step_bytes(*, hidden: int, layers: int, heads: int, kv_heads: int,
                      head_dim: int, ffn: int, vocab: int, rows: float,
                      lanes: float, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """Bytes one decode step has to read: every layer's matrices, head 0 of
    the prediction heads, and the cached rows its lanes attend (``rows``:
    summed over lanes, a layer), plus the lanes' new rows written."""
    matrices = layers * (2 * hidden * heads * head_dim
                         + 2 * hidden * kv_heads * head_dim
                         + 3 * hidden * ffn) * weight_bytes
    leaves = layers * 2 * kv_heads * head_dim * 4
    row = row_bytes(kv_heads=kv_heads, head_dim=head_dim, kv_bytes=kv_bytes)
    return (matrices + leaves + hidden * vocab * weight_bytes
            + layers * (rows + lanes) * row)


def sizes_of(view) -> dict:
    """The keyword sizes above from a cell's published keys as run; None
    where the file states no such attention."""
    s = view.sizes
    if s.get("attention_class") != "eva":
        return None
    heads = s["num_attention_heads"]
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                heads=heads, kv_heads=s["num_key_value_heads"],
                head_dim=s.get("head_dim") or s["hidden_size"] // heads,
                ffn=s["intermediate_size"], vocab=s["vocab_size"])


def decode_rows(view):
    """(rows attended a decode step a layer, summed over its lanes; lanes a
    step) from the program's counters over the window; None without them."""
    steps = view.counter("arkflow_gen_decode_steps_total")
    rows = view.counter("arkflow_gen_eva_rows_attended_total", phase="decode")
    busy = view.gauge("arkflow_gen_slots_busy")
    if steps <= 0 or rows <= 0 or not busy:
        return None
    return rows / steps, sum(busy) / len(busy)


def close_calls(view, module_re: str = r"jit__(decode|chunk)",
                op_re: str = r"^eva_summarise(\.\d+)?$"):
    """(seconds, closes) of the window closes on device 0 of the trace. A
    close is a loop over the step's closing rows: a gather of the window's
    pages, the ``eva_summarise`` kernel (named ``op_re``), a scatter of the
    summary pages. Ops nest on the trace's line, so a close's time is that of
    the SMALLEST op that holds the kernel's event (the loop), a kernel call a
    closed row; the kernel's own time is not the close's (XLA may hand it the
    gathered rows in fast memory: the gather then paid for the bytes). None
    where no window closed in the traced stretch."""
    import re

    from benchmark.lib.xtrace import op_name

    t = view.trace
    if not t or "first_device" not in t:
        return None
    dev = t["first_device"]
    mre, ore = re.compile(module_re), re.compile(op_re)
    spans = sorted((m[1], m[1] + m[2]) for m in dev["modules"] if mre.search(m[0]))
    inside = lambda s: any(lo <= s < hi for lo, hi in spans)  # noqa: E731
    ops = sorted(((s, s + d, op_name(n)) for n, s, d in dev["ops"] if inside(s)),
                 key=lambda e: (e[0], -e[1]))
    loops: dict = {}
    stack: list = []
    for lo, hi, name in ops:
        while stack and stack[-1][1] <= lo:
            stack.pop()
        if ore.search(name) and stack:
            held = loops.setdefault(stack[-1][:2], [0, stack[-1][2]])
            held[0] += 1
        stack.append((lo, hi, name))
    loops = {k: v for k, v in loops.items() if v[1].startswith("while")}
    closes = sum(v[0] for v in loops.values())
    total = sum(hi - lo for lo, hi in loops) * 1e-9
    return (total, closes) if closes and total > 0 else None
