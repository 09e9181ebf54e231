"""Needed bytes of a latent-attention (MLA) decoder with routed experts,
computed from shapes: the counts behind ``moe_decode_hbm_pct``,
``moe_expert_hbm_pct`` and ``mla_attn_hbm_pct``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
once — bf16 weights (the router float32, as it is placed), of the ROUTED
experts only those a step actually hit (the program's counter, not all of
them), and the latent rows of the tokens attended over, read once for all
heads. A lower bound on what any implementation moves: a share over 100 %
means the count is wrong. ``lib/costs.decode_step_bytes`` counts a dense
GQA layer and is wrong for this model.
"""

from __future__ import annotations


def attention_params(*, hidden: int, heads: int, nope: int, rope: int, v: int,
                     kv_lora: int) -> int:
    """Matrix parameters of one latent-attention block: ``q_proj`` (hidden x
    heads (nope + rope)), ``kv_a_proj_with_mqa`` (hidden x (kv_lora +
    rope)), ``kv_b_proj`` (kv_lora x heads (nope + v)) and ``o_proj``
    (heads v x hidden). Norm scales are vectors and not counted."""
    return (hidden * heads * (nope + rope) + hidden * (kv_lora + rope)
            + kv_lora * heads * (nope + v) + heads * v * hidden)


def expert_params(*, hidden: int, width: int) -> int:
    """One SwiGLU of that width: gate, up and down."""
    return 3 * hidden * width


def expert_product_bytes(*, hidden: int, moe_width: int, shared: int,
                         experts_hit: float, expert_layers: int,
                         weight_bytes: int = 2) -> float:
    """Bytes the routed + shared expert products of ONE step have to read:
    per expert layer the experts hit (mean a layer) and the shared experts,
    each three ``hidden x moe_width`` matrices."""
    one = expert_params(hidden=hidden, width=moe_width) * weight_bytes
    return expert_layers * (experts_hit + shared) * one


def latent_attention_bytes(*, heads: int, kv_lora: int, rope: int,
                           kv_tokens: float, queries: float, layers: int,
                           value_bytes: int = 2) -> float:
    """Bytes the latent paged attention of ONE step has to move over all
    layers: the latent row and rope key of every attended token
    (``kv_tokens``: context lengths summed over the rows) read ONCE for all
    heads, plus each query's per-head latent and rope parts in and its
    per-head latent output back (``queries``: query positions in the step:
    the busy lanes of a decode step)."""
    row = (kv_lora + rope) * value_bytes
    per_query = heads * ((kv_lora + rope) + kv_lora) * value_bytes
    return layers * (kv_tokens * row + queries * per_query)


def decode_step_bytes(*, hidden: int, layers: int, dense_layers: int,
                      heads: int, nope: int, rope: int, v: int, kv_lora: int,
                      dense_width: int, moe_width: int, experts: int,
                      shared: int, vocab: int, experts_hit: float,
                      kv_tokens: float, weight_bytes: int = 2,
                      kv_bytes: int = 2, router_bytes: int = 4) -> float:
    """Bytes one chip has to read for one lockstep decode step: the output
    head; per leading dense layer its attention and its dense SwiGLU; per
    expert layer its attention, the router (float32), the shared experts
    and the ``experts_hit`` routed experts the step touched (mean a layer);
    and the latent rows of the ``kv_tokens`` attended over. The embedding
    table is read one row a token: not counted."""
    attn = attention_params(hidden=hidden, heads=heads, nope=nope, rope=rope,
                            v=v, kv_lora=kv_lora) * weight_bytes
    dense = dense_layers * (
        attn + expert_params(hidden=hidden, width=dense_width) * weight_bytes)
    expert_layers = layers - dense_layers
    sparse = expert_layers * (attn + hidden * experts * router_bytes) \
        + expert_product_bytes(hidden=hidden, moe_width=moe_width,
                               shared=shared, experts_hit=experts_hit,
                               expert_layers=expert_layers,
                               weight_bytes=weight_bytes)
    cache = kv_tokens * layers * (kv_lora + rope) * kv_bytes
    return hidden * vocab * weight_bytes + dense + sparse + cache


def expected_experts_hit(*, experts: int, top_k: int, tokens: int) -> float:
    """Distinct experts a layer that ``tokens`` tokens hit under uniform
    routing: ``E (1 - (1 - k/E)^tokens)``."""
    return experts * (1.0 - (1.0 - top_k / experts) ** tokens)


def sizes_of(view) -> dict:
    """The keyword sizes above from a cell's published keys as run."""
    s = view.sizes
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                dense_layers=s["first_k_dense_replace"],
                heads=s["num_attention_heads"], nope=s["qk_nope_head_dim"],
                rope=s["qk_rope_head_dim"], v=s["v_head_dim"],
                kv_lora=s["kv_lora_rank"], dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"],
                experts=s["n_routed_experts"], shared=s["n_shared_experts"],
                vocab=s["vocab_size"])


# -- what the readers share -----------------------------------------------------


def decode_routing(view):
    """(mean distinct experts hit a layer, mean largest load, mean (token,
    expert) pairs a layer) over the window's decode steps, from the
    program's counters; None where the program has none."""
    hit_sum, steps = view.hist("arkflow_gen_moe_experts_hit", kind="decode")
    load_sum, _ = view.hist("arkflow_gen_moe_max_load", kind="decode")
    pairs = view.counter("arkflow_gen_moe_assignments_total", kind="decode")
    s = view.sizes
    expert_layers = s.get("num_hidden_layers", 0) - s.get("first_k_dense_replace", 0)
    if steps <= 0 or expert_layers <= 0:
        return None
    return hit_sum / steps, load_sum / steps, pairs / steps / expert_layers


def kernel_ms_per_decode(view, op_re: str):
    """Device ms a ``_decode`` execution spends in the ops matching
    ``op_re`` (device 0 of the trace); None where there is no such op."""
    from benchmark.lib.xtrace import ops_inside

    t = view.trace
    if not t or "first_device" not in t:
        return None
    dev = t["first_device"]
    total, steps = ops_inside(dev["ops"], dev["modules"], r"jit__decode", op_re)
    return None if not steps or total <= 0 else total / steps * 1e3


def decode_context(view):
    """(mean busy lanes, attended tokens summed over them) of a decode step:
    the gauge's mean times the mix's mean prompt plus half of
    ``max_new_tokens`` (the rule of ``decode_hbm_pct``)."""
    busy = view.gauge("arkflow_gen_slots_busy")
    if not busy:
        return None
    lanes = sum(busy) / len(busy)
    context = float(view.run.pool.tokens.mean()) + view.proc_cfg["max_new_tokens"] / 2
    return lanes, lanes * context
