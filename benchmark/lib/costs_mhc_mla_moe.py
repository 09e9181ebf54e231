"""Needed bytes of a decoder with several residual streams mixed by
hyper-connections around latent attention and routed experts (Xing4.0),
computed from shapes at PUBLISHED row widths: the counts behind
``mhc_moe_decode_hbm_pct`` and ``tools/profile_mhc_mix.py``.

"Needed" as in ``lib/costs.py``: what a perfect implementation has to move
once. A rope key is ``qk_rope_head_dim`` values (64) though the pool holds
128 lanes of it; of the routed experts a step needs those it HIT among the
experts held here (the program's counter); the router is float32 over ALL
its outputs (``router_outputs``: the file's ``n_routed_experts`` counts the
experts held); the query projection is low-rank (``q_lora_rank``), which
``lib/costs_mla_moe.attention_params`` does not know; the mixing's leaves are
float32. A lower bound on what any implementation moves: a share over 100 %
means the count is wrong.
"""

from __future__ import annotations

from benchmark.lib.costs_mla_moe import (expert_params, expert_product_bytes,
                                         latent_attention_bytes)


def n_coefficients(n: int) -> int:
    """Coefficients a token a sub-layer: n in, n out, n x n between."""
    return n * n + 2 * n


def mix_leaves_bytes(*, hidden: int, n: int) -> int:
    """One sub-layer's float32 leaves: phi, b, alpha."""
    k = n_coefficients(n)
    return 4 * (n * hidden * k + k + 3)


def mix_bytes(*, tokens: float, hidden: int, n: int, sub_layers: int,
              stream_bytes: int = 2) -> float:
    """Bytes the mixing of ``sub_layers`` sub-layers has to move for
    ``tokens`` tokens: before a sub-layer the n streams read once, its
    input written and the coefficients written (float32); after it the
    streams and its output read, the coefficients read and the n streams
    written — (3 n + 2) rows of ``hidden`` a token — plus the leaves."""
    k = n_coefficients(n)
    pre = tokens * (n * hidden * stream_bytes + hidden * stream_bytes + 4 * k)
    post = tokens * ((2 * n + 1) * hidden * stream_bytes + 4 * k)
    return sub_layers * (pre + post + mix_leaves_bytes(hidden=hidden, n=n))


def attention_params(*, hidden: int, heads: int, nope: int, rope: int, v: int,
                     kv_lora: int, q_lora) -> int:
    """Matrix parameters of one latent-attention block with a query latent:
    ``q_a_proj`` (hidden x q_lora) and ``q_b_proj`` (q_lora x heads (nope +
    rope)) — or one ``q_proj`` without —, ``kv_a_proj_with_mqa``,
    ``kv_b_proj`` and ``o_proj``."""
    q = (hidden * q_lora + q_lora * heads * (nope + rope) if q_lora
         else hidden * heads * (nope + rope))
    return (q + hidden * (kv_lora + rope) + kv_lora * heads * (nope + v)
            + heads * v * hidden)


def decode_step_bytes(*, hidden: int, layers: int, dense_layers: int,
                      heads: int, nope: int, rope: int, v: int, kv_lora: int,
                      q_lora, dense_width: int, moe_width: int,
                      router_outputs: int, shared: int, vocab: int, n: int,
                      experts_hit: float, kv_tokens: float, lanes: float,
                      weight_bytes: int = 2, kv_bytes: int = 2,
                      router_bytes: int = 4) -> float:
    """Bytes one chip has to read (and, for the streams, write) for one
    decode step: the output head; per layer its attention's matrices and
    both sub-layers' mixing (leaves, and the lanes' streams); per leading
    dense layer its SwiGLU; per expert layer the router (float32, every
    output), the shared experts and the ``experts_hit`` held experts the
    step touched (mean a layer); and the latent rows and rope keys of the
    ``kv_tokens`` attended over, at their published widths."""
    attn = attention_params(hidden=hidden, heads=heads, nope=nope, rope=rope,
                            v=v, kv_lora=kv_lora, q_lora=q_lora) * weight_bytes
    expert_layers = layers - dense_layers
    dense = dense_layers * expert_params(hidden=hidden, width=dense_width) * weight_bytes
    sparse = expert_layers * hidden * router_outputs * router_bytes \
        + expert_product_bytes(hidden=hidden, moe_width=moe_width, shared=shared,
                               experts_hit=experts_hit,
                               expert_layers=expert_layers,
                               weight_bytes=weight_bytes)
    cache = kv_tokens * layers * (kv_lora + rope) * kv_bytes
    mixing = mix_bytes(tokens=lanes, hidden=hidden, n=n, sub_layers=2 * layers)
    return (hidden * vocab * weight_bytes + layers * attn + dense + sparse
            + cache + mixing)


def sizes_of(view) -> dict:
    """The keyword sizes above from a cell's published keys as run; None
    where the file states no streams."""
    s = view.sizes
    if int(s.get("hc_mult") or 1) <= 1:
        return None
    return dict(hidden=s["hidden_size"], layers=s["num_hidden_layers"],
                dense_layers=s["first_k_dense_replace"],
                heads=s["num_attention_heads"], nope=s["qk_nope_head_dim"],
                rope=s["qk_rope_head_dim"], v=s["v_head_dim"],
                kv_lora=s["kv_lora_rank"], q_lora=s.get("q_lora_rank"),
                dense_width=s["intermediate_size"],
                moe_width=s["moe_intermediate_size"],
                router_outputs=s.get("router_outputs", s["n_routed_experts"]),
                shared=s["n_shared_experts"], vocab=s["vocab_size"],
                n=int(s["hc_mult"]))


__all__ = ["attention_params", "decode_step_bytes", "latent_attention_bytes",
           "mix_bytes", "mix_leaves_bytes", "n_coefficients", "sizes_of"]
