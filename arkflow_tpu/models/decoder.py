"""Decoder-only LM (Llama-style): GQA + RoPE + RMSNorm + SwiGLU.

BASELINE.json config 5 (Kafka CDC -> batched summarization -> NATS) and the
framework's multi-chip flagship: parameters carry tensor-parallel
PartitionSpecs, activations carry (dp, sp) sharding constraints, and the full
training step (loss + adamw update) jits over an arbitrary
``Mesh(dp, tp, sp)`` — GSPMD inserts the ICI collectives. Long-context
attention can also run as an explicit ring over the ``sp`` axis
(arkflow_tpu.parallel.ring_attention) when sequence length exceeds one chip's
HBM.

Defaults are a small test shape; ``llama3_8b()`` gives the production shape.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from arkflow_tpu.models import common as cm
from arkflow_tpu.models.registry import ModelFamily, register_model


FULL, SLIDING = "full_attention", "sliding_attention"
#: a layer whose mixer is a gated short convolution (``short_conv``): no
#: attention weights, no keys or values, a per-slot window of its last
#: ``conv_L_cache - 1`` gated inputs instead
CONV = "conv"
#: a layer whose mixer is a Gated DeltaNet (``gated_delta_net``) or — under a
#: ``linear_attn_config`` — Kimi Delta Attention: linear attention by the
#: gated delta rule — no keys or values by token, a float32 matrix state a
#: value head and a short conv window a SEQUENCE instead
LINEAR = "linear_attention"
#: the kinds of a model whose blocks hold ONE mixer each and nothing after it
#: (``nemotron_h``; ``hybrid_override_pattern`` spells them ``M``, ``E``,
#: ``*``): a Mamba-2 layer (no keys or values, a float32 state and a conv
#: window a SEQUENCE), a routed-expert layer and — ``full_attention`` — an
#: attention layer with no MLP behind it. The family's fourth, ``-`` (a dense
#: MLP block), is no kind here: the pattern's check refuses it by name
MAMBA, MOE = "mamba", "moe"
_PATTERN_KINDS = {"M": MAMBA, "E": MOE, "*": FULL}


@dataclass(frozen=True)
class AttnSpec:
    """What one kind of latent-attention layer is made of (``DecoderConfig.
    attn``): a full layer reads the model's own keys, a sliding layer its
    ``swa_*`` keys. ``window`` > 0 bounds the keys below (the last
    ``window`` positions, the query's own included); ``index_topk`` > 0
    selects them by the layer's indexer."""
    kind: str
    dim: int
    norm_eps: float
    heads: int
    q_lora_rank: Optional[int]
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    gate: bool = False
    rescale: bool = False
    window: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    #: ``DecoderConfig.rope_scaling`` (YaRN's keys as sorted pairs) or None
    rope_scaling: Optional[tuple] = None
    #: false (``mla_use_nope``): the shared key and the queries' rope part
    #: are left as projected — no position reaches the layer
    rotate: bool = True

    @property
    def softmax_scale(self) -> float:
        """What the scores are multiplied by before the softmax: ``(nope +
        rope)^-0.5``, times YaRN's ``g(mscale_all_dim)^2`` under a scaling."""
        scale = float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is None:
            return scale
        return scale * yarn_softmax_mult(self.rope_scaling)


@dataclass(frozen=True)
class GqaSpec:
    """What one kind of per-head K/V (GQA) layer is made of (``DecoderConfig.
    gqa``): a full layer reads the model's own keys, a sliding layer its
    ``swa_*`` keys where the model states any. Keys and queries are ``dk``
    wide, values ``dv``; the first ``rotary`` values of a key are rotated at
    base ``rope_theta`` (0: none); ``sink``: a learned logit a query head
    joins the softmax's denominator and adds no value."""
    kind: str
    kv_heads: int
    dk: int
    dv: int
    rope_theta: float
    rotary: int
    window: int = 0
    sink: bool = False

    @property
    def shape(self) -> tuple:
        """What decides the shape of the layer's parameter tree."""
        return self.kv_heads, self.dk, self.dv, self.sink

    @property
    def key_parts(self) -> int:
        """Lane-dense parts the page pools hold a key in: a key wider than
        128 lanes and no multiple of them is held as parts of 128 (192: its
        first 128 values, then the other 64 and 64 zeros), each part a
        layer of the pool's own (``paged_decode.CachePool``), so that the
        attention kernel's own walk can copy its pages on a chip
        (``ops/ragged_attention``: a copy out of a pool takes whole
        128-lane rows, and a [.., 4 heads, 256] pool is re-laid for every
        call). Any other key is held as it is, in one part: a narrow head
        padded would double its pool."""
        return 1 if self.dk <= 128 or self.dk % 128 == 0 else -(-self.dk // 128)

    @property
    def dk_held(self) -> int:
        """A key's width as the page pools hold it, its padding included."""
        return self.dk if self.key_parts == 1 else self.key_parts * 128

    @property
    def split_heads(self) -> bool:
        """True where the page pools hold each K/V head in a LAYER of its
        own, [kv_heads * layers, pages, page, 1, width] (head ``j`` of layer
        ``l`` at ``j * layers + l``), and the attention kernel is called a
        K/V head at a time: keys or values of more than one whole 128-lane
        run on 2 to 7 K/V heads. A pool [.., 2 or 4 heads, 256] is re-laid
        WHOLE for every call of the kernel on a chip (the call views a page
        as [page * heads, width] rows, and XLA pads a page's (heads, width)
        tile to 8 rows: two pool-sized temporaries a call, PERF.md PR 49);
        pools [.., 1, 256] and [.., 8, 256] are viewed in place. A layer
        that keeps every key only (a window pool's ring has the one layout)."""
        return (self.key_parts == 1 and max(self.dk, self.dv) > 128
                and 1 < self.kv_heads < 8 and not self.window)

    @property
    def row_major(self) -> bool:
        """True where the page pools hold a token's heads side by side,
        [.., kv_heads * width], and not on an axis of their own: a head
        narrower than 128 lanes, keys and values alike and no sink — what
        the attention kernel's narrow-head walk reads
        (``ops/ragged_attention._narrow_kernel``; a pool [.., kv_heads,
        width < 128] cannot be copied a page at a time on a chip)."""
        return self.dk == self.dv < 128 and not self.sink


@dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int = 2048
    dim: int = 256
    layers: int = 4
    heads: int = 8
    kv_heads: int = 4
    ffn: int = 688
    max_seq: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    #: route attention through the explicit sp-ring (long context): requires a
    #: mesh with an "sp" axis passed to forward/train_step
    use_ring_attention: bool = False
    #: >1 turns the MLP into a switch-style top-1 MoE; experts shard over the
    #: "ep" mesh axis via capacity-based dispatch/combine einsums (GSPMD turns
    #: the expert dim into true expert parallelism). Tokens beyond an expert's
    #: capacity are dropped (standard Switch behavior).
    num_experts: int = 0
    #: expert capacity = ceil(tokens / num_experts * capacity_factor)
    capacity_factor: float = 1.25
    #: Switch load-balance aux loss weight (alpha); without it top-1 routing
    #: collapses onto one expert and capacity overflow zeroes most tokens
    router_aux_weight: float = 0.01
    #: router z-loss weight (penalizes large router logits for stability)
    router_z_weight: float = 1e-3
    #: rematerialize each layer in the backward pass (jax.checkpoint): trades
    #: FLOPs for HBM so long-context training fits (activations are O(layers)
    #: otherwise)
    remat: bool = False
    # -- latent attention (MLA, the DeepSeek-V2/V3 layout), under the
    # published key names. ``kv_lora_rank`` > 0 turns it on: per token one
    # normed latent row of that width and one rotated rope key of
    # ``qk_rope_head_dim`` are cached for ALL heads; heads carry their own
    # sizes (q/k ``qk_nope_head_dim + qk_rope_head_dim``, v ``v_head_dim``),
    # not ``dim // heads``, and ``kv_heads`` is unused.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    #: a low-rank query: ``cq = RMSNorm(y W_qa)`` of this width, then
    #: ``q = cq W_qb``; None is a plain ``dim -> heads * qk`` product
    q_lora_rank: Optional[int] = None
    #: rotate the pairs (2i, 2i+1) instead of (i, i + d/2): what a latent
    #: model does, and only a latent model (any other value raises)
    rope_interleave: bool = False
    # -- dropless top-k routed experts with shared experts (DeepSeek-V3
    # routing). ``n_routed_experts`` > 0 turns it on for every layer from
    # ``first_k_dense_replace`` on (the leading layers stay dense SwiGLU of
    # width ``ffn``): scores ``sigmoid(x W_r)`` in float32, the top
    # ``num_experts_per_tok`` of ``score + selection bias`` are chosen, the
    # chosen experts are weighed by their UNBIASED scores, normalised to sum
    # 1, times ``routed_scaling_factor``, and ``n_shared_experts`` experts
    # of the same width see every token. No capacity: nothing is dropped at
    # any load. Latent attention and routed experts are served together
    # only (the one published layout that combines them), after at least
    # one leading dense layer; a per-head K/V model may route EVERY layer
    # (``first_k_dense_replace`` 0: no dense stack at all).
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    moe_intermediate_size: int = 0
    first_k_dense_replace: int = 0
    routed_scaling_factor: float = 1.0
    #: only what the routing above states is implemented; other values of
    #: these five raise ConfigError. ``scoring_func`` "softmax" with
    #: ``topk_method`` "greedy" is the other router served: scores
    #: ``softmax(x W_r)`` over all the experts, the largest chosen with NO
    #: selection bias (the layer has no ``router_bias`` leaf), weighed by
    #: their scores over the chosen ones' sum
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    #: the experts this chip holds of every expert layer, ``(first, count)``
    #: (expert parallelism's share): the router keeps ``n_routed_experts``
    #: outputs and ``num_experts_per_tok`` choices, weights are normalised
    #: over all the chosen, and the layer computes its own experts' part of
    #: the result; what absent experts would add is left out. None: all
    experts_held: Optional[tuple] = None
    # -- a layer pattern over latent layers, under the published key names.
    # ``layer_types`` names each layer ``full_attention`` or
    # ``sliding_attention`` (the first ``layers`` entries are read; None: all
    # full). A sliding layer attends the last ``sliding_window`` positions,
    # itself included, and has sizes of its own (``swa_*``; its rope key
    # width may differ too); a full layer with ``index_topk`` > 0 attends the
    # ``index_topk`` positions its indexer scores highest (``index_n_heads``
    # heads of ``index_head_dim``, one index key a token cached beside the
    # latent row). ``attention_gate_type`` "headwise" scales each head's
    # output by ``sigmoid(y W_g)``; ``apply_mla_qkv_lora_rescale`` scales the
    # normed query / key-value latents by ``sqrt(dim / rank)``.
    layer_types: Optional[tuple] = None
    sliding_window: int = 0
    swa_heads: int = 0
    swa_q_lora_rank: Optional[int] = None
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    swa_attention_gate_type: str = ""
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    attention_gate_type: str = ""
    apply_mla_qkv_lora_rescale: bool = False
    #: the size of an attention head (its queries and keys) where it is not
    #: ``dim // heads`` (per-head K/V layers only; 0: ``dim // heads``)
    head_dim: int = 0
    # -- a layer pattern over per-head K/V (GQA) layers: ``layer_types`` and
    # ``sliding_window`` as above, every layer at the model's one set of
    # sizes unless the per-kind keys below state another (``gqa``). A
    # sliding layer attends the last ``sliding_window`` positions
    # and caches only those (``paged_decode.cache_spec``: ``kv_window``
    # beside ``kv``). ``qk_norm``: an RMSNorm over each head's query and key
    # (scales of ``head_dim``, one set for queries and one for keys), before
    # the rotary embedding. ``full_attention_rope`` false: a full layer's
    # queries and keys are NOT rotated (positions reach it through the
    # sliding layers alone). Such a model may route its experts too
    # (``n_routed_experts``: a leading dense stack, then an expert stack).
    qk_norm: bool = False
    full_attention_rope: bool = True
    # -- per-head layers whose sizes go by kind, under the published key
    # names (``gqa(kind)`` states them): a sliding layer has
    # ``swa_kv_heads`` K/V heads and rotates at ``swa_rope_theta`` (0: the
    # model's own); a value head is ``v_head_dim`` wide (``swa_v_head_dim``
    # on a sliding layer; 0: the key's ``head_dim``); only the first
    # ``int(head_dim * partial_rotary_factor)`` values of a query and key
    # head are rotated; values are scaled by ``attention_value_scale``; a
    # kind with its sink flag set has one learned float32 logit a query
    # head (``attn_sink``) that joins the softmax and adds no value. Kinds
    # of different shapes stack apart (``layer_runs``).
    swa_kv_heads: int = 0
    partial_rotary_factor: float = 1.0
    attention_value_scale: float = 1.0
    add_swa_attention_sink_bias: bool = False
    add_full_attention_sink_bias: bool = False
    # -- the hybrid block (Falcon-H1), under the published key names.
    # ``mamba_d_ssm`` > 0 turns it on for EVERY layer: beside the GQA
    # attention, and fed by the same normed input, a Mamba-2 mixer
    # (``mamba_n_heads`` heads of ``mamba_d_head``, state ``mamba_d_state``,
    # B and C shared by ``mamba_n_groups`` groups of heads, a depthwise
    # causal conv over ``mamba_d_conv`` inputs, the gate before a grouped
    # RMSNorm); their outputs add into one residual. What a sequence caches
    # of it is a fixed-size state (``paged_decode.cache_spec``: kind
    # ``ssm``), not rows by token. The multipliers scale, in this order of
    # use: the embedding; the block's input into attention, its keys, its
    # output; the input into the mixer, the five segments z | x | B | C | dt
    # of its input projection, its output; the MLP's gate and output; the
    # logits.
    mamba_d_ssm: int = 0
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0, 1.0, 1.0, 1.0, 1.0)
    mlp_multipliers: tuple = (1.0, 1.0)
    lm_head_multiplier: float = 1.0
    # -- gated short-convolution layers among per-head K/V layers (LFM2),
    # under the published key names. ``layer_types`` names a layer ``conv``:
    # its mixer is ``[B | C | u] = y W_in``, a depthwise causal conv of
    # ``conv_L_cache`` taps over ``B * u`` (no activation, no bias:
    # ``conv_bias`` true is refused until a source states it), times ``C``,
    # then ``W_out`` — no attention weights and no keys or values: what a
    # SEQUENCE caches of it is its last ``conv_L_cache - 1`` gated inputs
    # (``paged_decode.cache_spec``: kind ``conv``, a row a slot). The
    # leading layers with a dense SwiGLU (this family's ``num_dense_layers``)
    # are ``first_k_dense_replace``; they may be conv layers.
    # ``norm_topk_eps`` is what the routed weights' normalisation adds to
    # their sum (this family: 1e-6).
    conv_L_cache: int = 0
    conv_bias: bool = False
    norm_topk_eps: float = 1e-20
    #: > 0: the routed layers' selection bias is SEEDED normal(0, this) and
    #: not uniform within +-0.01: wide enough beside the scores' own spread
    #: (~0.13 under a random router) that it decides choices and load, as a
    #: trained balancing bias does (``_init_ffn``)
    router_bias_std: float = 0.0
    #: the embedding table is SEEDED normal(0, this). At the usual 0.02 a
    #: seeded model's residual is soon its sub-layers' outputs (~0.1 a value),
    #: and under random weights attention is near uniform, so those are the
    #: context's pooled mean, the same for every token: by the tenth layer
    #: 50-85 % of a router's input is common to a chunk's tokens and half the
    #: experts are never chosen. At 0.5 a token's own embedding decides its
    #: routing, as a trained model's peaked attention would, and every expert
    #: is hit (PERF.md section 6, PR 53)
    embed_init_std: float = 0.02
    # -- Gated DeltaNet layers among gated per-head K/V layers (Qwen3-Next),
    # under the published key names. ``layer_types`` names a layer
    # ``linear_attention``: its mixer (``gated_delta_net``) has
    # ``linear_num_key_heads`` query / key heads of ``linear_key_head_dim``,
    # each serving ``linear_num_value_heads / linear_num_key_heads`` value
    # heads of ``linear_value_head_dim``, behind a depthwise causal conv of
    # ``linear_conv_kernel_dim`` taps and SiLU; what a SEQUENCE caches of it
    # is a float32 state [value heads, key dim, value dim] and the conv's
    # last ``linear_conv_kernel_dim - 1`` inputs (``paged_decode.cache_spec``:
    # kind ``gdn``). ``attention_gate_type`` "elementwise" on a per-head K/V
    # model: an attention layer's output times ``sigmoid(y W_g)``, value by
    # value, before ``o_proj``. ``norm_unit_offset``: every RMSNorm scale over
    # the stream and over an attention head is held as an offset from one
    # (``x_hat * (1 + w)``; the mixer's gated norm keeps a plain scale).
    # ``shared_expert_gate``: the shared experts' output times
    # ``sigmoid(y w_sg)``, one float32 logit a token.
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 0
    norm_unit_offset: bool = False
    shared_expert_gate: bool = False
    # -- Kimi Delta Attention layers among position-free latent layers (Kimi
    # Linear, arXiv:2510.26692), under the published key names. The published
    # ``linear_attn_config`` mapping (``num_heads``, ``head_dim``,
    # ``short_conv_kernel_size``, and ``kda_layers`` / ``full_attn_layers``,
    # both 1-based) PRESENT is what says that a ``linear_attention`` layer is
    # a KDA layer and not a Gated DeltaNet (``linear_num_*``): q, k and v each
    # projected to ``num_heads x head_dim`` behind a depthwise causal conv of
    # its own and SiLU, a log-decay a head AND key channel from a low-rank
    # projection (``kda_fa`` -> ``kda_fb``, + ``dt_bias``), an output gate from
    # another (``kda_ga`` -> ``kda_gb``) under a sigmoid inside a per-head
    # RMSNorm; what a SEQUENCE caches of it is a float32 state [heads, head
    # dim, head dim] and the convs' last ``short_conv_kernel_size - 1``
    # projected inputs (``paged_decode.cache_spec``: kind ``kda``). Held as
    # sorted (key, value) pairs. ``mla_use_nope``: a latent layer rotates
    # NOTHING — its ``qk_rope_head_dim`` wide shared key and the queries'
    # part that meets it are cached and scored as projected (positions reach
    # the model through the KDA layers alone; ``rope_theta`` is then unread).
    linear_attn_config: Optional[tuple] = None
    mla_use_nope: bool = False
    # -- several residual streams mixed by manifold-constrained
    # hyper-connections (mHC, arXiv:2512.24880; Xing4.0), under the published
    # key names. ``hc_mult`` = n > 1: a token's residual is n rows of ``dim``
    # (the embedding copied n times; summed before the final norm), and each
    # SUB-layer (attention, then the MLP half) has float32 leaves of its own
    # (``mhc_attn`` / ``mhc_mlp``: ``phi`` [n * dim, n * n + 2 n], ``b``,
    # ``alpha`` [3]) that give, a token, the weights its input is read with
    # (``sigmoid``), the weights its output is written back with (``2
    # sigmoid``) and an n x n mixing of the streams: ``exp`` of logits clamped
    # to ``mhc_h_res_clamp_min/max``, then ``hc_sinkhorn_iters`` rounds of
    # column and row normalisation with ``hc_eps`` in the denominators
    # (``ops/mhc_mix``). A latent model of full layers only.
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    #: None, or the published ``rope_scaling`` mapping of ``type`` "yarn"
    #: (DeepSeek-V3's: ``factor``, ``original_max_position_embeddings``,
    #: ``beta_fast``, ``beta_slow``, ``mscale``, ``mscale_all_dim``), held as
    #: sorted (key, value) pairs: the rope's frequencies are interpolated
    #: (``rope_frequencies``) and a latent layer's softmax scale grows by
    #: ``g(mscale_all_dim)^2``. A latent model's full layers only.
    rope_scaling: Optional[tuple] = None
    # -- a compacting window cache (EVA: Zheng et al., ICLR 2023,
    # arXiv:2302.04542; EvaByte), under the published key names.
    # ``attention_class`` "eva" on a per-head K/V model of full layers:
    # positions fall into blocked windows of ``window_size`` tokens and
    # chunks of ``chunk_size``; a query attends, in ONE softmax, the exact
    # keys of its own window up to itself and one SUMMARY row for every chunk
    # of every window before it (``ops/eva_summarise``: the chunk's keys
    # pooled by a softmax over ``dk^-0.5 k . phi``, plus ``mu``; its values
    # by the same weights), ``eva_phi`` / ``eva_mu`` float32 [kv heads, dk] a
    # layer. A key is counted once: exactly inside its query's window,
    # through its chunk outside it. What a sequence caches is its open
    # window's rows and the summaries behind them (``paged_decode.cache_spec``:
    # kind ``eva``, rows that are NOT positions). ``num_pred_heads`` > 1: the
    # output head predicts that many bytes ahead; head 0 is the model's own
    # (``lm_head``), the others are draft heads (``pred_heads``) that the
    # plain forward returns and serving does not read. ``fp32_skip_add``: the
    # residual is carried float32 from the table to the final norm.
    # ``fp32_logits``: the head's product accumulates and stays float32.
    attention_class: str = ""
    window_size: int = 0
    chunk_size: int = 0
    num_pred_heads: int = 1
    fp32_skip_add: bool = False
    fp32_logits: bool = False
    # -- blocks of ONE mixer each (Nemotron-H: ``nemotron_h``), under the
    # published key names. ``hybrid_override_pattern`` spells a kind a layer —
    # ``M`` a Mamba-2 layer, ``E`` a routed-expert layer, ``*`` an attention
    # layer — and is read as the ``layer_types`` ``mamba`` / ``moe`` /
    # ``full_attention`` (either key may state them; the first ``layers``
    # entries are read): block i is ``x + mixer(RMSNorm_i(x))``, one norm
    # (``attn_norm``) and one mixer, nothing after it. The Mamba-2 layer is the
    # hybrid block's mixer at its own sizes (``mamba_n_heads`` heads of
    # ``mamba_d_head``, ``mamba_d_ssm`` their product where it is left 0;
    # ``cache_spec``: pool ``ssm`` over the mamba layers only, ``kv`` over the
    # attention layers only). ``mlp_hidden_act`` "relu2": an expert is TWO
    # matrices, ``relu(x W_up)^2 W_down``, no gate (``ops/moe_experts``:
    # ``moe_expert_relu2``); ``moe_shared_expert_intermediate_size``: the
    # shared expert's width, a multiple of ``moe_intermediate_size`` (a
    # relu-squared expert of width 2 f IS two of width f side by side, and is
    # held so: ``shared_stack``). The attention layer is position-free:
    # ``full_attention_rope`` false says so.
    hybrid_override_pattern: str = ""
    mlp_hidden_act: str = "silu"
    moe_shared_expert_intermediate_size: int = 0

    def __post_init__(self):
        from arkflow_tpu.errors import ConfigError

        for name in ("layer_types", "experts_held", "ssm_multipliers",
                     "mlp_multipliers"):  # JSON lists: hashable
            if isinstance(getattr(self, name), list):
                object.__setattr__(self, name, tuple(getattr(self, name)))
        if isinstance(self.rope_scaling, dict):  # a JSON mapping: hashable
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if isinstance(self.linear_attn_config, dict):
            object.__setattr__(self, "linear_attn_config", tuple(sorted(
                (k, tuple(v) if isinstance(v, list) else v)
                for k, v in self.linear_attn_config.items())))
        self._read_override_pattern()
        self._check_streams()
        self._check_hybrid()
        self._check_eva()
        if self.latent:
            if min(self.qk_nope_head_dim, self.qk_rope_head_dim,
                   self.v_head_dim) <= 0 or self.qk_rope_head_dim % 2:
                raise ConfigError(
                    "kv_lora_rank > 0 (latent attention) needs "
                    "qk_nope_head_dim, v_head_dim and an even "
                    "qk_rope_head_dim")
            if self.q_lora_rank is not None and self.q_lora_rank <= 0:
                raise ConfigError(
                    "q_lora_rank is None (a plain query projection) or the "
                    f"width of the query latent, got {self.q_lora_rank}")
            if self.use_ring_attention or self.num_experts > 1:
                raise ConfigError(
                    "latent attention composes with neither ring attention "
                    "nor the Switch top-1 layer (num_experts)")
        if self.mla_use_nope and (not self.latent or self.rope_interleave
                                  or self.rope_scaling is not None):
            raise ConfigError(
                "mla_use_nope (no rotation anywhere) belongs to a "
                "latent-attention model (kv_lora_rank > 0) and leaves "
                "rope_interleave and rope_scaling nothing to say: remove them")
        if (self.latent != self.rope_interleave and not self.mla_use_nope) or (
                self.latent and not self.routed):
            raise ConfigError(
                "latent attention (kv_lora_rank) is served with top-k routed "
                "experts (n_routed_experts) and rope_interleave, and "
                "rope_interleave with latent attention only")
        if self.routed and (self.num_experts > 1 or self.use_ring_attention):
            raise ConfigError(
                "top-k routed experts (n_routed_experts) compose with "
                "neither the Switch top-1 layer (num_experts) nor ring "
                "attention")
        if self.routed:
            if not (0 < self.num_experts_per_tok <= self.n_routed_experts
                    and self.moe_intermediate_size > 0
                    and (self.first_k_dense_replace > 0 or not self.latent)
                    and 0 <= self.first_k_dense_replace < self.layers):
                raise ConfigError(
                    "n_routed_experts needs 0 < num_experts_per_tok <= "
                    "n_routed_experts, moe_intermediate_size > 0 and "
                    "first_k_dense_replace < layers (a leading dense stack, "
                    "then an expert stack; a latent model has at least one "
                    "dense layer, a per-head K/V model may have none)")
            if (self.scoring_func, self.topk_method) not in (
                    ("sigmoid", "noaux_tc"), ("softmax", "greedy")) or (
                    self.n_group, self.topk_group, self.norm_topk_prob) != (
                    1, 1, True):
                raise ConfigError(
                    "routed experts implement scoring_func sigmoid with "
                    "topk_method noaux_tc (a selection bias) or softmax "
                    "with greedy (none), n_group = topk_group = 1 (no "
                    "group-limited selection) and norm_topk_prob true; got "
                    f"{self.scoring_func!r}, {self.topk_method!r}, "
                    f"{self.n_group}, {self.topk_group}, {self.norm_topk_prob}")
        if self.shared_expert_gate and not (self.routed and self.n_shared_experts):
            raise ConfigError("shared_expert_gate weighs the shared experts "
                              "of a routed model (n_shared_experts > 0)")
        if self.experts_held is not None:
            first, count = (tuple(self.experts_held) + (0, 0))[:2]
            if not (self.routed and len(self.experts_held) == 2
                    and 0 <= first and 0 < count
                    and first + count <= self.n_routed_experts):
                raise ConfigError(
                    "experts_held is (first, count) within n_routed_experts "
                    f"of a routed model, got {self.experts_held}")
        self._check_layer_pattern()

    def _check_eva(self) -> None:
        """``attention_class`` and the keys that belong to "eva"."""
        from arkflow_tpu.errors import ConfigError

        if self.attention_class not in ("", "eva"):
            raise ConfigError(
                f"attention_class {self.attention_class!r} is not served: "
                "only 'eva' (a blocked exact window beside chunk summaries)")
        if not self.eva:
            if (self.window_size or self.chunk_size or self.num_pred_heads != 1
                    or self.fp32_skip_add or self.fp32_logits):
                raise ConfigError(
                    "window_size, chunk_size, num_pred_heads, fp32_skip_add "
                    "and fp32_logits belong to attention_class 'eva'")
            return
        if not (self.chunk_size > 0 and self.window_size > 0
                and self.window_size % self.chunk_size == 0
                and self.num_pred_heads >= 1):
            raise ConfigError(
                "attention_class 'eva' needs chunk_size > 0, a window_size "
                "that is a multiple of it and num_pred_heads >= 1; got "
                f"{self.window_size}, {self.chunk_size}, {self.num_pred_heads}")
        if (self.latent or self.routed or self.num_experts > 1 or self.hybrid
                or self.layer_types is not None or self.qk_norm
                or self.hc_mult > 1 or self.use_ring_attention
                or self.v_head_dim or self.partial_rotary_factor != 1.0
                or self.add_full_attention_sink_bias
                or self.attention_gate_type):
            raise ConfigError(
                "attention_class 'eva' is served on a per-head K/V model of "
                "full layers with a dense SwiGLU: not beside latent attention "
                "(kv_lora_rank), routed experts (n_routed_experts / "
                "num_experts), the hybrid block (mamba_d_ssm), a layer "
                "pattern (layer_types: another window or state kind), "
                "qk_norm, hc_mult, ring attention, v_head_dim, "
                "partial_rotary_factor, a sink or an output gate, yet")

    @property
    def eva(self) -> bool:
        """True where attention is EVA's: a blocked exact window beside
        chunk summaries, over a cache that compacts as windows close."""
        return self.attention_class == "eva"

    def _check_streams(self) -> None:
        """``hc_mult`` and ``rope_scaling``: what they are served beside."""
        from arkflow_tpu.errors import ConfigError

        if self.rope_scaling is not None:
            kind = dict(self.rope_scaling).get("type")
            if kind != "yarn":
                raise ConfigError(
                    f"rope_scaling of type {kind!r} is not served: only "
                    "'yarn' (DeepSeek-V3's frequency interpolation and "
                    "softmax scale) is")
            missing = set(_YARN_KEYS) - set(dict(self.rope_scaling))
            if missing:
                raise ConfigError(
                    f"rope_scaling of type 'yarn' needs {sorted(_YARN_KEYS)}, "
                    f"missing {sorted(missing)}")
            if not self.latent or set(self.kinds) != {FULL} or self.index_topk:
                raise ConfigError(
                    "rope_scaling (yarn) is served on a latent-attention "
                    "model's full layers only: the per-head K/V layers, "
                    "sliding latent layers and the indexer rotate at "
                    "unscaled frequencies and score at d^-0.5")
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 1 \
                or self.mhc_h_res_clamp_min >= self.mhc_h_res_clamp_max:
            raise ConfigError(
                "hc_mult >= 1, hc_sinkhorn_iters >= 1 and "
                "mhc_h_res_clamp_min < mhc_h_res_clamp_max, got "
                f"{self.hc_mult}, {self.hc_sinkhorn_iters}, "
                f"{self.mhc_h_res_clamp_min}, {self.mhc_h_res_clamp_max}")
        if self.hc_mult == 1:
            return
        if not self.latent:
            raise ConfigError(
                f"hc_mult {self.hc_mult} (several residual streams mixed by "
                "hyper-connections) is served with latent attention "
                "(kv_lora_rank > 0) only: the per-head K/V layer loop and "
                "the layers that carry a state (hybrid, conv, "
                "linear_attention) carry ONE residual stream")
        if set(self.kinds) != {FULL} or self.index_topk:
            raise ConfigError(
                f"hc_mult {self.hc_mult} is served over full_attention "
                "latent layers only: sliding_attention, linear_attention and "
                f"indexed layers carry ONE residual stream, got {self.layer_types} "
                f"and index_topk {self.index_topk}")
        if self.remat:
            raise ConfigError(
                "hc_mult > 1 is served, not trained: remat wraps a layer "
                "that carries ONE residual stream")

    @property
    def hc_res_clamp(self) -> tuple:
        """(min, max) the mixing logits are clamped to before the ``exp``."""
        return float(self.mhc_h_res_clamp_min), float(self.mhc_h_res_clamp_max)

    def _check_hybrid(self) -> None:
        from arkflow_tpu.errors import ConfigError

        if self.head_dim < 0 or self.head_dim % 2 or (
                self.head_dim and self.latent):
            raise ConfigError(
                "head_dim is an even attention head size of a per-head K/V "
                f"model (a latent model states its own), got {self.head_dim}")
        if not (self.hybrid or self.mamba):
            if self.mamba_n_heads or self.mamba_d_head or self.mamba_d_state:
                raise ConfigError("mamba_n_heads / mamba_d_head / "
                                  "mamba_d_state without mamba_d_ssm")
            return
        if self.hybrid and (self.latent or self.routed or self.num_experts > 1
                            or self.use_ring_attention):
            raise ConfigError(
                "the hybrid block (mamba_d_ssm > 0: a Mamba-2 mixer beside "
                "per-head GQA attention, dense SwiGLU) composes with "
                "neither latent attention (kv_lora_rank), routed experts "
                "(n_routed_experts), the Switch layer (num_experts) nor "
                "ring attention")
        if (min(self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state,
                self.mamba_n_groups, self.mamba_chunk_size) <= 0
                or self.mamba_d_conv < 2
                or self.mamba_n_heads * self.mamba_d_head != self.mamba_d_ssm
                or self.mamba_n_heads % self.mamba_n_groups
                or self.mamba_d_ssm % self.mamba_n_groups):
            raise ConfigError(
                "mamba_d_ssm = mamba_n_heads x mamba_d_head, with "
                "mamba_d_state, mamba_chunk_size > 0, mamba_d_conv >= 2 and "
                "mamba_n_groups dividing the heads; got "
                f"{self.mamba_d_ssm}, {self.mamba_n_heads}, "
                f"{self.mamba_d_head}, {self.mamba_d_state}, "
                f"{self.mamba_chunk_size}, {self.mamba_d_conv}, "
                f"{self.mamba_n_groups}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ConfigError(
                "ssm_multipliers has five entries (z, x, B, C, dt) and "
                "mlp_multipliers two (gate, output)")

    def _check_layer_pattern(self) -> None:
        from arkflow_tpu.errors import ConfigError

        extras = (self.sliding_window, self.swa_heads, self.swa_kv_lora_rank,
                  self.index_topk, self.index_n_heads, self.index_head_dim)
        gates = (self.attention_gate_type, self.swa_attention_gate_type)
        kinds = self.kinds
        if self.layer_types is not None and (
                len(kinds) != self.layers
                or set(kinds) - {FULL, SLIDING, CONV, LINEAR, MAMBA, MOE}):
            raise ConfigError(
                f"layer_types names each of the {self.layers} layers "
                f"{FULL!r}, {SLIDING!r}, {CONV!r}, {LINEAR!r}, {MAMBA!r} or "
                f"{MOE!r}, got {self.layer_types}")
        self._check_conv()
        self._check_linear()
        self._check_one_mixer()
        if not self.latent:
            if self.attention_gate_type == "elementwise":
                gates = gates[1:]  # a per-head layer's own gate
            if any(extras[1:]) or any(gates) or self.apply_mla_qkv_lora_rescale \
                    or self.swa_q_lora_rank is not None \
                    or self.swa_qk_nope_head_dim or self.swa_qk_rope_head_dim:
                raise ConfigError(
                    "swa_heads, swa_*_lora_rank, swa_qk_*_head_dim, index_*, "
                    "the attention gate and the latent rescale belong to a "
                    "latent-attention model (kv_lora_rank > 0): a per-head "
                    "K/V model's sliding layers differ by swa_kv_heads, "
                    "swa_v_head_dim, swa_rope_theta and their sink alone")
            if (SLIDING in kinds) != (self.sliding_window > 0):
                raise ConfigError(
                    "sliding_window > 0 and a sliding_attention layer in "
                    f"layer_types go together, got {self.sliding_window} "
                    f"and {self.layer_types}")
            if (self.layer_types is not None or self.qk_norm) and (
                    self.hybrid or self.num_experts > 1
                    or self.use_ring_attention):
                raise ConfigError(
                    "a layer pattern over per-head K/V layers (sliding, "
                    "conv or linear_attention layers among them), and "
                    "qk_norm, compose with "
                    "neither the hybrid block (mamba_d_ssm: a Mamba-2 mixer "
                    "beside every layer's attention), the Switch top-1 "
                    "layer (num_experts) nor ring attention")
            self._check_gqa_kinds()
            return
        if (self.swa_kv_heads or self.partial_rotary_factor != 1.0
                or self.attention_value_scale != 1.0
                or self.add_swa_attention_sink_bias
                or self.add_full_attention_sink_bias):
            raise ConfigError(
                "swa_kv_heads, partial_rotary_factor, attention_value_scale "
                "and add_*_attention_sink_bias belong to a per-head K/V "
                "model: a latent row has no K/V heads, rotates its own rope "
                "key whole and has no sink beside it yet")
        if self.qk_norm or not self.full_attention_rope or self.norm_unit_offset:
            raise ConfigError(
                "qk_norm, full_attention_rope and norm_unit_offset belong to "
                "a per-head K/V model (a latent model norms its latents and "
                "rotates its rope key)")
        if any(g not in ("", "headwise") for g in gates):
            raise ConfigError(
                f"attention_gate_type is '' or 'headwise', got {gates}")
        if SLIDING in kinds:
            if (min(self.sliding_window, self.swa_heads, self.swa_kv_lora_rank,
                    self.swa_qk_nope_head_dim, self.swa_qk_rope_head_dim,
                    self.swa_v_head_dim) <= 0 or self.swa_rope_theta <= 0
                    or self.swa_qk_rope_head_dim % 2
                    or (self.swa_q_lora_rank is not None
                        and self.swa_q_lora_rank <= 0)):
                raise ConfigError(
                    "a sliding_attention layer needs sliding_window, "
                    "swa_heads, swa_kv_lora_rank, swa_qk_nope_head_dim, an "
                    "even swa_qk_rope_head_dim, swa_v_head_dim and "
                    "swa_rope_theta (swa_q_lora_rank None or > 0)")
        elif self.sliding_window or self.swa_heads or self.swa_kv_lora_rank:
            raise ConfigError("sliding_window / swa_* without a "
                              "sliding_attention layer in layer_types")
        if self.index_topk:
            if (min(self.index_n_heads, self.index_topk) <= 0
                    or self.index_head_dim < self.qk_rope_head_dim
                    or self.q_lora_rank is None):
                raise ConfigError(
                    "index_topk > 0 needs index_n_heads, index_head_dim >= "
                    "qk_rope_head_dim and q_lora_rank (the indexer's queries "
                    "are projected from the query latent)")
        elif self.index_n_heads or self.index_head_dim:
            raise ConfigError("index_n_heads / index_head_dim without index_topk")

    def _check_conv(self) -> None:
        """The conv kind's keys, and what it is not served beside yet."""
        from arkflow_tpu.errors import ConfigError

        if not self.conv:
            if self.conv_L_cache or self.conv_bias:
                raise ConfigError("conv_L_cache / conv_bias without a conv "
                                  "layer in layer_types")
            return
        if self.conv_bias:
            raise ConfigError("conv_bias: a bias a channel on the conv is "
                              "not served (no source here states one)")
        if self.conv_L_cache < 2:
            raise ConfigError(
                "a conv layer needs conv_L_cache >= 2 (the taps of its "
                f"depthwise causal conv), got {self.conv_L_cache}")
        if self.latent or SLIDING in self.kinds or FULL not in self.kinds:
            raise ConfigError(
                "conv layers are served among full_attention per-head K/V "
                "layers (at least one): beside a latent row (kv_lora_rank) "
                "or a sliding window's pool they are not, yet")

    def _check_linear(self) -> None:
        """The Gated DeltaNet kind's keys, and what it is not served beside
        yet."""
        from arkflow_tpu.errors import ConfigError

        sizes = (self.linear_num_key_heads, self.linear_num_value_heads,
                 self.linear_key_head_dim, self.linear_value_head_dim,
                 self.linear_conv_kernel_dim)
        if self.kda:
            if any(sizes):
                raise ConfigError(
                    "linear_attn_config (Kimi Delta Attention) and "
                    "linear_num_*_heads / linear_*_head_dim (a Gated DeltaNet) "
                    "both say what a linear_attention layer is: state one")
            return self._check_kda()
        if not self.linear:
            if any(sizes):
                raise ConfigError("linear_num_*_heads / linear_*_head_dim / "
                                  "linear_conv_kernel_dim without a "
                                  "linear_attention layer in layer_types")
            return
        if (min(sizes) <= 0 or self.linear_conv_kernel_dim < 2
                or self.linear_num_value_heads % self.linear_num_key_heads):
            raise ConfigError(
                "a linear_attention layer needs linear_num_key_heads dividing "
                "linear_num_value_heads, linear_key_head_dim, "
                "linear_value_head_dim and linear_conv_kernel_dim >= 2; got "
                f"{sizes}")
        if (self.latent or self.hybrid or CONV in self.kinds
                or SLIDING in self.kinds or FULL not in self.kinds):
            raise ConfigError(
                "linear_attention layers (pool gdn: a matrix state a "
                "sequence) are served among full_attention per-head K/V "
                "layers (at least one): beside a latent row (kv_lora_rank), "
                "a sliding window's pool (kv_window), conv layers (pool "
                "conv) or the hybrid block (pool ssm) they are not, yet")

    def _read_override_pattern(self) -> None:
        """``hybrid_override_pattern`` read as ``layer_types``, and
        ``mamba_d_ssm`` as the mamba layers' heads x head size where it is
        left out (``nemotron_h`` publishes neither ``layer_types`` nor it)."""
        from arkflow_tpu.errors import ConfigError

        pattern = self.hybrid_override_pattern
        if pattern:
            if set(pattern) - set(_PATTERN_KINDS) or len(pattern) < self.layers:
                raise ConfigError(
                    "hybrid_override_pattern spells a kind a layer — M (a "
                    "Mamba-2 layer), E (a routed-expert layer), * (an "
                    f"attention layer) — for at least the {self.layers} "
                    f"layers, got {pattern!r}; '-', the family's dense MLP "
                    "block, is not served yet (no source here states a model "
                    "with one: Nemotron-3-Nano's pattern has none)")
            spelled = tuple(_PATTERN_KINDS[c] for c in pattern[:self.layers])
            if self.layer_types is not None and self.kinds != spelled:
                raise ConfigError(
                    "hybrid_override_pattern and layer_types both name the "
                    f"layers and disagree: {pattern[:self.layers]!r} against "
                    f"{self.kinds}; state one")
            object.__setattr__(self, "layer_types", spelled)
        if self.mamba and not self.mamba_d_ssm:
            object.__setattr__(self, "mamba_d_ssm",
                               self.mamba_n_heads * self.mamba_d_head)

    def _check_one_mixer(self) -> None:
        """Blocks of one mixer each (``mamba`` / ``moe`` layers): their keys,
        and what they are not served beside yet."""
        from arkflow_tpu.errors import ConfigError

        if not self.one_mixer:
            if (self.mlp_hidden_act != "silu"
                    or self.moe_shared_expert_intermediate_size):
                raise ConfigError(
                    "mlp_hidden_act (other than 'silu') and "
                    "moe_shared_expert_intermediate_size belong to a model of "
                    "one-mixer blocks (mamba / moe layers in layer_types or "
                    "hybrid_override_pattern): every other model's MLPs and "
                    "experts are SwiGLU, its shared experts of "
                    "moe_intermediate_size")
            return
        if (self.latent or self.num_experts > 1 or self.use_ring_attention
                or self.hc_mult > 1 or self.eva
                or set(self.kinds) - {MAMBA, MOE, FULL}
                or not {MAMBA, FULL} <= set(self.kinds)):
            raise ConfigError(
                "mamba and moe layers (blocks of one mixer each) are served "
                "among each other and full_attention per-head K/V layers (at "
                "least one mamba layer, which carries order, and one "
                "full_attention layer, which the page pools are built for): "
                "not beside latent attention (kv_lora_rank), sliding_attention, "
                "conv or linear_attention layers, the Switch layer "
                "(num_experts), ring attention, hc_mult or attention_class "
                "'eva', yet")
        if (MOE in self.kinds) != self.routed or self.first_k_dense_replace:
            raise ConfigError(
                "a moe layer and n_routed_experts > 0 go together, with "
                "first_k_dense_replace 0: where the blocks hold one mixer "
                "each, layer_types says which layers route, and no layer "
                f"has an MLP behind its mixer; got {self.n_routed_experts} "
                f"experts, first_k_dense_replace {self.first_k_dense_replace} "
                f"and {self.layer_types}")
        if self.mlp_hidden_act != "relu2":
            raise ConfigError(
                "a moe layer's experts are two matrices, relu(x W_up)^2 "
                "W_down (mlp_hidden_act 'relu2'): a gated expert behind a "
                f"one-mixer block is not served, got {self.mlp_hidden_act!r}")
        shared, f = self.moe_shared_expert_intermediate_size, self.moe_intermediate_size
        if self.routed and (shared < 0 or shared % f or bool(shared) != bool(
                self.n_shared_experts)):
            raise ConfigError(
                "moe_shared_expert_intermediate_size is the shared experts' "
                "width, a multiple of moe_intermediate_size (a relu-squared "
                "expert of width 2 f is held as two of width f), and 0 with "
                f"n_shared_experts 0; got {shared} beside {f} and "
                f"{self.n_shared_experts} shared experts")
        if (self.scoring_func, self.shared_expert_gate) != ("sigmoid", False):
            raise ConfigError(
                "a moe layer routes by sigmoid scores with a selection bias "
                "(scoring_func sigmoid, topk_method noaux_tc) and adds its "
                "shared expert ungated (shared_expert_gate false)")
        if self.full_attention_rope or self.qk_norm or self.hetero \
                or self.norm_unit_offset:
            raise ConfigError(
                "the attention layer among one-mixer blocks is position-free "
                "GQA at one head size: state full_attention_rope false (the "
                "family reads neither rope_theta nor partial_rotary_factor; "
                "the mamba layers carry order), and neither qk_norm, "
                "norm_unit_offset, v_head_dim, partial_rotary_factor, "
                "attention_value_scale, a sink nor an output gate")

    def _check_kda(self) -> None:
        """``linear_attn_config``'s keys, its layer lists against
        ``layer_types``, and what Kimi Delta Attention layers are served
        beside: full latent layers, and nothing else yet."""
        from arkflow_tpu.errors import ConfigError

        spec = dict(self.linear_attn_config)
        sizes = {"num_heads", "head_dim", "short_conv_kernel_size"}
        if (not sizes <= set(spec) <= sizes | {"kda_layers", "full_attn_layers"}
                or min(self.kda_heads, self.kda_head_dim) <= 0
                or self.kda_taps < 2):
            raise ConfigError(
                "linear_attn_config states num_heads, head_dim > 0 and "
                "short_conv_kernel_size >= 2 (and kda_layers / "
                f"full_attn_layers, 1-based), got {spec}")
        if not self.linear:
            raise ConfigError("linear_attn_config without a linear_attention "
                              "layer in layer_types")
        for key, kind in (("kda_layers", LINEAR), ("full_attn_layers", FULL)):
            if key not in spec:
                continue
            named = tuple(i for i in spec[key] if i <= self.layers)
            mine = tuple(i + 1 for i, k in enumerate(self.kinds) if k == kind)
            if named != mine:
                raise ConfigError(
                    f"linear_attn_config.{key} (1-based) names layers {named} "
                    f"of the first {self.layers}; layer_types names its "
                    f"{kind} layers {mine}")
        if not self.latent:
            raise ConfigError(
                "Kimi Delta Attention layers (linear_attn_config: pool kda) "
                "are served among latent-attention layers (kv_lora_rank > 0): "
                "among per-head K/V layers they are not, yet (a Gated "
                "DeltaNet, linear_num_*, is)")
        if CONV in self.kinds or self.hybrid:
            raise ConfigError(
                "conv layers (pool conv) and the hybrid block (pool ssm) are "
                "not served beside a latent row, with Kimi Delta Attention "
                "layers or without, yet")
        if SLIDING in self.kinds or self.index_topk or FULL not in self.kinds:
            raise ConfigError(
                "Kimi Delta Attention layers (pool kda) are served among "
                "plain full_attention latent layers (at least one): beside "
                "sliding latent layers (pool window) or indexed ones (pool "
                "index) they are not, yet")

    @property
    def latent(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def kda(self) -> bool:
        """True where a ``linear_attention`` layer is Kimi Delta Attention
        (``linear_attn_config`` present), not a Gated DeltaNet."""
        return self.linear_attn_config is not None

    def _kda_size(self, key: str) -> int:
        return int(dict(self.linear_attn_config).get(key, 0))

    @property
    def kda_heads(self) -> int:
        return self._kda_size("num_heads")

    @property
    def kda_head_dim(self) -> int:
        """A KDA head's key width, and its value width."""
        return self._kda_size("head_dim")

    @property
    def kda_taps(self) -> int:
        return self._kda_size("short_conv_kernel_size")

    @property
    def kda_conv_dim(self) -> int:
        """Channels the KDA convs run over: q | k | v, each heads x head dim."""
        return 3 * self.kda_heads * self.kda_head_dim

    @property
    def linear_taps(self) -> int:
        """Taps of a linear_attention layer's causal conv, either mixer's."""
        return self.kda_taps if self.kda else self.linear_conv_kernel_dim

    @property
    def hybrid(self) -> bool:
        """True where every layer runs a Mamba-2 mixer beside its attention."""
        return self.mamba_d_ssm > 0 and not self.mamba

    @property
    def mamba(self) -> bool:
        """True where some layers' ONE mixer is a Mamba-2 mixer."""
        return MAMBA in self.kinds

    @property
    def ssm_heads_packed(self) -> int:
        """Mixer heads a row of the state pool holds side by side: a ``mamba``
        layer's as many as fill 128 lanes (``ops/ssm_scan.heads_packed``: two
        of 64 lanes; 1 from 128 on). The parallel hybrid block's pool stays a
        head a row at every size, as its recorded programs hold it (Falcon-H1's
        heads are 128 wide: nothing to pack); the kernels tell by shapes."""
        from arkflow_tpu.ops.ssm_scan import heads_packed

        if not self.mamba:
            return 1
        return heads_packed(self.mamba_n_heads, self.mamba_n_groups,
                            self.mamba_d_head)

    @property
    def one_mixer(self) -> bool:
        """True where a block holds one mixer and nothing after it (mamba /
        moe layers; an attention layer among them has no MLP)."""
        return bool({MAMBA, MOE} & set(self.kinds))

    @property
    def relu2(self) -> bool:
        """True where an expert is two matrices, ``relu(x W_up)^2 W_down``."""
        return self.mlp_hidden_act == "relu2"

    @property
    def expert_width_held(self) -> int:
        """An expert's width as the stack holds it: ``moe_intermediate_size``,
        or — two-matrix experts of a width that is no multiple of 128 lanes
        (1,856) — the next multiple (1,920), zero columns of ``w_up`` and
        zero rows of ``w_down`` behind the expert's own (``relu(0)^2`` adds
        nothing). The chip tiles ``w_up``'s minor axis to whole 128-lane rows
        anyway, and a kernel's copy takes a slice of an expert out of the
        stack only in such rows: handed the published width, the compiler
        re-lays the whole stack for every call (3.3 GB at Nemotron-3-Nano's
        sizes). One layout, whatever serves."""
        f = self.moe_intermediate_size
        return -(-f // 128) * 128 if self.relu2 and f > 128 else f

    @property
    def shared_stack(self) -> int:
        """Shared experts AS STACKED behind the held ones, each
        ``moe_intermediate_size`` wide: a relu-squared shared expert of a
        multiple of that width is so many side by side."""
        if not self.moe_shared_expert_intermediate_size:
            return self.n_shared_experts
        return self.n_shared_experts * (self.moe_shared_expert_intermediate_size
                                        // self.moe_intermediate_size)

    @property
    def conv(self) -> bool:
        """True where some layers' mixer is a gated short convolution."""
        return CONV in self.kinds

    @property
    def linear(self) -> bool:
        """True where some layers' mixer is a Gated DeltaNet, or — ``kda``
        — Kimi Delta Attention."""
        return LINEAR in self.kinds

    @property
    def stateful(self) -> bool:
        """True where a sequence caches a state beside its rows by token
        (``paged_decode.cache_spec``: a ``per_slot`` pool)."""
        return self.hybrid or self.conv or self.linear or self.mamba

    @property
    def attn_kinds(self) -> tuple:
        """The kinds of the layers that attend (``gqa`` / ``attn`` state
        their sizes), in the layers' order."""
        return tuple(k for k in self.kinds
                     if k not in (CONV, LINEAR, MAMBA, MOE))

    @property
    def out_gate(self) -> bool:
        """True where a per-head attention layer's output is gated value by
        value (``attention_gate_type`` "elementwise")."""
        return not self.latent and self.attention_gate_type == "elementwise"

    @property
    def gdn_conv_dim(self) -> int:
        """Channels a Gated DeltaNet's causal conv runs over: q | k | v."""
        return (2 * self.linear_num_key_heads * self.linear_key_head_dim
                + self.linear_num_value_heads * self.linear_value_head_dim)

    @property
    def dh(self) -> int:
        """The size of a per-head K/V layer's attention head."""
        return self.head_dim or self.dim // self.heads

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's causal conv runs over: x | B | C."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def kinds(self) -> tuple:
        """Each layer's attention kind (all full without ``layer_types``)."""
        if self.layer_types is None:
            return (FULL,) * self.layers
        return tuple(self.layer_types[:self.layers])

    @property
    def layered(self) -> bool:
        """True where the cache is not one row shape for every layer: a
        sliding layer's window pool or an indexed layer's index keys."""
        return SLIDING in self.kinds or self.index_topk > 0

    @property
    def by_runs(self) -> bool:
        """True where the parameter tree stacks its layers by runs
        (``layer_runs``: a latent model, routed experts, a layer pattern,
        per-head norms); otherwise ``layers`` is the one stack of identical
        layers, as it always was."""
        return (self.latent or self.routed or self.layered or self.qk_norm
                or self.hetero or self.conv or self.linear or self.eva
                or self.one_mixer)

    def _check_gqa_kinds(self) -> None:
        """The per-kind keys of a per-head K/V model (``gqa``)."""
        from arkflow_tpu.errors import ConfigError

        per_kind = (self.swa_kv_heads, self.swa_v_head_dim, self.swa_rope_theta,
                    self.add_swa_attention_sink_bias)
        if any(per_kind) and SLIDING not in self.kinds:
            raise ConfigError(
                "swa_kv_heads / swa_v_head_dim / swa_rope_theta / "
                "add_swa_attention_sink_bias without a sliding_attention "
                "layer in layer_types")
        rotary = self.dh * self.partial_rotary_factor
        if not 0 < self.partial_rotary_factor <= 1.0 or int(rotary) % 2:
            raise ConfigError(
                "partial_rotary_factor rotates the first int(head_dim * "
                f"factor) values of a head, an even number in (0, head_dim]; "
                f"got {self.partial_rotary_factor} of {self.dh}")
        if self.v_head_dim < 0 or self.swa_v_head_dim < 0 \
                or self.swa_rope_theta < 0 or self.attention_value_scale <= 0:
            raise ConfigError(
                "v_head_dim, swa_v_head_dim, swa_rope_theta >= 0 and "
                "attention_value_scale > 0")
        for kind in set(self.attn_kinds):
            kvh = self.gqa(kind).kv_heads
            if kvh <= 0 or self.heads % kvh:
                key = "swa_kv_heads" if kind == SLIDING else "kv_heads"
                raise ConfigError(
                    f"a {kind} layer's K/V heads ({key} {kvh}) divide the "
                    f"{self.heads} query heads")
        if self.hetero and (self.hybrid or self.num_experts > 1
                            or self.use_ring_attention):
            raise ConfigError(
                "per-kind head sizes, a value head of its own width "
                "(v_head_dim), partial_rotary_factor, attention_value_scale "
                "and a sink compose with neither the hybrid block "
                "(mamba_d_ssm), the Switch top-1 layer (num_experts) nor "
                "ring attention")

    def gqa(self, kind: str) -> "GqaSpec":
        """The sizes of one kind of per-head K/V layer: the one place that
        says what a sliding layer has of its own."""
        sliding = kind == SLIDING
        rotate = sliding or self.full_attention_rope
        return GqaSpec(
            kind=kind,
            kv_heads=(sliding and self.swa_kv_heads) or self.kv_heads,
            dk=self.dh,
            dv=(sliding and self.swa_v_head_dim) or self.v_head_dim or self.dh,
            rope_theta=(sliding and self.swa_rope_theta) or self.rope_theta,
            rotary=int(self.dh * self.partial_rotary_factor) if rotate else 0,
            window=self.sliding_window if sliding else 0,
            sink=(self.add_swa_attention_sink_bias if sliding
                  else self.add_full_attention_sink_bias))

    @property
    def hetero(self) -> bool:
        """True where a per-head K/V model departs from one head size for
        keys and values on every layer: such a model stacks by runs, and
        kinds of different shapes in stacks of their own."""
        if self.latent:
            return False
        specs = [self.gqa(kind) for kind in dict.fromkeys(self.attn_kinds)]
        return (any(sp.dv != sp.dk or sp.sink for sp in specs)
                or len({sp.shape for sp in specs}) > 1
                or self.partial_rotary_factor != 1.0
                or self.attention_value_scale != 1.0 or self.out_gate)

    @property
    def kind_stacks(self) -> bool:
        """True where a layer's kind decides the stack its parameters live
        in: a latent model's kinds, and a per-head model's where their
        shapes differ (a conv or linear_attention layer has no attention
        weights at all)."""
        return self.latent or self.conv or self.linear or self.one_mixer or len(
            {self.gqa(kind).shape for kind in self.kinds}) > 1

    def attn(self, kind: str) -> "AttnSpec":
        """The sizes of one kind of latent layer, under the names the
        ``mla_*`` functions read (the model's own keys for a full layer)."""
        if kind == SLIDING:
            return AttnSpec(
                kind=kind, dim=self.dim, norm_eps=self.norm_eps,
                heads=self.swa_heads, q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank,
                qk_nope_head_dim=self.swa_qk_nope_head_dim,
                qk_rope_head_dim=self.swa_qk_rope_head_dim,
                v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
                gate=self.swa_attention_gate_type == "headwise",
                rescale=self.apply_mla_qkv_lora_rescale,
                window=self.sliding_window)
        return AttnSpec(
            kind=kind, dim=self.dim, norm_eps=self.norm_eps, heads=self.heads,
            q_lora_rank=self.q_lora_rank, kv_lora_rank=self.kv_lora_rank,
            qk_nope_head_dim=self.qk_nope_head_dim,
            qk_rope_head_dim=self.qk_rope_head_dim, v_head_dim=self.v_head_dim,
            rope_theta=self.rope_theta,
            gate=self.attention_gate_type == "headwise",
            rescale=self.apply_mla_qkv_lora_rescale,
            index_n_heads=self.index_n_heads,
            index_head_dim=self.index_head_dim, index_topk=self.index_topk,
            rope_scaling=self.rope_scaling, rotate=not self.mla_use_nope)

    @property
    def routed(self) -> bool:
        return self.n_routed_experts > 0

    @property
    def held(self) -> tuple:
        """(first, count) of the routed experts this chip holds."""
        return tuple(self.experts_held or (0, self.n_routed_experts))

    @property
    def dense_layers(self) -> int:
        """Layers of the leading dense stack (all of them without experts)."""
        return self.first_k_dense_replace if self.routed else self.layers

    @property
    def expert_layers(self) -> int:
        """Layers of the routed-expert stack that follows it — among
        one-mixer blocks, the moe layers."""
        if self.one_mixer:
            return self.kinds.count(MOE)
        return self.layers - self.dense_layers


def llama3_8b() -> DecoderConfig:
    return DecoderConfig(
        vocab_size=128256, dim=4096, layers=32, heads=32, kv_heads=8,
        ffn=14336, max_seq=8192,
    )


def _init_latent_layer(key, cfg: DecoderConfig, routed: bool,
                       kind: str = FULL) -> dict:
    """One layer of a latent-attention model: the MLA projections (HF names:
    q_proj — or q_a_proj, q_a_layernorm, q_b_proj with a low-rank query —,
    kv_a_proj_with_mqa, kv_a_layernorm, kv_b_proj, o_proj) at the sizes of
    its ``kind``, the headwise gate and the indexer where the kind has them,
    and either a dense SwiGLU or the routed experts. ``experts`` holds the
    routed experts HELD here first and the shared experts after them, each
    of width ``moe_intermediate_size``: a shared MLP of ``n_shared_experts``
    times that width is the sum of so many SwiGLUs of one width, and one
    stacked tensor lets one product (``ops/moe_experts``) serve both."""
    k = iter(jax.random.split(key, 12))
    sp = cfg.attn(kind)
    qk = sp.qk_nope_head_dim + sp.qk_rope_head_dim
    layer = {
        "attn_norm": cm.rms_norm_init(cfg.dim),
        "wq": cm.dense_init(next(k), sp.q_lora_rank or cfg.dim, sp.heads * qk,
                            bias=False),
        "wkv_a": cm.dense_init(next(k), cfg.dim,
                               sp.kv_lora_rank + sp.qk_rope_head_dim, bias=False),
        "kv_norm": cm.rms_norm_init(sp.kv_lora_rank),
        "wkv_b": cm.dense_init(
            next(k), sp.kv_lora_rank,
            sp.heads * (sp.qk_nope_head_dim + sp.v_head_dim), bias=False),
        "wo": cm.dense_init(next(k), sp.heads * sp.v_head_dim, cfg.dim, bias=False),
        "mlp_norm": cm.rms_norm_init(cfg.dim),
    }
    # what a layer pattern adds draws from keys of its own (fold_in): the
    # leaves above keep the values they had before there were kinds
    extra = (jax.random.fold_in(key, 100 + i) for i in range(8))
    if sp.q_lora_rank:
        layer["wq_a"] = cm.dense_init(next(extra), cfg.dim, sp.q_lora_rank, bias=False)
        layer["q_norm"] = cm.rms_norm_init(sp.q_lora_rank)
    if sp.gate:
        layer["w_head_gate"] = cm.dense_init(next(extra), cfg.dim, sp.heads, bias=False)
    if sp.index_topk:
        # the indexer (DeepSeek-V3.2: wq_b, wk + k_norm, weights_proj): it
        # SELECTS, as the router does, so its leaves are float32 as served
        layer["index_wq"] = cm.dense_init(
            next(extra), sp.q_lora_rank, sp.index_n_heads * sp.index_head_dim,
            bias=False)
        layer["index_wk"] = cm.dense_init(next(extra), cfg.dim, sp.index_head_dim,
                                          bias=False)
        layer["index_k_norm"] = cm.layer_norm_init(sp.index_head_dim)
        layer["index_w"] = cm.dense_init(next(extra), cfg.dim, sp.index_n_heads,
                                         bias=False)
    if cfg.hc_mult > 1:
        for i, name in enumerate(("mhc_attn", "mhc_mlp")):
            layer[name] = _init_mhc(jax.random.fold_in(key, 200 + i), cfg)
    layer.update(_init_ffn(k, cfg, routed))
    return layer


def _init_mhc(key, cfg: DecoderConfig) -> dict:
    """One sub-layer's hyper-connection leaves (``ops/mhc_mix``), float32.
    ``phi`` ~ N(0, 1 / (n dim)): the normed projection ``m`` is then N(0, 1)
    a coefficient, so ``alpha`` IS the size of a coefficient's dynamic part.
    Seeded so that the dynamic and the static part of every logit are of
    one size and no coefficient is trivial (no source states a trained
    model's values): every ``alpha`` near 0.5; ``b`` ~ N(0, 0.5) (input
    weights spread over (0, 1), output weights over (0, 2)), the n x n
    logits plus 1 on the diagonal — after the normalisations a stream keeps
    ~0.4 of itself and takes ~0.2 of each other: neither the identity
    (streams that never mix) nor uniform (streams that are one), a token's
    own matrix (its entries vary by ~0.08 over tokens), and of a spread at
    which the configured twenty iterations reach rows AND columns of sum 1
    to 1e-5 where one iteration leaves the columns 4 % off (logits of
    spread 1 leave some tokens' columns 1e-3 off after twenty)."""
    n = cfg.hc_mult
    k_phi, k_b, k_a = jax.random.split(key, 3)
    cols = n * n + 2 * n
    phi = jax.random.normal(k_phi, (n * cfg.dim, cols), jnp.float32) / (
        n * cfg.dim) ** 0.5
    b = 0.5 * jax.random.normal(k_b, (cols,), jnp.float32)
    b = b.at[2 * n:].add(jnp.eye(n, dtype=jnp.float32).reshape(-1))
    alpha = 0.5 * jax.random.uniform(k_a, (3,), jnp.float32, 0.8, 1.2)
    return {"phi": phi, "b": b, "alpha": alpha}


def hc_expand(x: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """[B, S, dim] -> the ``hc_mult`` residual streams, each a copy of the
    embedding, side by side in one lane-dense row [B, S, n dim] (stream j in
    columns j dim .. (j + 1) dim: ``ops/mhc_mix`` says why flat)."""
    return jnp.tile(x, (1, 1, cfg.hc_mult))


def hc_collapse(x: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """The streams' sum [B, S, n dim] -> [B, S, dim] (float32 sums)."""
    d = cfg.dim
    return functools.reduce(lambda a, b: a + b, (
        x[..., j * d:(j + 1) * d].astype(jnp.float32)
        for j in range(cfg.hc_mult))).astype(x.dtype)


def hc_pre(leaves: dict, x: jnp.ndarray, cfg: DecoderConfig, **form):
    """A sub-layer's input ``u`` [B, S, dim] and the token's coefficients
    ``H`` from the streams ``x`` [B, S, n dim] (``ops/mhc_mix.mhc_pre``)."""
    from arkflow_tpu.ops.mhc_mix import mhc_pre

    return mhc_pre(x, leaves, n=cfg.hc_mult, iters=cfg.hc_sinkhorn_iters,
                   eps=cfg.hc_eps,
                   clamp=cfg.hc_res_clamp, norm_eps=cfg.norm_eps, **form)


def hc_post(x: jnp.ndarray, y: jnp.ndarray, h: jnp.ndarray, **form):
    """The streams after a sub-layer's output ``y`` (``mhc_mix.mhc_post``)."""
    from arkflow_tpu.ops.mhc_mix import mhc_post

    return mhc_post(x, y, h, **form)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _uniform_padded(key, shape: tuple, bound: float, pad: tuple):
    """uniform(-bound, bound) of ``shape`` with zeros behind it, drawn INTO
    the padded array by one program (drawn and then padded, a layer's 1.3 GB
    of experts is written twice)."""
    return jnp.pad(jax.random.uniform(key, shape, jnp.float32, -bound, bound), pad)


def _init_ffn(k, cfg: DecoderConfig, routed: bool) -> dict:
    """A layer's MLP half, drawn from the key iterator ``k``: a dense SwiGLU
    of width ``ffn``, or the router, its selection bias and the stacked
    experts (those HELD here first, the shared experts after them)."""
    if not routed:
        return {"w_gate": cm.dense_init(next(k), cfg.dim, cfg.ffn, bias=False),
                "w_up": cm.dense_init(next(k), cfg.dim, cfg.ffn, bias=False),
                "w_down": cm.dense_init(next(k), cfg.ffn, cfg.dim, bias=False)}
    layer = {}
    e = cfg.held[1] + cfg.shared_stack
    f = cfg.moe_intermediate_size
    up, down = 1.0 / (cfg.dim ** 0.5), 1.0 / (f ** 0.5)
    layer["router"] = cm.dense_init(next(k), cfg.dim, cfg.n_routed_experts, bias=False)
    # the noaux_tc selection bias: trained to BALANCE load, so never zero in
    # a real checkpoint. Seeded non-zero, of the order of the gap between
    # neighbouring scores (~0.01 for 128 experts): selecting by score + bias
    # and by the score alone then differ, and a random router, balanced
    # already, stays balanced. (+-0.1 was tried on the chip, PERF.md PR 27:
    # it decides the selection, 16 lanes then hit 52 experts a layer and
    # not 69, and the busiest expert takes 9 x the mean load.)
    bias_key = next(k)
    if cfg.topk_method == "noaux_tc":  # a greedy router has no bias at all
        layer["router_bias"] = (
            cfg.router_bias_std * jax.random.normal(
                bias_key, (cfg.n_routed_experts,), jnp.float32)
            if cfg.router_bias_std > 0 else jax.random.uniform(
                bias_key, (cfg.n_routed_experts,), jnp.float32, -0.01, 0.01))
    if cfg.shared_expert_gate:  # one logit a token: sigmoid of it weighs
        layer["shared_gate"] = cm.dense_init(  # the shared experts' output
            jax.random.fold_in(bias_key, 1), cfg.dim, 1, bias=False)
    if cfg.relu2:
        # two matrices an expert, relu(x W_up)^2 W_down: no gate is drawn (a
        # third of the layer's draws), and zeros stand behind the width (as
        # held: ``expert_width_held``)
        next(k)
        pad = cfg.expert_width_held - f
        layer["experts"] = {
            "w_up": _uniform_padded(next(k), (e, cfg.dim, f), up,
                                    ((0, 0), (0, 0), (0, pad))),
            "w_down": _uniform_padded(next(k), (e, f, cfg.dim), down,
                                      ((0, 0), (0, pad), (0, 0)))}
        return layer
    layer["experts"] = {
        "w_gate": jax.random.uniform(next(k), (e, cfg.dim, f), jnp.float32, -up, up),
        "w_up": jax.random.uniform(next(k), (e, cfg.dim, f), jnp.float32, -up, up),
        "w_down": jax.random.uniform(next(k), (e, f, cfg.dim), jnp.float32, -down, down),
    }
    return layer


#: the stack a layer's parameters live in, by (kind, routed?): layers of one
#: shape stack on a leading axis
_STACKS = {(FULL, False): "dense_layers", (FULL, True): "layers",
           (SLIDING, False): "swa_dense_layers", (SLIDING, True): "swa_layers",
           (CONV, False): "conv_dense_layers", (CONV, True): "conv_layers",
           (LINEAR, False): "gdn_dense_layers", (LINEAR, True): "gdn_layers",
           # blocks of one mixer each: an attention layer among them is a
           # ``dense_layers`` entry with no MLP
           (MAMBA, False): "mamba_layers", (MOE, True): "moe_layers"}


def layer_runs(cfg: DecoderConfig) -> list:
    """The model's layers in order as runs of one shape: each ``(stack name,
    first, stop, kind, routed?, kind_first)`` — layers ``first..stop`` of
    that stack, ``kind_first`` the index of the run's first layer among the
    layers of its kind (the cache pools' layer axis). A model without a
    pattern has ``dense_layers`` then ``layers``, each whole. A per-head K/V
    model whose layers have one shape of attention whatever their kind
    stacks by dense | routed alone (``layers`` the only stack without routed
    experts), and a pattern makes runs WITHIN a stack; where the kinds'
    shapes differ (``kind_stacks``) each kind has stacks of its own, as a
    latent model's."""
    runs, in_stack, of_kind = [], {}, {}
    by_kind = cfg.kind_stacks
    for i, kind in enumerate(cfg.kinds):
        routed = (kind == MOE if cfg.one_mixer
                  else cfg.routed and i >= cfg.first_k_dense_replace)
        name = _STACKS[kind if by_kind else FULL,
                       routed if cfg.one_mixer else routed or not cfg.routed]
        if cfg.kda and kind == LINEAR:  # the other mixer's leaves: its own stacks
            name = name.replace("gdn", "kda")
        at, kat = in_stack.get(name, 0), of_kind.get(kind, 0)
        if runs and runs[-1][0] == name and runs[-1][3] == kind:
            runs[-1][2] = at + 1
        else:
            runs.append([name, at, at + 1, kind, routed, kat])
        in_stack[name], of_kind[kind] = at + 1, kat + 1
    return [tuple(r) for r in runs]


#: ``eva_phi`` and ``eva_mu`` are SEEDED normal(0, this). The family's own
#: init (0.01275) makes a chunk's pooling logits ~0.01 wide: every summary is
#: then its chunk's plain mean and ``mu`` moves no score, so a wrong ``phi``
#: or a missing ``mu`` could not be told from a right one. At 0.5 the logits
#: (and ``mu``'s part of a score) spread ~0.6, as wide as a seeded model's
#: attention scores
_EVA_INIT_STD = 0.5


def _init_gqa_layer(key, cfg: DecoderConfig, routed: bool,
                    kind: str = FULL) -> dict:
    """One layer of a per-head K/V model that stacks by runs (routed experts
    or a layer pattern): GQA projections (HF names: q_proj, k_proj, v_proj,
    o_proj; q_norm / k_norm over a head with ``qk_norm``) at the sizes of
    its ``kind`` (``cfg.gqa``), the kind's sink logits where it has them,
    and a dense SwiGLU or the routed experts."""
    k = iter(jax.random.split(key, 12))
    sp = cfg.gqa(kind)
    layer = {
        "attn_norm": cm.rms_norm_init(cfg.dim),
        "wq": cm.dense_init(next(k), cfg.dim, cfg.heads * sp.dk, bias=False),
        "wk": cm.dense_init(next(k), cfg.dim, sp.kv_heads * sp.dk, bias=False),
        "wv": cm.dense_init(next(k), cfg.dim, sp.kv_heads * sp.dv, bias=False),
        "wo": cm.dense_init(next(k), cfg.heads * sp.dv, cfg.dim, bias=False),
        "mlp_norm": cm.rms_norm_init(cfg.dim),
    }
    if cfg.one_mixer:  # the block ends with its attention: no MLP behind it
        del layer["mlp_norm"]
        return layer
    if cfg.qk_norm:
        layer.update(q_head_norm=cm.rms_norm_init(sp.dk),
                     k_head_norm=cm.rms_norm_init(sp.dk))
    if sp.sink:
        # a trained sink is no zero: it takes what a window's keys do not
        # claim. normal(2, 1), of the order of a window's largest score: a
        # few percent to a third of a head's probability beside 128 seeded
        # keys (at normal(0, 1) it takes 1 % and leaving it out moves no
        # token: PERF.md, PR 42), from a key of its own (the leaves above
        # keep their values)
        layer["attn_sink"] = 2.0 + jax.random.normal(
            jax.random.fold_in(key, 200), (cfg.heads,), jnp.float32)
    if cfg.out_gate:
        # the output gate's half of the source's fused query projection
        # ([q | gate] a head): a leaf of its own, a permutation of its columns
        layer["w_out_gate"] = cm.dense_init(
            jax.random.fold_in(key, 300), cfg.dim, cfg.heads * sp.dv, bias=False)
    if cfg.eva:  # the summariser's pooling direction and key offset, a head
        for i, name in enumerate(("eva_phi", "eva_mu")):
            layer[name] = _EVA_INIT_STD * jax.random.normal(
                jax.random.fold_in(key, 600 + i), (sp.kv_heads, sp.dk), jnp.float32)
    layer.update(_init_ffn(k, cfg, routed))
    return layer


def _init_one_mixer_layer(key, cfg: DecoderConfig, kind: str) -> dict:
    """One block of a model whose blocks hold one mixer each (HF names:
    backbone.layers.i.norm, .mixer): the block's norm — ``attn_norm``, the
    name every stack's depth is read from — and a Mamba-2 mixer
    (``_init_mixer``), the routed experts (``_init_ffn``) or the attention
    projections (``_init_gqa_layer``)."""
    if kind == MAMBA:
        return {"attn_norm": cm.rms_norm_init(cfg.dim), **_init_mixer(key, cfg)}
    if kind == MOE:
        return {"attn_norm": cm.rms_norm_init(cfg.dim),
                **_init_ffn(iter(jax.random.split(key, 6)), cfg, True)}
    return _init_gqa_layer(key, cfg, False, kind)


def _init_gdn_layer(key, cfg: DecoderConfig, routed: bool) -> dict:
    """One Gated DeltaNet layer (HF names: input_layernorm, linear_attn.
    in_proj_qkvz, in_proj_ba, conv1d, A_log, dt_bias, norm, out_proj,
    post_attention_layernorm). ``gdn_in``'s columns are q | k | v | z and
    ``gdn_ba``'s b | a, each segment head-major (the source groups all four
    by key head: a permutation of columns); ``gdn_conv_w`` [q | k | v
    channels, taps] the depthwise taps, oldest input first, no bias (torch's
    default init). ``A_log`` is the log of uniform(0, 16) and ``dt_bias``
    ones in the family's initialisation; ``dt_bias`` and the gated norm's
    scale are seeded AROUND one here, so that leaving either out shows."""
    k = iter(jax.random.split(key, 12))
    nv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    conv, taps = cfg.gdn_conv_dim, cfg.linear_conv_kernel_dim
    bound = taps ** -0.5
    extra = (jax.random.fold_in(key, 400 + i) for i in range(4))
    layer = {
        "attn_norm": cm.rms_norm_init(cfg.dim),
        "gdn_in": cm.dense_init(next(k), cfg.dim, conv + nv * dv, bias=False),
        "gdn_ba": cm.dense_init(next(k), cfg.dim, 2 * nv, bias=False),
        "gdn_conv_w": jax.random.uniform(next(k), (conv, taps), jnp.float32,
                                         -bound, bound),
        "gdn_A_log": jnp.log(jax.random.uniform(next(extra), (nv,), jnp.float32,
                                                1e-6, 16.0)),
        "gdn_dt_bias": 1.0 + 0.1 * jax.random.normal(next(extra), (nv,),
                                                     jnp.float32),
        "gdn_norm": {"scale": 1.0 + 0.1 * jax.random.normal(
            next(extra), (dv,), jnp.float32)},
        "gdn_out": cm.dense_init(next(k), nv * dv, cfg.dim, bias=False),
        "mlp_norm": cm.rms_norm_init(cfg.dim),
    }
    layer.update(_init_ffn(k, cfg, routed))
    return layer


def _init_kda_layer(key, cfg: DecoderConfig, routed: bool) -> dict:
    """One Kimi Delta Attention layer (HF names: input_layernorm,
    self_attn.q_proj / k_proj / v_proj, q_conv1d / k_conv1d / v_conv1d,
    f_a_proj -> f_b_proj, b_proj, A_log, dt_bias, g_a_proj -> g_b_proj,
    o_norm, o_proj, post_attention_layernorm). ``kda_qkv``'s columns are q | k
    | v, each head-major (three matrices side by side: the same bytes);
    ``kda_conv_w`` [q | k | v channels, taps] the three convs' depthwise taps,
    oldest input first, no bias (torch's default init). ``A_log`` is the log
    of uniform(1, 16) a head and ``dt_bias`` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] a CHANNEL (the Mamba-2 / flash-linear-attention
    initialisation this family ships: ``_init_mixer``'s): a head's memory
    then spans tens to thousands of tokens, so what a state holds is most of
    its sequence and its precision shows. The gated norm's scale is seeded
    around one, so that leaving it out shows."""
    k = iter(jax.random.split(key, 16))
    h, d, taps = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_taps
    p, bound = h * d, taps ** -0.5
    extra = (jax.random.fold_in(key, 800 + i) for i in range(4))
    dt = jnp.exp(jax.random.uniform(next(extra), (p,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    layer = {
        "attn_norm": cm.rms_norm_init(cfg.dim),
        "kda_qkv": cm.dense_init(next(k), cfg.dim, 3 * p, bias=False),
        "kda_conv_w": jax.random.uniform(next(k), (3 * p, taps), jnp.float32,
                                         -bound, bound),
        "kda_fa": cm.dense_init(next(k), cfg.dim, d, bias=False),
        "kda_fb": cm.dense_init(next(k), d, p, bias=False),
        "kda_b": cm.dense_init(next(k), cfg.dim, h, bias=False),
        "kda_ga": cm.dense_init(next(k), cfg.dim, d, bias=False),
        "kda_gb": cm.dense_init(next(k), d, p, bias=False),
        "kda_A_log": jnp.log(jax.random.uniform(next(extra), (h,), jnp.float32,
                                                1.0, 16.0)),
        "kda_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "kda_norm": {"scale": 1.0 + 0.1 * jax.random.normal(
            next(extra), (d,), jnp.float32)},
        "kda_out": cm.dense_init(next(k), p, cfg.dim, bias=False),
        "mlp_norm": cm.rms_norm_init(cfg.dim),
    }
    layer.update(_init_ffn(k, cfg, routed))
    return layer


#: the norms whose scale ``norm_unit_offset`` holds as an offset from one
_OFFSET_NORMS = ("attn_norm", "mlp_norm", "norm_out", "q_head_norm", "k_head_norm")


def _seed_offset_norms(params: dict, key) -> dict:
    """``params`` with every offset-from-one norm scale seeded small and
    non-zero (normal(0, 0.1): a trained ``w``; zeros would make ``1 + w`` and
    a plain scale of ones the same model)."""
    def walk(tree, key):
        out = {}
        for i, (name, sub) in enumerate(tree.items()):
            sub_key = jax.random.fold_in(key, i)
            if name in _OFFSET_NORMS:
                sub = {"scale": 0.1 * jax.random.normal(
                    sub_key, sub["scale"].shape, jnp.float32)}
            elif isinstance(sub, dict):
                sub = walk(sub, sub_key)
            out[name] = sub
        return out
    return walk(params, key)


def _init_conv_layer(key, cfg: DecoderConfig, routed: bool) -> dict:
    """One conv layer (HF names: operator_norm, conv.in_proj, conv.conv,
    conv.out_proj, ffn_norm): the input projection's columns are B | C | u,
    ``conv_w`` [dim, conv_L_cache] the depthwise taps, oldest input first
    (torch's default init: uniform within fan_in ** -0.5), and a dense
    SwiGLU or the routed experts. The operator norm keeps the name every
    stack's first norm has (``attn_norm``)."""
    k = iter(jax.random.split(key, 12))
    bound = cfg.conv_L_cache ** -0.5
    layer = {
        "attn_norm": cm.rms_norm_init(cfg.dim),
        "conv_in": cm.dense_init(next(k), cfg.dim, 3 * cfg.dim, bias=False),
        "conv_w": jax.random.uniform(next(k), (cfg.dim, cfg.conv_L_cache),
                                     jnp.float32, -bound, bound),
        "conv_out": cm.dense_init(next(k), cfg.dim, cfg.dim, bias=False),
        "mlp_norm": cm.rms_norm_init(cfg.dim),
    }
    layer.update(_init_ffn(k, cfg, routed))
    return layer


def _init_runs(rng, cfg: DecoderConfig) -> dict:
    """``init`` for a model whose layers stack by runs (``layer_runs``)."""
    keys = iter(jax.random.split(rng, 2 + cfg.layers))
    params = {
        "embed": cm.embedding_init(next(keys), cfg.vocab_size, cfg.dim,
                                   cfg.embed_init_std),
        "norm_out": cm.rms_norm_init(cfg.dim),
        "lm_head": cm.dense_init(next(keys), cfg.dim, cfg.vocab_size, bias=False),
    }
    stacks: dict = {}
    for name, first, stop, kind, routed, _ in layer_runs(cfg):
        stacks.setdefault(name, []).extend(
            _init_one_mixer_layer(next(keys), cfg, kind) if cfg.one_mixer
            else _init_kda_layer(next(keys), cfg, routed) if cfg.kda and kind == LINEAR
            else _init_latent_layer(next(keys), cfg, routed, kind) if cfg.latent
            else _init_conv_layer(next(keys), cfg, routed) if kind == CONV
            else _init_gdn_layer(next(keys), cfg, routed) if kind == LINEAR
            else _init_gqa_layer(next(keys), cfg, routed, kind)
            for _ in range(first, stop))
    for name in list(stacks):
        params[name] = _stack_layers(stacks.pop(name))
    if cfg.num_pred_heads > 1:  # the draft heads, head 1 first: [dim, 7 vocab]
        params["pred_heads"] = cm.dense_init(
            jax.random.fold_in(rng, 700), cfg.dim,
            (cfg.num_pred_heads - 1) * cfg.vocab_size, bias=False)
    if cfg.norm_unit_offset:
        params = _seed_offset_norms(params, jax.random.fold_in(rng, 500))
    return params


def _stack_layers(layers: list) -> dict:
    """The layers of one stack joined on a leading axis, LEAF BY LEAF, each
    layer's own leaf let go as its stacked copy is made: the host holds the
    layers once and one leaf twice, not every stack twice (at 4 B float32
    masters of which 3.4 B are one stack's experts that is 22 GB, not 31, of
    a 40 GB host)."""
    flat = [jax.tree_util.tree_flatten(layer) for layer in layers]
    treedef, leaves = flat[0][1], [list(f[0]) for f in flat]
    layers.clear()
    del flat
    out = []
    for i in range(treedef.num_leaves):
        out.append(jnp.stack([own[i] for own in leaves]))
        for own in leaves:
            own[i] = None
    return treedef.unflatten(out)


def layer_stacks(params: dict, cfg: DecoderConfig) -> list:
    """The model's layer runs in order, as (stacked params of the run,
    routed?, kind, index of its first layer among its kind's): a dense model
    has one, a latent one ``dense_layers`` and then ``layers`` (the expert
    stack), a layer pattern as many as its kinds alternate. A run that is
    not its whole stack is sliced out of it."""
    out = []
    for name, first, stop, kind, routed, kind_first in layer_runs(cfg):
        stack = params[name]
        if (first, stop) != (0, stack["attn_norm"]["scale"].shape[0]):
            stack = jax.tree_util.tree_map(lambda a: a[first:stop], stack)
        out.append((stack, routed, kind, kind_first))
    return out


def init(rng, cfg: DecoderConfig) -> dict:
    if cfg.by_runs:
        return _init_runs(rng, cfg)
    dh = cfg.dh
    keys = iter(jax.random.split(rng, 4 + 7 * cfg.layers))
    params = {
        "embed": cm.embedding_init(next(keys), cfg.vocab_size, cfg.dim,
                                   cfg.embed_init_std),
        "norm_out": cm.rms_norm_init(cfg.dim),
        "lm_head": cm.dense_init(next(keys), cfg.dim, cfg.vocab_size, bias=False),
        "layers": [],
    }
    for _ in range(cfg.layers):
        layer = {
            "attn_norm": cm.rms_norm_init(cfg.dim),
            "wq": cm.dense_init(next(keys), cfg.dim, cfg.heads * dh, bias=False),
            "wk": cm.dense_init(next(keys), cfg.dim, cfg.kv_heads * dh, bias=False),
            "wv": cm.dense_init(next(keys), cfg.dim, cfg.kv_heads * dh, bias=False),
            "wo": cm.dense_init(next(keys), cfg.heads * dh, cfg.dim, bias=False),
            "mlp_norm": cm.rms_norm_init(cfg.dim),
        }
        if cfg.num_experts > 1:
            e = cfg.num_experts
            sub = jax.random.split(next(keys), 4)
            scale = 1.0 / (cfg.dim ** 0.5)
            layer["router"] = cm.dense_init(sub[0], cfg.dim, e, bias=False)
            layer["experts"] = {
                "w_gate": jax.random.uniform(sub[1], (e, cfg.dim, cfg.ffn), jnp.float32, -scale, scale),
                "w_up": jax.random.uniform(sub[2], (e, cfg.dim, cfg.ffn), jnp.float32, -scale, scale),
                "w_down": jax.random.uniform(sub[3], (e, cfg.ffn, cfg.dim), jnp.float32, -scale, scale),
            }
        else:
            layer["w_gate"] = cm.dense_init(next(keys), cfg.dim, cfg.ffn, bias=False)
            layer["w_up"] = cm.dense_init(next(keys), cfg.dim, cfg.ffn, bias=False)
            layer["w_down"] = cm.dense_init(next(keys), cfg.ffn, cfg.dim, bias=False)
        if cfg.hybrid:
            # the mixer draws from keys of its own: the leaves above keep
            # the values a model without one has
            layer.update(_init_mixer(
                jax.random.fold_in(rng, 1000 + len(params["layers"])), cfg))
        params["layers"].append(layer)
    params["layers"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params["layers"])
    return params


def _init_mixer(key, cfg: DecoderConfig) -> dict:
    """One layer's Mamba-2 mixer (HF names: in_proj, conv1d, dt_bias, A_log,
    D, norm, out_proj). ``ssm_in``'s columns are z | x | B | C | dt. What a
    uniform draw would make degenerate takes Mamba-2's published init: A
    uniform in [1, 16] (``A_log`` its log), dt log-uniform in [1e-3, 1e-1]
    through the inverse softplus into ``dt_bias``, D = 1."""
    k = jax.random.split(key, 6)
    h, conv = cfg.mamba_n_heads, cfg.ssm_conv_dim
    bound = cfg.mamba_d_conv ** -0.5
    dt = jnp.exp(jax.random.uniform(k[4], (h,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return {
        "ssm_in": cm.dense_init(k[0], cfg.dim, cfg.mamba_d_ssm + conv + h, bias=False),
        "ssm_conv": {
            "w": jax.random.uniform(k[1], (conv, cfg.mamba_d_conv), jnp.float32,
                                    -bound, bound),
            "b": jax.random.uniform(k[2], (conv,), jnp.float32, -bound, bound)},
        "ssm_A_log": jnp.log(jax.random.uniform(k[3], (h,), jnp.float32, 1.0, 16.0)),
        "ssm_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "ssm_D": jnp.ones((h,), jnp.float32),
        "ssm_norm": cm.rms_norm_init(cfg.mamba_d_ssm),
        "ssm_out": cm.dense_init(k[5], cfg.mamba_d_ssm, cfg.dim, bias=False),
    }


def _scaled(x: jnp.ndarray, by: float) -> jnp.ndarray:
    """``x * by``, and ``x`` itself where ``by`` is 1 (no op is traced)."""
    return x if by == 1.0 else (x.astype(jnp.float32) * by).astype(x.dtype)


def ssm_project(lp: dict, y: jnp.ndarray, cfg: DecoderConfig):
    """The mixer's input projection of normed activations ``y`` [B, S, dim]:
    the gate ``z`` [B, S, d_ssm] and the raw step ``dt`` [B, S, heads],
    float32, times their ``ssm_multipliers`` entries, and the conv's input
    ``x | B | C`` [B, S, conv channels] AS PROJECTED (bfloat16, before its
    multipliers: what the conv window caches; ``ssm_conv`` scales it)."""
    u = cm.dense(lp["ssm_in"], _scaled(y, cfg.ssm_in_multiplier))
    d, conv = cfg.mamba_d_ssm, cfg.ssm_conv_dim
    mz, _, _, _, mdt = cfg.ssm_multipliers
    return (u[..., :d].astype(jnp.float32) * mz, u[..., d:d + conv],
            u[..., d + conv:].astype(jnp.float32) * mdt)


def ssm_conv(lp: dict, ext: jnp.ndarray, s: int, cfg: DecoderConfig) -> jnp.ndarray:
    """The depthwise causal conv and its SiLU over ``ext`` [B, d_conv - 1 +
    S, channels] — the ``d_conv - 1`` projected inputs before the block,
    then the block's — in float32: each segment x | B | C times its
    multiplier, then output t reads ``ext[t : t + d_conv]``."""
    _, mx, mb, mc, _ = cfg.ssm_multipliers
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    scale = jnp.concatenate([jnp.full((cfg.mamba_d_ssm,), mx, jnp.float32),
                             jnp.full((gn,), mb, jnp.float32),
                             jnp.full((gn,), mc, jnp.float32)])
    ext = ext.astype(jnp.float32) * scale
    w = lp["ssm_conv"]["w"].astype(jnp.float32)                   # [C, K]
    out = lp["ssm_conv"]["b"].astype(jnp.float32) + sum(
        ext[:, k:k + s] * w[:, k] for k in range(w.shape[1]))
    return jax.nn.silu(out)


def ssm_operands(lp: dict, conved: jnp.ndarray, dt: jnp.ndarray,
                 cfg: DecoderConfig, valid=None):
    """What the recurrence reads, from the conv's output [B, S, channels]
    and the raw step [B, S, heads]: ``x`` [B, S, H, P], the step
    ``softplus(dt + dt_bias)`` [B, S, H] — 0 where ``valid`` [B, S] is
    false, so that a padded position or an idle lane leaves the state as it
    is —, ``A = -exp(A_log)`` [H], ``B`` and ``C`` [B, S, G, N]."""
    b, s = conved.shape[:2]
    d, g, n = cfg.mamba_d_ssm, cfg.mamba_n_groups, cfg.mamba_d_state
    x = conved[..., :d].reshape(b, s, cfg.mamba_n_heads, cfg.mamba_d_head)
    bm = conved[..., d:d + g * n].reshape(b, s, g, n)
    cmat = conved[..., d + g * n:].reshape(b, s, g, n)
    step = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(jnp.float32))
    if valid is not None:
        step = jnp.where(valid[..., None], step, 0.0)
    return x, step, -jnp.exp(lp["ssm_A_log"].astype(jnp.float32)), bm, cmat


def ssm_output(lp: dict, o: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray,
               cfg: DecoderConfig, dtype) -> jnp.ndarray:
    """The recurrence's output ``o`` [B, S, H, P] -> the mixer's [B, S,
    dim]: the skip ``D x``, the gate ``silu(z)`` FIRST, then RMSNorm over
    each of the ``mamba_n_groups`` groups of channels
    (``mamba_norm_before_gate`` false), ``out_proj``, the multiplier."""
    b, s = z.shape[:2]
    o = o + lp["ssm_D"].astype(jnp.float32)[:, None] * x
    gated = (o.reshape(b, s, -1) * jax.nn.silu(z)).reshape(
        b, s, cfg.mamba_n_groups, -1)
    normed = gated * jax.lax.rsqrt(
        jnp.square(gated).mean(-1, keepdims=True) + cfg.norm_eps)
    normed = normed.reshape(b, s, -1) * lp["ssm_norm"]["scale"]
    return _scaled(cm.dense(lp["ssm_out"], normed.astype(dtype)),
                   cfg.ssm_out_multiplier)


def _mixer_block(lp: dict, y: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """The Mamba-2 mixer over a whole block from a zero state (``forward``):
    the chunked scan in plain XLA, no cache."""
    from arkflow_tpu.ops.ssm_scan import scan_from

    b, s = y.shape[:2]
    z, u, dt = ssm_project(lp, y, cfg)
    ext = jnp.pad(u, ((0, 0), (cfg.mamba_d_conv - 1, 0), (0, 0)))
    x, step, a, bm, cmat = ssm_operands(lp, ssm_conv(lp, ext, s, cfg), dt, cfg)
    s0 = jnp.zeros((b, cfg.mamba_n_heads, cfg.mamba_d_state, cfg.mamba_d_head),
                   jnp.float32)
    o, _ = scan_from(s0, x, step, a, bm, cmat, cfg.mamba_chunk_size)
    return ssm_output(lp, o, x, z, cfg, y.dtype)


def conv_gate(lp: dict, y: jnp.ndarray):
    """A conv mixer's input product over normed activations ``y`` [B, S,
    dim]: ``[B | C | u] = y W_in``. Returns (the output gate ``C`` float32,
    the gated input ``v = B * u`` rounded to the activations' type: what a
    sequence caches)."""
    d = y.shape[-1]
    bcu = cm.dense(lp["conv_in"], y)
    gate_b, gate_c, u = (bcu[..., i * d:(i + 1) * d].astype(jnp.float32)
                         for i in range(3))
    return gate_c, (gate_b * u).astype(bcu.dtype)


def conv_taps(lp: dict, ext: jnp.ndarray, s: int, cfg: DecoderConfig):
    """The depthwise causal conv ``c_t = sum_j w[:, j] v_{t - (L - 1) + j}``
    of the last ``s`` positions of ``ext`` [B, L - 1 + s, dim] (the gated
    inputs before a block, then the block's), in float32 with no activation."""
    w = lp["conv_w"].astype(jnp.float32)                          # [dim, L]
    return sum(ext[:, j:j + s].astype(jnp.float32) * w[:, j]
               for j in range(cfg.conv_L_cache))


def short_conv(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, before=None):
    """A conv layer's mixer over normed activations ``y`` [B, S, dim]:
    ``[B | C | u] = y W_in``, the gated input ``v = B * u`` (``conv_gate``),
    the depthwise causal conv over it (``conv_taps``), ``C * c``, then
    ``W_out``. ``before`` [B, L - 1, dim]: the
    gated inputs of the positions before the block, oldest first (None: the
    sequence starts here, zeros). Returns (the mixer's output [B, S, dim],
    ``before`` and the block's gated inputs joined [B, L - 1 + S, dim])."""
    b, s, d = y.shape
    gate_c, v = conv_gate(lp, y)
    if before is None:
        before = jnp.zeros((b, cfg.conv_L_cache - 1, d), v.dtype)
    ext = jnp.concatenate([before.astype(v.dtype), v], axis=1)
    conved = conv_taps(lp, ext, s, cfg)
    return cm.dense(lp["conv_out"], (gate_c * conved).astype(v.dtype)), ext


def _norm(p: dict, x: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """RMSNorm over the stream or an attention head at the model's epsilon:
    the scale as it is, or — ``norm_unit_offset`` — held as an offset from
    one, ``x_hat * (1 + w)``."""
    if not cfg.norm_unit_offset:
        return cm.rms_norm(p, x, cfg.norm_eps)
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                           + cfg.norm_eps)
    return (y * (1.0 + p["scale"])).astype(x.dtype)


def gdn_project(lp: dict, y: jnp.ndarray, cfg: DecoderConfig):
    """A Gated DeltaNet's input projections of normed activations ``y``
    [B, S, dim]: the conv's input ``q | k | v`` [B, S, conv channels] AS
    PROJECTED (bfloat16: what the conv window caches), the output gate ``z``
    [B, S, value heads x value dim] and the raw ``b`` and ``a`` [B, S, value
    heads], float32."""
    u = cm.dense(lp["gdn_in"], y)
    ba = cm.dense(lp["gdn_ba"], y).astype(jnp.float32)
    conv, nv = cfg.gdn_conv_dim, cfg.linear_num_value_heads
    return u[..., :conv], u[..., conv:].astype(jnp.float32), ba[..., :nv], ba[..., nv:]


def gdn_conv(lp: dict, ext: jnp.ndarray, s: int) -> jnp.ndarray:
    """The depthwise causal conv (no bias) and its SiLU over ``ext`` [B,
    taps - 1 + S, channels] — the ``taps - 1`` projected inputs before the
    block, then the block's — in float32: output t reads ``ext[t : t +
    taps]``."""
    return _conv_silu(lp["gdn_conv_w"], ext, s)


def _conv_silu(w: jnp.ndarray, ext: jnp.ndarray, s: int) -> jnp.ndarray:
    """``silu`` of the depthwise causal conv of taps ``w`` [channels, taps]
    over ``ext`` [B, taps - 1 + S, channels], float32 sums."""
    ext, w = ext.astype(jnp.float32), w.astype(jnp.float32)
    return jax.nn.silu(sum(ext[:, j:j + s] * w[:, j] for j in range(w.shape[1])))


def gdn_operands(lp: dict, conved: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray,
                 cfg: DecoderConfig, valid=None):
    """What the delta rule reads (``ops/gdn_scan``), from the conv's output
    [B, S, channels] and the raw ``b`` / ``a`` [B, S, value heads], float32:
    queries and keys L2-normalised a head (``x rsqrt(sum x^2 + 1e-6)``), a key
    head serving ``value heads / key heads`` value heads in a row
    (``repeat_interleave``), the queries times ``key dim ** -0.5``, [B, S,
    value heads, key dim]; values [B, S, value heads, value dim]; ``g = -exp(
    A_log) softplus(a + dt_bias)`` and ``beta = sigmoid(b)`` [B, S, value
    heads] — both 0 where ``valid`` [B, S] is false, so that a padded
    position or an idle lane leaves the state as it is."""
    bsz, s = conved.shape[:2]
    nk, nv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim

    def heads(x):  # [B, S, key heads, key dim] -> normalised, a value head each
        x = x.reshape(bsz, s, nk, dk)
        x = x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, nv // nk, axis=2)

    q = heads(conved[..., :nk * dk]) * dk ** -0.5
    k = heads(conved[..., nk * dk:2 * nk * dk])
    v = conved[..., 2 * nk * dk:].reshape(bsz, s, nv, dv)
    g = -jnp.exp(lp["gdn_A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + lp["gdn_dt_bias"].astype(jnp.float32))
    beta = jax.nn.sigmoid(b)
    if valid is not None:
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta


def gdn_output(lp: dict, o: jnp.ndarray, z: jnp.ndarray, cfg: DecoderConfig,
               dtype) -> jnp.ndarray:
    """The delta rule's output ``o`` [B, S, value heads, value dim] -> the
    mixer's [B, S, dim]: RMSNorm over each head's values times the norm's
    plain scale (one set for all heads), times ``silu(z)``, ``out_proj``."""
    bsz, s = z.shape[:2]
    normed = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                               + cfg.norm_eps) * lp["gdn_norm"]["scale"]
    gated = normed.reshape(bsz, s, -1) * jax.nn.silu(z)
    return cm.dense(lp["gdn_out"], gated.astype(dtype))


def _gdn_block(lp: dict, y: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """The Gated DeltaNet mixer over a whole block from a zero state and an
    empty window (``forward``): the chunked form in plain XLA, no cache."""
    from arkflow_tpu.ops.gdn_scan import chunk_from

    bsz, s = y.shape[:2]
    u, z, b, a = gdn_project(lp, y, cfg)
    ext = jnp.pad(u, ((0, 0), (cfg.linear_conv_kernel_dim - 1, 0), (0, 0)))
    q, k, v, g, beta = gdn_operands(lp, gdn_conv(lp, ext, s), b, a, cfg)
    s0 = jnp.zeros((bsz, cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                    cfg.linear_value_head_dim), jnp.float32)
    o, _ = chunk_from(s0, q, k, v, g, beta)
    return gdn_output(lp, o, z, cfg, y.dtype)


def kda_project(lp: dict, y: jnp.ndarray, cfg: DecoderConfig):
    """A Kimi Delta Attention layer's input projections of normed
    activations ``y`` [B, S, dim], in ``gdn_project``'s places: the convs'
    input ``q | k | v`` [B, S, 3 heads x head dim] AS PROJECTED (bfloat16:
    what the conv window caches), the output gate's logits ``z = (y W_ga)
    W_gb`` [B, S, heads x head dim], the raw ``b`` [B, S, heads] and the raw
    decay ``a = (y W_fa) W_fb`` [B, S, heads x head dim], float32."""
    f32 = jnp.float32
    return (cm.dense(lp["kda_qkv"], y),
            cm.dense(lp["kda_gb"], cm.dense(lp["kda_ga"], y)).astype(f32),
            cm.dense(lp["kda_b"], y).astype(f32),
            cm.dense(lp["kda_fb"], cm.dense(lp["kda_fa"], y)).astype(f32))


def kda_conv(lp: dict, ext: jnp.ndarray, s: int) -> jnp.ndarray:
    """The three depthwise causal convs (no bias) and their SiLU over ``ext``
    [B, taps - 1 + S, q | k | v channels], ``gdn_conv``'s convention: float32
    sums, output t reads ``ext[t : t + taps]``."""
    return _conv_silu(lp["kda_conv_w"], ext, s)


def kda_operands(lp: dict, conved: jnp.ndarray, b: jnp.ndarray, a: jnp.ndarray,
                 cfg: DecoderConfig, valid=None):
    """What the delta rule reads (``ops/kda_scan``), from the convs' output
    [B, S, channels], the raw ``b`` [B, S, heads] and the raw decay ``a``
    [B, S, heads x head dim], float32: queries and keys L2-normalised a head
    (``x rsqrt(sum x^2 + 1e-6)``), the queries times ``head dim ** -0.5``,
    and values, each [B, S, heads, head dim]; ``g = -exp(A_log) softplus(a +
    dt_bias)`` [B, S, heads, head dim] — a log-decay a head AND key channel —
    and ``beta = sigmoid(b)`` [B, S, heads]; both 0 where ``valid`` [B, S] is
    false, so that a padded position or an idle lane leaves the state as it
    is."""
    bsz, s = conved.shape[:2]
    h, d = cfg.kda_heads, cfg.kda_head_dim

    def unit(x):
        return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)

    q, k, v = (conved[..., i * h * d:(i + 1) * h * d].reshape(bsz, s, h, d)
               for i in range(3))
    q, k = unit(q) * d ** -0.5, unit(k)
    g = -jnp.exp(lp["kda_A_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
        a + lp["kda_dt_bias"].astype(jnp.float32)).reshape(bsz, s, h, d)
    beta = jax.nn.sigmoid(b)
    if valid is not None:
        g = jnp.where(valid[..., None, None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
    return q, k, v, g, beta


def kda_output(lp: dict, o: jnp.ndarray, z: jnp.ndarray, cfg: DecoderConfig,
               dtype) -> jnp.ndarray:
    """The delta rule's output ``o`` [B, S, heads, head dim] -> the mixer's
    [B, S, dim]: RMSNorm over each head's values times the norm's plain scale
    (one set for all heads), times ``sigmoid(z)``, ``o_proj``."""
    bsz, s = z.shape[:2]
    normed = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True)
                               + cfg.norm_eps) * lp["kda_norm"]["scale"]
    gated = normed.reshape(bsz, s, -1) * jax.nn.sigmoid(z)
    return cm.dense(lp["kda_out"], gated.astype(dtype))


def linear_mixer(cfg: DecoderConfig) -> tuple:
    """The four steps of the model's ``linear_attention`` layers — project,
    conv, operands, output, each pair of one signature — and the two forms
    of its delta rule (one token a lane; a chunk): Kimi Delta Attention's
    under a ``linear_attn_config``, else the Gated DeltaNet's. The one seam
    ``paged_decode`` calls either mixer through."""
    if cfg.kda:
        from arkflow_tpu.ops.kda_scan import kda_chunk_scan, kda_state_update

        return (kda_project, kda_conv, kda_operands, kda_output,
                kda_state_update, kda_chunk_scan)
    from arkflow_tpu.ops.gdn_scan import gdn_chunk_scan, gdn_state_update

    return (gdn_project, gdn_conv, gdn_operands, gdn_output,
            gdn_state_update, gdn_chunk_scan)


def _kda_block(lp: dict, y: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """The Kimi Delta Attention mixer over a whole block from a zero state
    and empty windows (``forward``): the chunked form in plain XLA, no cache."""
    from arkflow_tpu.ops.kda_scan import chunk_from

    bsz, s = y.shape[:2]
    u, z, b, a = kda_project(lp, y, cfg)
    ext = jnp.pad(u, ((0, 0), (cfg.kda_taps - 1, 0), (0, 0)))
    q, k, v, g, beta = kda_operands(lp, kda_conv(lp, ext, s), b, a, cfg)
    s0 = jnp.zeros((bsz, cfg.kda_heads, cfg.kda_head_dim, cfg.kda_head_dim),
                   jnp.float32)
    o, _ = chunk_from(s0, q, k, v, g, beta)
    return kda_output(lp, o, z, cfg, y.dtype)


def attn_out_gate(lp: dict, y: jnp.ndarray, attn: jnp.ndarray,
                  cfg: DecoderConfig) -> jnp.ndarray:
    """A per-head layer's attention output [B, S, H, dv] under the model's
    elementwise gate, ``attn * sigmoid(y W_g)`` in float32 (``y``: the
    block's normed input); as it is where the model has none."""
    if not cfg.out_gate:
        return attn
    gate = jax.nn.sigmoid(cm.dense(lp["w_out_gate"], y).astype(jnp.float32))
    return (attn.astype(jnp.float32) * gate.reshape(attn.shape)).astype(attn.dtype)


#: what a ``rope_scaling`` of type "yarn" states (DeepSeek-V3's keys)
_YARN_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
              "beta_slow", "mscale", "mscale_all_dim")


def _yarn_g(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 at factor <= 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_softmax_mult(scaling) -> float:
    """``g(mscale_all_dim)^2``: what YaRN multiplies a latent layer's
    softmax scale by (DeepSeek-V3's modelling code)."""
    y = dict(scaling)
    return _yarn_g(float(y["factor"]), float(y["mscale_all_dim"])) ** 2


def rope_frequencies(d: int, theta: float, scaling=None):
    """The rotary table's ``d / 2`` frequencies, and what its cos and sin
    are multiplied by: ``theta^(-2i/d)`` and 1 — or, under a YaRN
    ``scaling`` (``DecoderConfig.rope_scaling``), DeepSeek-V3's: with ``L``
    the original positions and ``corr(b) = d ln(L / (2 pi b)) / (2 ln
    theta)``, pairs below ``floor(corr(beta_fast))`` keep their frequency,
    pairs above ``ceil(corr(beta_slow))`` turn ``factor`` times slower and
    those between are interpolated linearly; cos and sin are scaled by
    ``g(mscale) / g(mscale_all_dim)``. The ONE table both rotations read."""
    # float: a published theta of 1e11 read as an int does not fit 32 bits
    freqs = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if scaling is None:
        return freqs, 1.0
    y = dict(scaling)
    factor, orig = float(y["factor"]), float(y["original_max_position_embeddings"])

    def corr(rotations: float) -> float:
        return d * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(float(theta)))

    low = max(math.floor(corr(float(y["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(y["beta_slow"]))), d // 2 - 1)
    if high == low:
        high += 0.001  # as the published code: no division by zero
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low),
                    0.0, 1.0)
    mult = _yarn_g(factor, float(y["mscale"])) / _yarn_g(
        factor, float(y["mscale_all_dim"]))
    return freqs / factor * ramp + freqs * (1.0 - ramp), mult


def _rope_angles(positions: jnp.ndarray, d: int, theta: float, scaling):
    """The rotation angles [B, S, d / 2] of ``positions`` [B, S], and what
    their cos and sin are multiplied by (``rope_frequencies``)."""
    freqs, mult = rope_frequencies(d, theta, scaling)
    return positions[..., None].astype(jnp.float32) * freqs, mult


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
          scaling=None) -> jnp.ndarray:
    """Rotary embedding. x: [B, S, H, Dh]; positions: [B, S]."""
    angles, mult = _rope_angles(positions, x.shape[-1], theta, scaling)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _rope_interleaved(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
                      scaling=None) -> jnp.ndarray:
    """Rotary embedding over the pairs (2i, 2i+1) (``rope_interleave``).
    x: [B, S, ..., D]; positions: [B, S]."""
    d = x.shape[-1]
    angles, mult = _rope_angles(positions, d, theta, scaling)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + (d // 2,))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if mult != 1.0:
        cos, sin = cos * mult, sin * mult
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (d // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _rescaled(x: jnp.ndarray, cfg: AttnSpec, rank: int) -> jnp.ndarray:
    """A normed latent times ``sqrt(dim / rank)`` under the rescale flag."""
    if not cfg.rescale:
        return x
    return (x.astype(jnp.float32) * (cfg.dim / rank) ** 0.5).astype(x.dtype)


def mla_query_latent(lp: dict, y: jnp.ndarray, cfg: AttnSpec):
    """The low-rank query's latent ``cq = RMSNorm(y W_qa)`` [B, S,
    q_lora_rank] (times ``sqrt(dim / rank)`` under the rescale flag), which
    the query projection and a full layer's indexer both read; None where
    the kind projects its queries from ``y`` directly."""
    if not cfg.q_lora_rank:
        return None
    return _rescaled(cm.rms_norm(lp["q_norm"], cm.dense(lp["wq_a"], y),
                                 cfg.norm_eps), cfg, cfg.q_lora_rank)


def mla_project(lp: dict, y: jnp.ndarray, cfg: AttnSpec, positions, cq=None):
    """The latent-attention projections of normed activations ``y``
    [B, S, dim] at ``positions`` [B, S]: per-head queries split into their
    no-position part [B, S, H, nope] and rotated rope part [B, S, H, rope],
    and — what the cache holds — the normed latent row ``c`` [B, S,
    kv_lora_rank] and the one rotated rope key ``k_r`` [B, S, rope] that
    every head shares (neither part rotated where ``cfg.rotate`` is false:
    ``mla_use_nope``). ``cfg`` is the layer kind's ``AttnSpec``; ``cq`` the
    query latent (``mla_query_latent``), if any."""
    b, s = positions.shape
    nope, rope = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    rot = _rope_interleaved
    q = cm.dense(lp["wq"], y if cq is None else cq).reshape(
        b, s, cfg.heads, nope + rope)
    kv = cm.dense(lp["wkv_a"], y)
    c = _rescaled(cm.rms_norm(lp["kv_norm"], kv[..., :cfg.kv_lora_rank],
                              cfg.norm_eps), cfg, cfg.kv_lora_rank)
    if not cfg.rotate:  # position-free: the shared key and q_r as projected
        return q[..., :nope], q[..., nope:], c, kv[..., cfg.kv_lora_rank:]
    k_r = rot(kv[..., None, cfg.kv_lora_rank:], positions, cfg.rope_theta,
              cfg.rope_scaling)[:, :, 0]
    return (q[..., :nope],
            rot(q[..., nope:], positions, cfg.rope_theta, cfg.rope_scaling), c, k_r)


def mla_head_gate(lp: dict, y: jnp.ndarray, cfg):
    """The headwise output gate ``sigmoid(y W_g)`` [B, S, H] of the block's
    normed input; None where the kind has no gate."""
    if not cfg.gate:
        return None
    return jax.nn.sigmoid(cm.dense(lp["w_head_gate"], y).astype(jnp.float32))


def _mla_up(lp: dict, cfg):
    """``kv_b_proj`` as its two per-head halves: W_uk [L, H, nope] (latent
    -> the keys' no-position part) and W_uv [L, H, v] (latent -> values)."""
    w = lp["wkv_b"]["w"].reshape(
        cfg.kv_lora_rank, cfg.heads, cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_absorb_query(lp: dict, q_nope: jnp.ndarray, cfg) -> jnp.ndarray:
    """Carry the queries into the latent space (``q_nope W_uk^T``): scored
    against the cached ``c`` they give ``q_nope . k_nope`` without ever
    expanding a key. [B, S, H, nope] -> [B, S, H, kv_lora_rank]."""
    w_uk, _ = _mla_up(lp, cfg)
    return jnp.einsum("bshn,lhn->bshl", q_nope, w_uk.astype(q_nope.dtype))


def _gated_out(lp: dict, o: jnp.ndarray, cfg, gate) -> jnp.ndarray:
    """Per-head outputs [B, S, H, v] -> [B, S, dim]: the headwise gate,
    if any, then ``o_proj``."""
    if gate is not None:
        o = (o.astype(jnp.float32) * gate[..., None]).astype(o.dtype)
    return cm.dense(lp["wo"], o.reshape(o.shape[:2] + (cfg.heads * cfg.v_head_dim,)))


def mla_output(lp: dict, o_lat: jnp.ndarray, cfg, gate=None) -> jnp.ndarray:
    """``sum p c`` per head [B, S, H, kv_lora_rank] -> the attention
    block's output [B, S, dim]: ``W_uv``, the gate, then ``o_proj``."""
    _, w_uv = _mla_up(lp, cfg)
    o = jnp.einsum("bshl,lhv->bshv", o_lat, w_uv.astype(o_lat.dtype))
    return _gated_out(lp, o, cfg, gate)


def mla_expanded_attention(lp: dict, q_nope, q_rope, c, k_r, mask,
                           cfg, gate=None) -> jnp.ndarray:
    """The published (expanded) form over a block that holds its own keys:
    ``[k_nope | v] = c W_kvb`` per head, the shared ``k_r`` appended to
    every head's key, softmax over ``q . k / sqrt(nope + rope)``. Used by
    the full forward and the one-shot prefill; the paged paths run the
    absorbed form against the cache. Returns [B, S, dim]."""
    w_uk, w_uv = _mla_up(lp, cfg)
    k_nope = jnp.einsum("bsl,lhn->bshn", c, w_uk.astype(c.dtype))
    v = jnp.einsum("bsl,lhv->bshv", c, w_uv.astype(c.dtype))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r[:, :, None, :], k_nope.shape[:3] + k_r.shape[-1:])],
        axis=-1)
    if cfg.rope_scaling is None:
        return _gated_out(lp, cm.attention(q, k, v, mask), cfg, gate)
    return _gated_out(lp, cm.attention(q, k, v, mask, scale=cfg.softmax_scale),
                      cfg, gate)


def index_project(lp: dict, y: jnp.ndarray, cq: jnp.ndarray, cfg, positions):
    """A full layer's indexer over normed activations ``y`` and the query
    latent ``cq`` (DeepSeek-V3.2's lightning indexer): index queries
    ``q_i = cq W`` [B, S, Hi, Di], the ONE index key a token ``k_i =
    LayerNorm(y W_k)`` [B, S, Di] (what the cache holds beside the latent
    row) — rope on the first ``qk_rope_head_dim`` of the Di dims of both, in
    split halves — and the heads' weights ``w = y W_w / sqrt(Hi Di)``
    [B, S, Hi]. All float32 at ``highest`` precision from float32 leaves:
    the indexer selects, as the router does."""
    b, s = positions.shape
    hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    f32 = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    yf = y.astype(jnp.float32)
    q_i = f32(cq.astype(jnp.float32), lp["index_wq"]["w"].astype(jnp.float32)
              ).reshape(b, s, hi, di)
    k_i = cm.layer_norm(lp["index_k_norm"],
                        f32(yf, lp["index_wk"]["w"].astype(jnp.float32)),
                        cfg.norm_eps)[:, :, None, :]
    q_i, k_i = (jnp.concatenate(
        [_rope(t[..., :rope], positions, cfg.rope_theta), t[..., rope:]],
        axis=-1) for t in (q_i, k_i))
    w = f32(yf, lp["index_w"]["w"].astype(jnp.float32)) * (hi * di) ** -0.5
    return q_i, k_i[:, :, 0], w


def index_scores(q_i, w, k_i) -> jnp.ndarray:
    """``I(t, s) = sum_j w_j(t) relu(q_i_j(t) . k_i(s))``: [B, S, Hi, Di],
    [B, S, Hi], keys [B, K, Di] -> float32 [B, S, K]. The product is of
    bfloat16 operands (the cache holds index keys in bfloat16) accumulated
    in float32."""
    dots = jnp.einsum("bshd,bkd->bshk", q_i.astype(jnp.bfloat16),
                      k_i.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32)
    return jnp.einsum("bshk,bsh->bsk", jax.nn.relu(dots), w)


def index_mask(scores, positions, key_pos, topk: int):
    """The allowed set of each query as a mask over the keys: of the keys at
    ``key_pos`` [K] (or [B, K]) not after the query (``positions`` [B, S]),
    the ``topk`` of largest score [B, S, K] — all of them while there are no
    more than ``topk``. Exact (a full sort; ties keep the earlier key)."""
    causal = jnp.reshape(key_pos, (-1, 1, scores.shape[-1])) <= positions[..., None]
    if scores.shape[-1] <= topk:
        return causal
    masked = jnp.where(causal, scores, -jnp.inf)
    _, idx = jax.lax.top_k(masked, topk)
    chosen = jnp.zeros(scores.shape, bool)
    chosen = jnp.put_along_axis(chosen, idx, True, axis=-1, inplace=False)
    return chosen & causal


def route_topk(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, token_mask=None,
               lanes: int = 0):
    """The router of one expert layer over tokens ``y`` [T, dim]: float32
    scores — ``scoring_func``: sigmoid of each logit, or softmax over all the
    experts' — (the product at ``highest`` precision — on a TPU a float32
    product otherwise runs in bfloat16 passes, and a 6th-against-7th choice
    is decided in the fourth decimal), the top ``num_experts_per_tok`` of
    ``score + router_bias`` chosen (of the scores alone where the layer has
    no selection bias: ``topk_method`` greedy), weighed by the unbiased
    scores (normalised, times ``routed_scaling_factor``).

    Returns the combine weights [T, held + shared] float32 — routed weights
    in their experts' columns (of the experts HELD here, ``cfg.held``: the
    weights are normalised over all the chosen, and a choice of an absent
    expert has no column), 1 in the shared experts' (``shared_expert_gate``:
    ``sigmoid(y w_sg)``, the token's own) — and the layer's load
    [E] int32 (tokens routed to each of ALL the experts). Tokens that
    ``token_mask`` excludes (inactive lanes, padding) have an all-zero row:
    they route nowhere and count nowhere. ``lanes`` > 0 (a fused step's
    block): the load by row range, [2, E] — of the first ``lanes`` tokens
    (the decode step's), then of the rest (the chunk's); their sum is what
    the block hit."""
    e, k = cfg.n_routed_experts, cfg.num_experts_per_tok
    score = jax.nn.softmax if cfg.scoring_func == "softmax" else jax.nn.sigmoid
    scores = score(jnp.dot(
        y.astype(jnp.float32), lp["router"]["w"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    biased = scores
    if "router_bias" in lp:
        biased = scores + lp["router_bias"].astype(jnp.float32)
    _, idx = jax.lax.top_k(biased, k)
    w = jnp.take_along_axis(scores, idx, axis=-1)                 # [T, k]
    w = (w / (w.sum(axis=-1, keepdims=True) + cfg.norm_topk_eps)
         * cfg.routed_scaling_factor)
    chosen = jax.nn.one_hot(idx, e, dtype=jnp.float32)            # [T, k, E]
    live = (jnp.ones(y.shape[:1], jnp.float32) if token_mask is None
            else token_mask.reshape(-1).astype(jnp.float32))
    assign = chosen.sum(axis=1) * live[:, None]                   # [T, E] 0/1
    cw = jnp.einsum("tk,tke->te", w, chosen) * live[:, None]
    if cfg.experts_held is not None:
        first, count = cfg.held
        cw = cw[:, first:first + count]
    shared = jnp.broadcast_to(live[:, None], (y.shape[0], cfg.shared_stack))
    if cfg.shared_expert_gate:
        shared = shared * jax.nn.sigmoid(jnp.dot(
            y.astype(jnp.float32), lp["shared_gate"]["w"].astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST))
    cw = jnp.concatenate([cw, shared], axis=-1)
    load = assign.sum(axis=0) if not lanes else jnp.stack(
        [assign[:lanes].sum(axis=0), assign[lanes:].sum(axis=0)])
    return cw, load.astype(jnp.int32)


def expert_products(cfg: DecoderConfig) -> tuple:
    """(the kernel product, its plain-XLA twin, an expert's leaves in the
    products' operand order): gate | up | down for a SwiGLU expert, up | down
    for a two-matrix one (``mlp_hidden_act`` relu2)."""
    from arkflow_tpu.ops import moe_experts as me

    if cfg.relu2:
        return me.moe_expert_relu2, me.expert_relu2_dense, ("w_up", "w_down")
    return me.moe_expert_swiglu, me.expert_swiglu_dense, ("w_gate", "w_up", "w_down")


def routed_mlp(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, token_mask=None,
               kernel: bool = False, interpret: bool = False, stacked=None,
               lanes: int = 0):
    """Routed + shared experts over ``y`` [B, S, dim] -> (output [B, S,
    dim], load [E] — by row range, [2, E], where ``lanes`` > 0:
    ``route_topk``). ``kernel`` runs the products through the Pallas kernel
    that reads only the experts hit (``ops/moe_experts``); otherwise plain
    XLA over every expert. The experts are ``lp["experts"]``, or — from a
    layer loop that must not slice them — ``stacked = (experts of the whole
    stack, this layer's index)``."""
    b, s, d = y.shape
    yf = y.reshape(b * s, d)
    cw, load = route_topk(lp, yf, cfg, token_mask,
                          **({"lanes": lanes} if lanes else {}))
    ex, layer = stacked if stacked is not None else (lp["experts"], None)
    product, dense, names = expert_products(cfg)
    if kernel:
        out = product(yf, cw, *(ex[n] for n in names), layer, interpret=interpret)
    else:
        if layer is not None:
            ex = jax.tree_util.tree_map(lambda a: a[layer], ex)
        out = dense(yf, cw, *(ex[n] for n in names))
    return out.reshape(b, s, d), load


def moe_step_stats(loads: jnp.ndarray, held=None) -> jnp.ndarray:
    """The three counters a serving step reports, from the expert layers'
    loads [layers, E]: (token, expert) pairs routed (summed over layers),
    distinct experts hit (summed over layers) and the largest expert's load
    (over layers). int32 [3]. With ``held`` = (first, count) — a chip that
    holds a share — the experts hit and the largest load are of the experts
    held (what the step computes), the pairs still of all, and a fourth
    counter follows: the pairs routed to the experts held. int32 [4]."""
    if held is None:
        return jnp.stack([loads.sum(), (loads > 0).sum(), loads.max()]).astype(jnp.int32)
    here = loads[:, held[0]:held[0] + held[1]]
    return jnp.stack([loads.sum(), (here > 0).sum(), here.max(),
                      here.sum()]).astype(jnp.int32)


def _moe_mlp(lp: dict, y: jnp.ndarray, cfg: DecoderConfig,
             token_mask=None) -> jnp.ndarray:
    """Switch-style top-1 MoE SwiGLU with capacity-based dispatch/combine.

    Each token routes to its top expert; tokens queue into per-expert capacity
    slots (cumsum position) and overflow drops to zero output. Compute is
    dispatch -> per-expert SwiGLU on [E, C, D] -> combine, so FLOPs scale with
    ``tokens * capacity_factor`` regardless of expert count, and GSPMD shards
    the E dim over the "ep" mesh axis (param specs) — the dispatch/combine
    einsums become the all-to-all.

    ``token_mask`` ([B, S] bool/int) excludes tokens (right padding, inactive
    serving lanes) from routing entirely: they consume NO expert capacity and
    produce zero MLP output — otherwise one row's padding could evict another
    row's real tokens from a full expert queue.
    """
    import math

    ex = lp["experts"]
    dtype = y.dtype
    b, s, d = y.shape
    e = ex["w_gate"].shape[0]
    tokens = b * s
    capacity = max(1, math.ceil(tokens / e * cfg.capacity_factor))

    yf = y.reshape(tokens, d)
    router_logits = cm.dense(lp["router"], yf, dtype=jnp.float32)  # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top = jnp.argmax(probs, axis=-1)  # [T]
    weight = jnp.take_along_axis(probs, top[:, None], axis=-1)[:, 0]  # [T]
    expert_onehot = jax.nn.one_hot(top, e, dtype=jnp.float32)  # [T, E]
    if token_mask is not None:
        expert_onehot = expert_onehot * token_mask.reshape(tokens, 1).astype(jnp.float32)
    # position of each token in its expert's queue: the routed column holds
    # position+1, others 0; sum over E then subtract 1
    pos_plus1 = (jnp.cumsum(expert_onehot, axis=0) * expert_onehot).sum(axis=-1)
    pos_idx = pos_plus1.astype(jnp.int32) - 1  # [T]
    keep = (pos_idx >= 0) & (pos_idx < capacity)
    slot_onehot = jax.nn.one_hot(pos_idx, capacity, dtype=jnp.float32) * keep[:, None]
    dispatch = jnp.einsum("te,tc->tec", expert_onehot, slot_onehot)  # [T, E, C]

    expert_in = jnp.einsum("tec,td->ecd", dispatch.astype(dtype), yf.astype(dtype))
    gate = jnp.einsum("ecd,edf->ecf", expert_in, ex["w_gate"].astype(dtype))
    up = jnp.einsum("ecd,edf->ecf", expert_in, ex["w_up"].astype(dtype))
    act = jax.nn.silu(gate.astype(jnp.float32)).astype(dtype) * up
    expert_out = jnp.einsum("ecf,efd->ecd", act, ex["w_down"].astype(dtype))

    combine = dispatch * weight[:, None, None]  # routing prob folded in
    out = jnp.einsum("tec,ecd->td", combine.astype(jnp.float32),
                     expert_out.astype(jnp.float32))

    # Switch aux stats: f_e = fraction of tokens routed to expert e, P_e =
    # mean router prob; lb = E * sum(f*P) is minimized by uniform routing.
    # z = mean(logsumexp(logits)^2) keeps router logits small.
    frac = expert_onehot.mean(axis=0)
    mean_prob = probs.mean(axis=0)
    lb = e * jnp.sum(frac * mean_prob)
    z = jnp.mean(jax.scipy.special.logsumexp(router_logits, axis=-1) ** 2)
    return out.reshape(b, s, d).astype(dtype), (lb, z)


def _attention_block(lp: dict, x: jnp.ndarray, cfg: DecoderConfig, positions,
                     causal=None, ring_attn=None, kind: str = FULL) -> jnp.ndarray:
    """Shared pre-norm GQA attention block (rope, kv-head repeat, residual).

    ``ring_attn`` substitutes the sp-ring kernel for plain masked attention.
    Used by forward() and the pipeline-parallel stage apply — one source of
    truth for the layer math. A sliding layer (``kind``) attends the last
    ``sliding_window`` positions under ``causal``, at its kind's sizes."""
    b, s = positions.shape
    sp = cfg.gqa(kind)
    group = cfg.heads // sp.kv_heads
    y = _norm(lp["attn_norm"], x, cfg)
    q, k, v = qkv_project(lp, y, cfg, kind)
    q, k = qk_positioned(lp, q, k, cfg, positions, kind)
    if sp.window:
        causal = causal & (positions[:, None, None, :]
                           > positions[:, None, :, None] - sp.window)
    if cfg.eva:
        k, v, causal = eva_keys(lp, k, v, cfg, positions)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    if ring_attn is not None:
        attn = ring_attn(q, k, v)
    else:
        attn = cm.attention(q, k, v, causal, sink=lp.get("attn_sink"))
    attn = attn_out_gate(lp, y, attn, cfg)
    out = _scaled(cm.dense(lp["wo"], attn.reshape(b, s, cfg.heads * sp.dv)),
                  cfg.attention_out_multiplier)
    if cfg.hybrid:  # the parallel mixer: one norm feeds both, one residual
        out = out + _mixer_block(lp, y, cfg)
    return x + out


def eva_keys(lp: dict, k, v, cfg: DecoderConfig, positions):
    """What a block of EVA queries at ``positions`` [B, S] = 0..S-1 attends,
    from the block's own rotated keys ``k`` and values ``v`` [B, S, kv heads,
    width]: (keys, values, mask) with one SUMMARY row for every chunk of
    every closed window first (``ops/eva_summarise``), then the exact rows.
    Query t sees the summaries of the windows before its own and the exact
    rows of its own window up to itself: a key is counted once."""
    from arkflow_tpu.ops.eva_summarise import eva_summarise_plain

    w, c = cfg.window_size, cfg.chunk_size
    s = positions.shape[1]
    closed = (s // w) * w            # tokens of whole windows: what can close
    qw = positions[:, None, :, None] // w                          # [B,1,S,1]
    kpos = positions[:, None, None, :]
    exact = (kpos // w == qw) & (kpos <= positions[:, None, :, None])
    if not closed:
        return k, v, exact
    ks, vs = eva_summarise_plain(k[:, :closed], v[:, :closed], lp["eva_phi"],
                                 lp["eva_mu"], c)
    chunk_window = (jnp.arange(closed // c) * c // w)[None, None, None, :]
    mask = jnp.concatenate(
        [jnp.broadcast_to(chunk_window < qw, exact.shape[:3] + (closed // c,)),
         exact], axis=-1)
    return (jnp.concatenate([ks, k], axis=1), jnp.concatenate([vs, v], axis=1),
            mask)


def qkv_project(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, kind: str = FULL):
    """Per-head queries [B, S, H, dk], keys [B, S, KV, dk] and values [B, S,
    KV, dv] of normed activations ``y`` [B, S, dim] at the sizes of the
    layer's ``kind``, before the rotary embedding: the block's input and
    its keys times their multipliers where the model states any, the values
    times ``attention_value_scale``."""
    b, s = y.shape[:2]
    sp = cfg.gqa(kind)
    y = _scaled(y, cfg.attention_in_multiplier)
    q = cm.dense(lp["wq"], y).reshape(b, s, cfg.heads, sp.dk)
    k = _scaled(cm.dense(lp["wk"], y), cfg.key_multiplier).reshape(
        b, s, sp.kv_heads, sp.dk)
    v = _scaled(cm.dense(lp["wv"], y), cfg.attention_value_scale)
    return q, k, v.reshape(b, s, sp.kv_heads, sp.dv)


def qk_positioned(lp: dict, q, k, cfg: DecoderConfig, positions, kind: str = FULL):
    """A per-head layer's queries and keys [B, S, heads, dk] as attention
    scores them: each head normed where the model has ``qk_norm`` (float32
    statistics, the layer's own scales), then the rotary embedding at
    ``positions`` and the kind's base over the first ``rotary`` values of a
    head (all of them without ``partial_rotary_factor``; the rest pass as
    they are) — on every layer, or with ``full_attention_rope`` false on
    the sliding layers only."""
    sp = cfg.gqa(kind)
    if cfg.qk_norm:
        q = _norm(lp["q_head_norm"], q, cfg)
        k = _norm(lp["k_head_norm"], k, cfg)
    if sp.rotary == sp.dk:
        q = _rope(q, positions, sp.rope_theta)
        k = _rope(k, positions, sp.rope_theta)
    elif sp.rotary:
        q, k = (jnp.concatenate(
            [_rope(t[..., :sp.rotary], positions, sp.rope_theta),
             t[..., sp.rotary:]], axis=-1) for t in (q, k))
    return q, k


def _mlp(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, token_mask=None) -> jnp.ndarray:
    """Dense SwiGLU or Switch MoE, depending on cfg (aux stats dropped) —
    the shared MLP for the incremental-decode paths, where the aux loss is
    irrelevant."""
    if cfg.num_experts > 1:
        out, _aux = _moe_mlp(lp, y, cfg, token_mask=token_mask)
        return out
    gate_mult, out_mult = cfg.mlp_multipliers
    pre = cm.dense(lp["w_gate"], y).astype(jnp.float32)
    gate = jax.nn.silu(pre if gate_mult == 1.0 else pre * gate_mult).astype(y.dtype)
    return _scaled(cm.dense(lp["w_down"], gate * cm.dense(lp["w_up"], y)), out_mult)


def lm_logits(params: dict, x: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """The final norm and the output head: [..., dim] -> float32 [..., vocab]
    (times ``lm_head_multiplier`` where the model states one)."""
    x = _norm(params["norm_out"], x, cfg)
    if cfg.fp32_logits:  # the product accumulates AND stays float32
        return _head_product(params["lm_head"], x)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    return logits if cfg.lm_head_multiplier == 1.0 else logits * cfg.lm_head_multiplier


def _head_product(head: dict, x: jnp.ndarray) -> jnp.ndarray:
    """``fp32_logits``: operands in the dtype the head is held in (bfloat16
    as served, on the MXU), the result accumulated and kept float32."""
    return jnp.dot(x.astype(head["w"].dtype), head["w"],
                   preferred_element_type=jnp.float32)


def pred_logits(params: dict, x: jnp.ndarray, cfg: DecoderConfig) -> jnp.ndarray:
    """Every prediction head's logits, [..., dim] -> float32 [...,
    num_pred_heads, vocab]: head ``m`` scores the token ``m + 1`` ahead; head
    0 is ``lm_logits``, the others the draft heads (``pred_heads``)."""
    first = lm_logits(params, x, cfg)[..., None, :]
    if cfg.num_pred_heads == 1:
        return first
    rest = _head_product(params["pred_heads"], _norm(params["norm_out"], x, cfg))
    return jnp.concatenate([first, rest.reshape(
        *rest.shape[:-1], cfg.num_pred_heads - 1, cfg.vocab_size)], axis=-2)


def _shard_act(x, axes):
    """Constrain [B, S, ...] activations to (dp, sp) when a mesh is active."""
    if not axes:
        return x
    spec = P(axes.get("dp"), axes.get("sp"), *([None] * (x.ndim - 2)))
    try:
        return jax.lax.with_sharding_constraint(x, spec)
    except (ValueError, RuntimeError):
        return x  # no mesh in scope (single-chip eager/test path)


def forward(params: dict, cfg: DecoderConfig, input_ids, *, axes=None, mesh=None,
            return_aux: bool = False, pred_heads: bool = False):
    """[B, S] ids -> [B, S, vocab] float32 logits (causal); ``pred_heads``:
    every prediction head's, [B, S, num_pred_heads, vocab] (``pred_logits``).

    With ``cfg.use_ring_attention`` and a mesh carrying an ``sp`` axis, the
    attention core runs as an explicit K/V ring over sequence shards
    (arkflow_tpu.parallel.ring_attention) instead of GSPMD's default
    all-gather — O(S/n) attention memory per chip for long context.
    """
    axes = axes or {}
    b, s = input_ids.shape
    if cfg.latent:
        return _forward_latent(params, cfg, input_ids, axes, return_aux)
    x = _scaled(cm.embedding(params["embed"], input_ids), cfg.embedding_multiplier)
    if cfg.fp32_skip_add:  # every sub-layer's output adds into float32
        x = x.astype(jnp.float32)
    x = _shard_act(x, axes)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None, :, :]

    ring_attn = None
    if cfg.use_ring_attention and mesh is not None and axes.get("sp"):
        from arkflow_tpu.parallel.ring_attention import make_ring_attention_spec

        ring_attn = make_ring_attention_spec(
            mesh, sp_axis=axes["sp"], batch_axis=axes.get("dp"),
            head_axis=axes.get("tp"), causal=True,
        )

    def make_layer(routed: bool, kind: str):
        def layer(x, lp):
            if cfg.one_mixer:  # one mixer a block, nothing after it
                if kind == FULL:
                    x = _attention_block(lp, x, cfg, positions, causal, None, kind)
                else:
                    y = _norm(lp["attn_norm"], x, cfg)
                    x = x + (_mixer_block(lp, y, cfg) if kind == MAMBA
                             else routed_mlp(lp, y, cfg)[0])
                return _shard_act(x, axes), (jnp.zeros((), jnp.float32),) * 2
            if kind == CONV:  # from the sequence's start: zeros before it
                x = x + short_conv(
                    lp, cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps), cfg)[0]
            elif kind == LINEAR:  # from a zero state and an empty window
                x = x + _gdn_block(lp, _norm(lp["attn_norm"], x, cfg), cfg)
            else:
                x = _attention_block(lp, x, cfg, positions, causal, ring_attn,
                                     kind)
            x = _shard_act(x, axes)
            y = _norm(lp["mlp_norm"], x, cfg)
            aux = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
            if cfg.num_experts > 1:
                moe_out, aux = _moe_mlp(lp, y, cfg)
                x = x + moe_out
            elif routed:  # plain XLA over every expert held; dropless: no aux
                x = x + routed_mlp(lp, y, cfg)[0]
            else:
                x = x + _mlp(lp, y, cfg)
            return _shard_act(x, axes), aux
        # prevent_cse=False: scan already isolates iterations, and the default
        # optimization barriers would block XLA fusion in the backward pass
        return jax.checkpoint(layer, prevent_cse=False) if cfg.remat else layer

    # one scan a run of layers of one shape and kind (``layer_runs``): a
    # model without routed experts or a pattern has ONE, over ``layers``
    aux = []
    for stack, routed, kind, _ in layer_stacks(params, cfg):
        x, run_aux = jax.lax.scan(make_layer(routed, kind), x, stack)
        aux.append(run_aux)
    lb_per_layer, z_per_layer = (jnp.concatenate(a) if len(aux) > 1 else a[0]
                                 for a in zip(*aux))
    logits = (pred_logits if pred_heads else lm_logits)(params, x, cfg)
    if return_aux:
        return logits, {"load_balance": lb_per_layer.mean(), "router_z": z_per_layer.mean()}
    return logits


def _forward_latent(params: dict, cfg: DecoderConfig, input_ids, axes: dict,
                    return_aux: bool):
    """``forward`` for a latent-attention model: the published (expanded)
    attention under each layer kind's mask (causal; the last
    ``sliding_window`` positions; the indexer's ``index_topk``), and one
    scan per layer run — the leading dense layers, then the expert layers
    (plain XLA over every expert held; dropless, so there is no auxiliary
    loss to carry and the aux terms are zero)."""
    b, s = input_ids.shape
    x = _shard_act(cm.embedding(params["embed"], input_ids), axes)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None, :, :]
    key_pos = jnp.arange(s)
    hc = cfg.hc_mult > 1

    def make_layer(routed: bool, sp: Optional[AttnSpec]):
        def streams_layer(x, lp):
            # x: the residual streams [B, S, n dim]; each sub-layer reads
            # their weighted sum and is written back into all of them
            u, h = hc_pre(lp["mhc_attn"], x, cfg)
            y = cm.rms_norm(lp["attn_norm"], u, cfg.norm_eps)
            x = hc_post(x, mla_expanded_attention(
                lp, *mla_project(lp, y, sp, positions, mla_query_latent(lp, y, sp)),
                causal, sp), h)
            u, h = hc_pre(lp["mhc_mlp"], x, cfg)
            y = cm.rms_norm(lp["mlp_norm"], u, cfg.norm_eps)
            return hc_post(x, routed_mlp(lp, y, cfg)[0] if routed
                           else _mlp(lp, y, cfg), h), None

        def layer(x, lp):
            y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
            cq = mla_query_latent(lp, y, sp)
            mask = causal
            if sp.window:
                mask = causal & (key_pos[None, :] > key_pos[:, None] - sp.window)
            if sp.index_topk:
                q_i, k_i, w = index_project(lp, y, cq, sp, positions)
                mask = index_mask(index_scores(q_i, w, k_i), positions,
                                  key_pos, sp.index_topk)[:, None]
            x = x + mla_expanded_attention(
                lp, *mla_project(lp, y, sp, positions, cq), mask, sp,
                mla_head_gate(lp, y, sp))
            x = _shard_act(x, axes)
            y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
            x = x + (routed_mlp(lp, y, cfg)[0] if routed else _mlp(lp, y, cfg))
            return _shard_act(x, axes), None
        def kda_layer(x, lp):  # from a zero state and empty windows
            x = x + _kda_block(lp, cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps), cfg)
            x = _shard_act(x, axes)
            y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
            x = x + (routed_mlp(lp, y, cfg)[0] if routed else _mlp(lp, y, cfg))
            return _shard_act(x, axes), None

        if hc:
            return streams_layer
        if sp is None:
            layer = kda_layer
        return jax.checkpoint(layer, prevent_cse=False) if cfg.remat else layer

    if hc:
        x = hc_expand(x, cfg)
    for stack, routed, kind, _ in layer_stacks(params, cfg):
        x, _ = jax.lax.scan(make_layer(
            routed, None if kind == LINEAR else cfg.attn(kind)), x, stack)
    if hc:
        x = hc_collapse(x, cfg)
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    if return_aux:
        zero = jnp.zeros((), jnp.float32)
        return logits, {"load_balance": zero, "router_z": zero}
    return logits


def apply(params: dict, cfg: DecoderConfig, *, input_ids, axes=None, mesh=None) -> dict:
    logits = forward(params, cfg, input_ids, axes=axes, mesh=mesh)
    return {"logits": logits, "next_token": jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)}


def loss_fn(params: dict, cfg: DecoderConfig, input_ids, targets, mask, *, axes=None, mesh=None):
    """Causal LM cross-entropy, mean over unmasked target tokens.

    MoE configs additionally carry the Switch load-balance aux loss and
    router z-loss (weighted by ``router_aux_weight`` / ``router_z_weight``)
    — without them top-1 routing collapses onto a single expert.
    """
    logits, aux = forward(params, cfg, input_ids, axes=axes, mesh=mesh, return_aux=True)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    maskf = mask.astype(jnp.float32)
    loss = -(ll * maskf).sum() / jnp.maximum(maskf.sum(), 1.0)
    if cfg.num_experts > 1:
        loss = (loss
                + cfg.router_aux_weight * aux["load_balance"]
                + cfg.router_z_weight * aux["router_z"])
    return loss


def make_train_step(cfg: DecoderConfig, optimizer, *, axes=None, mesh=None):
    """Returns ``train_step(params, opt_state, batch) -> (params, opt_state, loss)``.

    Jit this over a Mesh with sharded params/batch for the full
    dp x tp x sp distributed step.
    """

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, cfg, batch["input_ids"], batch["targets"], batch["mask"],
            axes=axes, mesh=mesh,
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        import optax

        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


def param_specs(cfg: DecoderConfig, axes: dict) -> dict:
    """Sharding layout: attention heads and FFN over ``tp``; expert dim over
    ``ep`` (MoE); embed/lm_head on the vocab dim; norms replicated."""
    if cfg.by_runs:
        # a model that stacks by runs (latent attention, routed experts, a
        # layer pattern) is served on one chip so far (tp / ep over latent
        # or window pages and routed experts is refused where a mesh is
        # built): every leaf is replicated, in the tree's own shape
        return jax.tree_util.tree_map(lambda _: P(), serve_dtypes(cfg))
    tp = axes.get("tp")
    ep = axes.get("ep")
    layer = {
        "attn_norm": {"scale": P(None)},
        "wq": {"w": P(None, tp)},
        "wk": {"w": P(None, tp)},
        "wv": {"w": P(None, tp)},
        "wo": {"w": P(tp, None)},
        "mlp_norm": {"scale": P(None)},
    }
    if cfg.num_experts > 1:
        layer["router"] = {"w": P(None, None)}
        layer["experts"] = {
            "w_gate": P(ep, None, tp),
            "w_up": P(ep, None, tp),
            "w_down": P(ep, tp, None),
        }
    else:
        layer["w_gate"] = {"w": P(None, tp)}
        layer["w_up"] = {"w": P(None, tp)}
        layer["w_down"] = {"w": P(tp, None)}
    if cfg.hybrid:
        layer.update(jax.tree_util.tree_map(lambda _: P(), _mixer_dtypes()))
    layer = jax.tree_util.tree_map(
        lambda sp: P(None, *sp), layer, is_leaf=lambda x: isinstance(x, P)
    )
    return {
        "embed": {"table": P(tp, None)},
        "norm_out": {"scale": P(None)},
        "lm_head": {"w": P(None, tp)},
        "layers": layer,
    }


def serve_dtypes(cfg: DecoderConfig) -> dict:
    """The dtype the forward consumes each leaf in, in ``param_specs``'s tree
    shape: bfloat16 wherever ``cm.dense`` / ``cm.embedding`` / the expert
    einsums cast at use, float32 for what is multiplied in float32 (norm
    scales, the MoE router). A serving path that places leaves in these
    dtypes once runs the same arithmetic with no per-step cast of a weight;
    a layer added to ``init`` states its dtype here
    (tests/test_generate_placed_params.py fails on a cast that is left)."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if cfg.by_runs:
        return _serve_dtypes_runs(cfg)
    layer = {
        "attn_norm": {"scale": f32},
        "wq": {"w": bf16},
        "wk": {"w": bf16},
        "wv": {"w": bf16},
        "wo": {"w": bf16},
        "mlp_norm": {"scale": f32},
    }
    if cfg.num_experts > 1:
        layer["router"] = {"w": f32}
        layer["experts"] = {"w_gate": bf16, "w_up": bf16, "w_down": bf16}
    else:
        layer["w_gate"] = {"w": bf16}
        layer["w_up"] = {"w": bf16}
        layer["w_down"] = {"w": bf16}
    if cfg.hybrid:
        layer.update(_mixer_dtypes())
    return {
        "embed": {"table": bf16},
        "norm_out": {"scale": f32},
        "lm_head": {"w": bf16},
        "layers": layer,
    }


def _mixer_dtypes() -> dict:
    """``serve_dtypes`` of the mixer's leaves: what the recurrence reads in
    float32 (A_log, D, dt_bias) and the gated norm's scale float32, the
    projections and the conv bfloat16."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    return {"ssm_in": {"w": bf16}, "ssm_conv": {"w": bf16, "b": bf16},
            "ssm_A_log": f32, "ssm_dt_bias": f32, "ssm_D": f32,
            "ssm_norm": {"scale": f32}, "ssm_out": {"w": bf16}}


def _attn_dtypes(cfg: DecoderConfig, kind: str) -> dict:
    """``serve_dtypes`` of what an attending layer of ``kind`` holds beside
    its query and output projections."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    sp = cfg.attn(kind)  # a per-head model's reads no latent extra
    if cfg.latent:
        layer = dict(wkv_a={"w": bf16}, kv_norm={"scale": f32},
                     wkv_b={"w": bf16})
    else:
        layer = dict(wk={"w": bf16}, wv={"w": bf16})
        if cfg.qk_norm:
            layer.update(q_head_norm={"scale": f32}, k_head_norm={"scale": f32})
        if cfg.gqa(kind).sink:
            layer["attn_sink"] = f32
        if cfg.out_gate:
            layer["w_out_gate"] = {"w": bf16}
        if cfg.eva:  # they shape a softmax's weights and a key: float32
            layer.update(eva_phi=f32, eva_mu=f32)
    if sp.q_lora_rank:
        layer.update(wq_a={"w": bf16}, q_norm={"scale": f32})
    if sp.gate:
        layer["w_head_gate"] = {"w": bf16}
    if sp.index_topk:
        layer.update(index_wq={"w": f32}, index_wk={"w": f32},
                     index_k_norm={"scale": f32, "bias": f32},
                     index_w={"w": f32})
    return layer


def _serve_dtypes_runs(cfg: DecoderConfig) -> dict:
    """``serve_dtypes`` for a model that stacks by runs: the router, its
    selection bias, the indexer (it selects too) and every norm scale (the
    latent and the per-head norms too) float32 — they are multiplied in
    float32 — and every other leaf bfloat16. One entry a layer stack the
    model has."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    out = {
        "embed": {"table": bf16},
        "norm_out": {"scale": f32},
        "lm_head": {"w": bf16},
    }
    if cfg.num_pred_heads > 1:
        out["pred_heads"] = {"w": bf16}
    for name, _, _, kind, routed, _ in layer_runs(cfg):
        layer = {"attn_norm": {"scale": f32}, "mlp_norm": {"scale": f32}}
        if cfg.one_mixer:  # one norm and one mixer a block
            del layer["mlp_norm"]
            if kind == MAMBA:
                layer.update(_mixer_dtypes())
            elif kind == MOE:
                layer.update(router={"w": f32}, router_bias=f32,
                             experts={"w_up": bf16, "w_down": bf16})
            else:
                layer.update(wq={"w": bf16}, wo={"w": bf16},
                             **_attn_dtypes(cfg, kind))
            out[name] = layer
            continue
        if kind == CONV:
            layer.update(conv_in={"w": bf16}, conv_w=bf16, conv_out={"w": bf16})
        elif kind == LINEAR and cfg.kda:
            layer.update(kda_qkv={"w": bf16}, kda_conv_w=bf16,
                         kda_fa={"w": bf16}, kda_fb={"w": bf16},
                         kda_b={"w": bf16}, kda_ga={"w": bf16},
                         kda_gb={"w": bf16}, kda_A_log=f32, kda_dt_bias=f32,
                         kda_norm={"scale": f32}, kda_out={"w": bf16})
        elif kind == LINEAR:  # what shapes the decay and the gated norm: f32
            layer.update(gdn_in={"w": bf16}, gdn_ba={"w": bf16}, gdn_conv_w=bf16,
                         gdn_A_log=f32, gdn_dt_bias=f32,
                         gdn_norm={"scale": f32}, gdn_out={"w": bf16})
        else:
            layer.update(wq={"w": bf16}, wo={"w": bf16},
                         **_attn_dtypes(cfg, kind))
            if cfg.hc_mult > 1:  # the mixing selects and normalises: float32
                layer.update({name: {"phi": f32, "b": f32, "alpha": f32}
                              for name in ("mhc_attn", "mhc_mlp")})
        if routed:
            layer.update(router={"w": f32},
                         experts={"w_gate": bf16, "w_up": bf16, "w_down": bf16})
            if cfg.topk_method == "noaux_tc":
                layer["router_bias"] = f32
            if cfg.shared_expert_gate:
                layer["shared_gate"] = {"w": f32}
        else:
            layer.update(w_gate={"w": bf16}, w_up={"w": bf16}, w_down={"w": bf16})
        out[name] = layer
    return out


def from_hf_state_dict(state: dict, cfg: DecoderConfig) -> dict:
    """Convert a HuggingFace ``LlamaForCausalLM`` state_dict (torch tensors —
    any dtype including bfloat16 — or numpy arrays) into this model's param
    pytree. Linear weights transpose from torch's [out, in] to [in, out]."""
    if cfg.kda:
        return _from_kimi_linear_state_dict(state, cfg)
    if cfg.num_experts > 1 or cfg.by_runs or cfg.hybrid:
        raise ValueError("from_hf_state_dict maps dense Llama checkpoints and "
                         "Kimi Linear's (linear_attn_config); other MoE, "
                         "latent-attention, layer-pattern and hybrid configs "
                         "unsupported")

    def t(name, transpose=False):
        return cm.hf_tensor(state, name, transpose)

    layers = []
    for i in range(cfg.layers):
        p = f"model.layers.{i}"
        layers.append(
            {
                "attn_norm": {"scale": t(f"{p}.input_layernorm.weight")},
                "wq": {"w": t(f"{p}.self_attn.q_proj.weight", transpose=True)},
                "wk": {"w": t(f"{p}.self_attn.k_proj.weight", transpose=True)},
                "wv": {"w": t(f"{p}.self_attn.v_proj.weight", transpose=True)},
                "wo": {"w": t(f"{p}.self_attn.o_proj.weight", transpose=True)},
                "mlp_norm": {"scale": t(f"{p}.post_attention_layernorm.weight")},
                "w_gate": {"w": t(f"{p}.mlp.gate_proj.weight", transpose=True)},
                "w_up": {"w": t(f"{p}.mlp.up_proj.weight", transpose=True)},
                "w_down": {"w": t(f"{p}.mlp.down_proj.weight", transpose=True)},
            }
        )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    lm_head = ("lm_head.weight" if "lm_head.weight" in state
               else "model.embed_tokens.weight")  # tied embeddings
    return {
        "embed": {"table": t("model.embed_tokens.weight")},
        "norm_out": {"scale": t("model.norm.weight")},
        "lm_head": {"w": t(lm_head, transpose=True)},
        "layers": stacked,
    }


def _from_kimi_linear_state_dict(state: dict, cfg: DecoderConfig) -> dict:
    """``from_hf_state_dict`` for a ``KimiLinearForCausalLM`` state_dict
    (``model_type: kimi_linear``), under its published key names: a KDA
    layer's ``self_attn.{q,k,v}_proj`` / ``{q,k,v}_conv1d`` ([channels, 1,
    taps]) / ``f_a_proj`` -> ``f_b_proj`` / ``b_proj`` / ``A_log`` /
    ``dt_bias`` / ``g_a_proj`` -> ``g_b_proj`` / ``o_norm`` / ``o_proj``; an
    MLA layer's ``q_proj`` / ``kv_a_proj_with_mqa`` / ``kv_a_layernorm`` /
    ``kv_b_proj`` / ``o_proj``; ``mlp.*`` on the dense layers and
    ``block_sparse_moe.gate`` (+ ``e_score_correction_bias``), ``experts.N.
    w1 | w3 | w2`` (gate, up, down) and ``shared_experts.*`` on the others.
    Of the routed experts those HELD here are read (``cfg.held``). The real
    checkpoint is not in the repository: held by a seeded state dict of
    these names (``tests/test_kda_mla_moe.py``)."""
    def t(name, transpose=False):
        return cm.hf_tensor(state, name, transpose)

    def lin(name):
        return {"w": t(f"{name}.weight", transpose=True)}

    def layer(i, kind, routed):
        p, a = f"model.layers.{i}", f"model.layers.{i}.self_attn"
        out = {"attn_norm": {"scale": t(f"{p}.input_layernorm.weight")},
               "mlp_norm": {"scale": t(f"{p}.post_attention_layernorm.weight")}}
        if kind == LINEAR:
            out.update(
                kda_qkv={"w": jnp.concatenate(
                    [t(f"{a}.{x}_proj.weight", True) for x in "qkv"], axis=1)},
                kda_conv_w=jnp.concatenate(
                    [t(f"{a}.{x}_conv1d.weight")[:, 0] for x in "qkv"], axis=0),
                kda_fa=lin(f"{a}.f_a_proj"), kda_fb=lin(f"{a}.f_b_proj"),
                kda_b=lin(f"{a}.b_proj"), kda_ga=lin(f"{a}.g_a_proj"),
                kda_gb=lin(f"{a}.g_b_proj"),
                kda_A_log=t(f"{a}.A_log").reshape(-1),
                kda_dt_bias=t(f"{a}.dt_bias").reshape(-1),
                kda_norm={"scale": t(f"{a}.o_norm.weight")},
                kda_out=lin(f"{a}.o_proj"))
        else:
            out.update(wq=lin(f"{a}.q_proj"), wkv_a=lin(f"{a}.kv_a_proj_with_mqa"),
                       kv_norm={"scale": t(f"{a}.kv_a_layernorm.weight")},
                       wkv_b=lin(f"{a}.kv_b_proj"), wo=lin(f"{a}.o_proj"))
        if not routed:
            out.update({ours: lin(f"{p}.mlp.{theirs}") for ours, theirs in (
                ("w_gate", "gate_proj"), ("w_up", "up_proj"), ("w_down", "down_proj"))})
            return out
        m = f"{p}.block_sparse_moe"
        first, count = cfg.held
        out.update(router=lin(f"{m}.gate"),
                   router_bias=t(f"{m}.gate.e_score_correction_bias"))
        out["experts"] = {
            ours: jnp.stack(
                [t(f"{m}.experts.{e}.{theirs}.weight", True)
                 for e in range(first, first + count)]
                + [t(f"{m}.shared_experts.{shared}.weight", True)])
            for ours, theirs, shared in (("w_gate", "w1", "gate_proj"),
                                         ("w_up", "w3", "up_proj"),
                                         ("w_down", "w2", "down_proj"))}
        return out

    if cfg.n_shared_experts != 1:
        raise ValueError("a Kimi Linear checkpoint has one shared expert a layer")
    params = {"embed": {"table": t("model.embed_tokens.weight")},
              "norm_out": {"scale": t("model.norm.weight")},
              "lm_head": {"w": t("lm_head.weight", transpose=True)}}
    stacks: dict = {}
    at = 0
    for name, first, stop, kind, routed, _ in layer_runs(cfg):
        stacks.setdefault(name, []).extend(
            layer(at + j, kind, routed) for j in range(stop - first))
        at += stop - first
    for name, stack in stacks.items():
        params[name] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stack)
    return params


# -- incremental decoding (batched summarization path) ---------------------

def select_token(logits, key=None, temperature: float = 0.0, top_k: int = 0):
    """Greedy (temperature<=0) or temperature/top-k categorical sampling.

    ``logits``: [B, V] float32; ``key`` required when sampling."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k > 0:
        # lax.top_k, not a full vocab sort: this runs once per decoded token
        k = min(int(top_k), scaled.shape[-1])  # permissive top_k degrades
        kth = jax.lax.top_k(scaled, k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def init_kv_cache(cfg: DecoderConfig, batch: int, max_len: int) -> dict:
    """Cache layout for ragged batched generation:

    - ``length``: scalar write cursor (same slot for every row).
    - ``lengths``: per-row true context length (RoPE positions; right-padding
      slots between ``lengths[i]`` and ``prompt_len`` are masked out of
      attention forever).
    - ``prompt_len``: width of the prefilled prompt block (0 = pure stepwise).
    """
    # the contiguous cache knows per-head K/V of one width, kept for a
    # request's life (``paged_decode`` imports this module, not the reverse)
    from arkflow_tpu.models.paged_decode import refuse

    refuse(cfg, "batch")
    dh = cfg.dh
    shape = (cfg.layers, batch, max_len, cfg.kv_heads, dh)
    return {
        "k": jnp.zeros(shape, jnp.bfloat16),
        "v": jnp.zeros(shape, jnp.bfloat16),
        "length": jnp.zeros((), jnp.int32),
        "lengths": jnp.zeros((batch,), jnp.int32),
        "prompt_len": jnp.zeros((), jnp.int32),
    }


def prefill(params: dict, cfg: DecoderConfig, input_ids, cache: dict,
            lengths=None, return_logits: bool = False) -> tuple[jnp.ndarray, dict]:
    """Fill a FRESH KV cache with right-padded prompts in one forward pass.

    input_ids: [B, T]; ``lengths``: [B] true prompt lengths (default: T for
    every row). Attention masks out each row's padding slots, and the greedy
    next token is read from position ``lengths[i] - 1`` — padded prompts
    condition only on real tokens. The cache write cursor lands at T;
    continuing from a non-empty cache is not supported (cursor must be 0).
    """
    b, t = input_ids.shape
    dh = cfg.dh
    group = cfg.heads // cfg.kv_heads
    if lengths is None:
        lengths = jnp.full((b,), t, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(t)[None, :], (b, t))
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    key_valid = (jnp.arange(t)[None, :] < lengths[:, None])[:, None, None, :]  # [B,1,1,T]
    mask = jnp.logical_and(causal, key_valid)
    token_mask = jnp.arange(t)[None, :] < lengths[:, None]  # [B, T] real tokens
    x = cm.embedding(params["embed"], input_ids)

    def layer(carry, lp):
        x, li = carry
        y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q = cm.dense(lp["wq"], y).reshape(b, t, cfg.heads, dh)
        k = cm.dense(lp["wk"], y).reshape(b, t, cfg.kv_heads, dh)
        v = cm.dense(lp["wv"], y).reshape(b, t, cfg.kv_heads, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"][li], k.astype(jnp.bfloat16), (0, 0, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"][li], v.astype(jnp.bfloat16), (0, 0, 0, 0)
        )
        kk = jnp.repeat(k, group, axis=2)
        vv = jnp.repeat(v, group, axis=2)
        attn = cm.attention(q, kk, vv, mask).reshape(b, t, cfg.heads * dh)
        x = x + cm.dense(lp["wo"], attn)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        x = x + _mlp(lp, y, cfg, token_mask=token_mask)
        return (x, li + 1), (k_cache, v_cache)

    (x, _), (ks, vs) = jax.lax.scan(layer, (x, 0), params["layers"])
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)  # [B, T, V]
    # read each row's logits at its true last token, not at padding
    last = jnp.clip(lengths - 1, 0, t - 1)
    last_logits = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
    new_cache = {
        "k": ks, "v": vs,
        "length": jnp.asarray(t, jnp.int32),
        "lengths": lengths,
        "prompt_len": jnp.asarray(t, jnp.int32),
    }
    if return_logits:
        return last_logits, new_cache
    return jnp.argmax(last_logits, axis=-1).astype(jnp.int32), new_cache


def decode_step(params: dict, cfg: DecoderConfig, token_ids, cache: dict,
                return_logits: bool = False) -> tuple[jnp.ndarray, dict]:
    """One token per sequence: [B, 1] ids + cache -> ([B] next ids, cache).

    Jittable with a static cache size; the python generation loop lives in
    the summarization processor.
    """
    b = token_ids.shape[0]
    dh = cfg.dh
    group = cfg.heads // cfg.kv_heads
    pos = cache["length"]  # scalar write cursor (shared slot)
    lengths = cache["lengths"]  # [B] true per-row context lengths (RoPE)
    prompt_len = cache["prompt_len"]
    max_len = cache["k"].shape[2]
    positions = lengths[:, None]
    x = cm.embedding(params["embed"], token_ids)

    # valid keys per row: real prompt tokens + the generated block (padding
    # slots between lengths[i] and prompt_len stay masked forever)
    ks_idx = jnp.arange(max_len)[None, :]
    valid = jnp.logical_or(
        ks_idx < lengths[:, None],
        jnp.logical_and(ks_idx >= prompt_len, ks_idx <= pos),
    )[:, None, None, :]

    def layer(carry, inputs):
        x, li = carry[0], carry[1]
        lp = inputs
        y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
        q = cm.dense(lp["wq"], y).reshape(b, 1, cfg.heads, dh)
        k = cm.dense(lp["wk"], y).reshape(b, 1, cfg.kv_heads, dh)
        v = cm.dense(lp["wv"], y).reshape(b, 1, cfg.kv_heads, dh)
        q = _rope(q, positions, cfg.rope_theta)
        k = _rope(k, positions, cfg.rope_theta)
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"][li], k.astype(jnp.bfloat16), (0, pos, 0, 0)
        )
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"][li], v.astype(jnp.bfloat16), (0, pos, 0, 0)
        )
        kk = jnp.repeat(k_cache, group, axis=2)
        vv = jnp.repeat(v_cache, group, axis=2)
        attn = cm.attention(q, kk, vv, valid).reshape(b, 1, cfg.heads * dh)
        x = x + cm.dense(lp["wo"], attn)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        x = x + _mlp(lp, y, cfg)
        return (x, li + 1), (k_cache, v_cache)

    (x, _), (ks, vs) = jax.lax.scan(layer, (x, 0), params["layers"])
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    new_cache = {
        "k": ks, "v": vs,
        "length": pos + 1,
        "lengths": lengths + 1,
        "prompt_len": prompt_len,
    }
    if return_logits:
        return logits[:, -1, :], new_cache
    return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32), new_cache


def generate(params: dict, cfg: DecoderConfig, input_ids, lengths,
             max_new_tokens: int, eos_id: int = 2,
             n_real=None, temperature: float = 0.0, top_k: int = 0,
             rng_key=None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Whole-sequence generation under one jit: prefill + a
    ``lax.while_loop`` decode with EOS early-exit. One device dispatch per
    batch instead of one per token — the difference between usable and
    unusable latency over a remote-TPU link.

    ``temperature<=0`` is greedy; otherwise temperature/top-k categorical
    sampling driven by ``rng_key`` (one split per step, deterministic for a
    fixed key). Returns (tokens [B, max_new_tokens] int32 zero-padded after
    EOS, counts [B] of real tokens per row).
    """
    b, t = input_ids.shape
    sampling = temperature > 0.0
    key = rng_key if rng_key is not None else jax.random.PRNGKey(0)
    cache = init_kv_cache(cfg, b, t + max_new_tokens)
    first, cache = prefill(params, cfg, input_ids, cache, lengths=lengths,
                           return_logits=True)
    key, sub = jax.random.split(key)
    nxt = select_token(first, sub, temperature if sampling else 0.0, top_k)
    out0 = jnp.zeros((b, max_new_tokens), jnp.int32)
    # batch-padding rows start done, so they don't gate the EOS early-exit
    done0 = (jnp.arange(b) >= n_real) if n_real is not None else jnp.zeros((b,), bool)
    counts0 = jnp.zeros((b,), jnp.int32)

    def cond(state):
        step, _nxt, _key, done, _counts, _cache, _out = state
        return jnp.logical_and(step < max_new_tokens, ~jnp.all(done))

    def body(state):
        step, nxt, key, done, counts, cache, out = state
        # decode at the TOP for steps >= 1 (step 0 uses the prefill token), so
        # the loop never pays a trailing forward pass after the final emission
        key, sub = jax.random.split(key)

        def decode(args):
            nxt, cache = args
            logits, cache = decode_step(params, cfg, nxt[:, None], cache,
                                        return_logits=True)
            return select_token(logits, sub, temperature if sampling else 0.0,
                                top_k), cache

        nxt, cache = jax.lax.cond(step > 0, decode, lambda args: args, (nxt, cache))
        is_eos = nxt == eos_id
        keep = jnp.logical_and(~done, ~is_eos)
        emit = jnp.where(keep, nxt, 0)
        out = jax.lax.dynamic_update_slice(out, emit[:, None], (0, step))
        counts = counts + keep.astype(jnp.int32)
        done = jnp.logical_or(done, is_eos)
        return step + 1, nxt, key, done, counts, cache, out

    _, _, _, _, counts, _, out = jax.lax.while_loop(
        cond, body, (0, nxt, key, done0, counts0, cache, out0)
    )
    return out, counts


def input_spec(cfg: DecoderConfig) -> dict:
    return {"input_ids": ("int32", ("seq",))}


register_model(
    ModelFamily(
        name="decoder_lm",
        make_config=DecoderConfig,
        init=init,
        apply=apply,
        input_spec=input_spec,
        param_specs=param_specs,
        extras={
            "serve_dtypes": serve_dtypes,
            "forward": forward,
            "loss_fn": loss_fn,
            "make_train_step": make_train_step,
            "llama3_8b": llama3_8b,
            "from_hf_state_dict": from_hf_state_dict,
            "init_kv_cache": init_kv_cache,
            "prefill": prefill,
            "decode_step": decode_step,
            "generate": generate,
        },
    )
)
