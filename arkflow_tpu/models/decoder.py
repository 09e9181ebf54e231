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

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from arkflow_tpu.models import common as cm
from arkflow_tpu.models.registry import ModelFamily, register_model


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


def llama3_8b() -> DecoderConfig:
    return DecoderConfig(
        vocab_size=128256, dim=4096, layers=32, heads=32, kv_heads=8,
        ffn=14336, max_seq=8192,
    )


def init(rng, cfg: DecoderConfig) -> dict:
    dh = cfg.dim // cfg.heads
    keys = iter(jax.random.split(rng, 4 + 7 * cfg.layers))
    params = {
        "embed": cm.embedding_init(next(keys), cfg.vocab_size, cfg.dim),
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
        params["layers"].append(layer)
    params["layers"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params["layers"])
    return params


def _rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotary embedding. x: [B, S, H, Dh]; positions: [B, S]."""
    dh = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, Dh/2]
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


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
                     causal=None, ring_attn=None) -> jnp.ndarray:
    """Shared pre-norm GQA attention block (rope, kv-head repeat, residual).

    ``ring_attn`` substitutes the sp-ring kernel for plain masked attention.
    Used by forward() and the pipeline-parallel stage apply — one source of
    truth for the layer math."""
    b, s = positions.shape
    dh = cfg.dim // cfg.heads
    group = cfg.heads // cfg.kv_heads
    y = cm.rms_norm(lp["attn_norm"], x, cfg.norm_eps)
    q = cm.dense(lp["wq"], y).reshape(b, s, cfg.heads, dh)
    k = cm.dense(lp["wk"], y).reshape(b, s, cfg.kv_heads, dh)
    v = cm.dense(lp["wv"], y).reshape(b, s, cfg.kv_heads, dh)
    q = _rope(q, positions, cfg.rope_theta)
    k = _rope(k, positions, cfg.rope_theta)
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    if ring_attn is not None:
        attn = ring_attn(q, k, v)
    else:
        attn = cm.attention(q, k, v, causal)
    return x + cm.dense(lp["wo"], attn.reshape(b, s, cfg.heads * dh))


def _mlp(lp: dict, y: jnp.ndarray, cfg: DecoderConfig, token_mask=None) -> jnp.ndarray:
    """Dense SwiGLU or Switch MoE, depending on cfg (aux stats dropped) —
    the shared MLP for the incremental-decode paths, where the aux loss is
    irrelevant."""
    if cfg.num_experts > 1:
        out, _aux = _moe_mlp(lp, y, cfg, token_mask=token_mask)
        return out
    gate = jax.nn.silu(cm.dense(lp["w_gate"], y).astype(jnp.float32)).astype(y.dtype)
    return cm.dense(lp["w_down"], gate * cm.dense(lp["w_up"], y))


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
            return_aux: bool = False):
    """[B, S] ids -> [B, S, vocab] float32 logits (causal).

    With ``cfg.use_ring_attention`` and a mesh carrying an ``sp`` axis, the
    attention core runs as an explicit K/V ring over sequence shards
    (arkflow_tpu.parallel.ring_attention) instead of GSPMD's default
    all-gather — O(S/n) attention memory per chip for long context.
    """
    axes = axes or {}
    b, s = input_ids.shape
    dh = cfg.dim // cfg.heads
    group = cfg.heads // cfg.kv_heads
    x = cm.embedding(params["embed"], input_ids)
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

    def layer(x, lp):
        x = _attention_block(lp, x, cfg, positions, causal, ring_attn)
        x = _shard_act(x, axes)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        if cfg.num_experts > 1:
            moe_out, aux = _moe_mlp(lp, y, cfg)
            x = x + moe_out
        else:
            gate = jax.nn.silu(cm.dense(lp["w_gate"], y).astype(jnp.float32)).astype(y.dtype)
            x = x + cm.dense(lp["w_down"], gate * cm.dense(lp["w_up"], y))
            aux = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32))
        return _shard_act(x, axes), aux

    # prevent_cse=False: scan already isolates iterations, and the default
    # optimization barriers would block XLA fusion in the backward pass
    scan_body = jax.checkpoint(layer, prevent_cse=False) if cfg.remat else layer
    x, (lb_per_layer, z_per_layer) = jax.lax.scan(scan_body, x, params["layers"])
    x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
    logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
    if return_aux:
        return logits, {"load_balance": lb_per_layer.mean(), "router_z": z_per_layer.mean()}
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
    return {
        "embed": {"table": bf16},
        "norm_out": {"scale": f32},
        "lm_head": {"w": bf16},
        "layers": layer,
    }


def from_hf_state_dict(state: dict, cfg: DecoderConfig) -> dict:
    """Convert a HuggingFace ``LlamaForCausalLM`` state_dict (torch tensors —
    any dtype including bfloat16 — or numpy arrays) into this model's param
    pytree. Linear weights transpose from torch's [out, in] to [in, out]."""
    if cfg.num_experts > 1:
        raise ValueError("from_hf_state_dict maps dense Llama checkpoints; MoE configs unsupported")

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
    dh = cfg.dim // cfg.heads
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
    dh = cfg.dim // cfg.heads
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
    dh = cfg.dim // cfg.heads
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


def pp_stage_fns(cfg: DecoderConfig):
    """Stage bodies for pipelined-parallel serving (parallel/pipeline.py
    ``make_pp_infer_step``): embed -> dense decoder block -> norm/lm_head.
    Mirrors ``forward``'s scan body (no mesh axes: pp streams whole
    activations stage-to-stage, never sharding them), so pp outputs match
    the single-device ``apply`` bitwise per row. MoE routes through ep and
    long context through sp/ring — not composed with pp, same as training."""
    from arkflow_tpu.errors import ConfigError

    if cfg.num_experts > 1:
        raise ConfigError("pipeline parallelism + MoE (ep) is not composed yet")
    if cfg.use_ring_attention:
        raise ConfigError("pipeline parallelism + ring attention is not composed yet")

    def pre(params: dict, inputs: dict):
        return cm.embedding(params["embed"], inputs["input_ids"]), {}

    def layer(lp: dict, x, aux: dict):
        b, s = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        causal = jnp.tril(jnp.ones((s, s), bool))[None, None, :, :]
        x = _attention_block(lp, x, cfg, positions, causal)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        gate = jax.nn.silu(cm.dense(lp["w_gate"], y).astype(jnp.float32)).astype(y.dtype)
        return x + cm.dense(lp["w_down"], gate * cm.dense(lp["w_up"], y))

    def post(params: dict, x, aux: dict):
        x = cm.rms_norm(params["norm_out"], x, cfg.norm_eps)
        logits = cm.dense(params["lm_head"], x).astype(jnp.float32)
        return {"logits": logits,
                "next_token": jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)}

    return pre, layer, post


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
            "pp_stage_fns": pp_stage_fns,
        },
    )
)
