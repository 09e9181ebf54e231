"""Shared pure-JAX building blocks: params-as-pytrees, jittable applies.

Design rules (TPU-first):
- Params live in nested dicts; applies are pure functions -> trivially
  jittable, shardable with ``NamedSharding`` pytrees, no framework state.
- Compute dtype is bfloat16 (MXU-native); normalisation statistics and softmax
  run in float32 for stability; params are kept in float32 master copies and
  cast at use (standard mixed-precision recipe; a no-op where a serving path
  placed the leaf in its compute dtype already, as ``tpu_generate`` does).
- No Python control flow on data; recurrences use ``lax.scan``.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

Params = Any  # nested dict pytree


def dense_init(key, in_dim: int, out_dim: int, *, bias: bool = True) -> Params:
    w_key, _ = jax.random.split(key)
    scale = 1.0 / math.sqrt(in_dim)
    p = {"w": jax.random.uniform(w_key, (in_dim, out_dim), jnp.float32, -scale, scale)}
    if bias:
        p["b"] = jnp.zeros((out_dim,), jnp.float32)
    return p


def dense(p: Params, x: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    if "w_q" in p:  # W8A8 serving form (models/quantize.py): int8 on the MXU
        from arkflow_tpu.models.quantize import dense_w8a8

        return dense_w8a8(p, x, dtype)
    y = x.astype(dtype) @ p["w"].astype(dtype)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


def layer_norm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32), "bias": jnp.zeros((dim,), jnp.float32)}


def layer_norm(p: Params, x: jnp.ndarray, eps: float = 1e-12) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


def rms_norm_init(dim: int) -> Params:
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rms_norm(p: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * p["scale"]).astype(x.dtype)


def embedding_init(key, vocab: int, dim: int, scale: float = 0.02) -> Params:
    return {"table": jax.random.normal(key, (vocab, dim), jnp.float32) * scale}


def embedding(p: Params, ids: jnp.ndarray, dtype=jnp.bfloat16) -> jnp.ndarray:
    return p["table"].astype(dtype)[ids]


def gelu(x: jnp.ndarray) -> jnp.ndarray:
    return jax.nn.gelu(x, approximate=True)


def attention(q, k, v, mask=None, *, softmax_dtype=jnp.float32, sink=None,
              scale=None):
    """Batched multi-head attention core: [B, S, H, Dh] tensors (values may
    be of another width than queries and keys).

    Softmax in float32; matmuls in the input dtype (bfloat16) for the MXU.
    ``mask``: broadcastable to [B, H, Sq, Sk], True = attend. ``sink`` [H]:
    one logit a head that joins every softmax as one more column and is
    dropped after it: it takes probability and adds no value. ``scale``:
    what the scores are multiplied by where it is not ``Dh^-0.5``.
    """
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(softmax_dtype)
    scores = scores / math.sqrt(dh) if scale is None else scores * scale
    if mask is not None:
        scores = jnp.where(mask, scores, jnp.finfo(softmax_dtype).min)
    if sink is not None:
        col = jnp.broadcast_to(sink.astype(softmax_dtype)[None, :, None, None],
                               scores.shape[:3] + (1,))
        scores = jnp.concatenate([scores, col], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if sink is not None:
        probs = probs[..., :-1]
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def count_params(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


def hf_tensor(state: dict, name: str, transpose: bool = False) -> jnp.ndarray:
    """One HF state_dict entry (torch tensor — any dtype incl. bfloat16 — or
    numpy array) -> float32 jnp array, optionally transposed ([out,in] ->
    [in,out] for torch linear weights)."""
    import numpy as np

    v = state[name]
    if hasattr(v, "detach"):  # torch tensor; .float() first (numpy lacks bf16)
        v = v.detach().cpu().float().numpy()
    arr = np.asarray(v, dtype=np.float32)
    return jnp.asarray(arr.T if transpose else arr)
