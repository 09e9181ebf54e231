"""Ring attention: exact attention over sequence shards on a ring.

Long-context first-class path: the sequence axis is sharded over the ``sp``
mesh axis; each device holds Q/K/V chunks of shape [B, S/n, H, Dh] and the
K/V blocks rotate around the ring with ``lax.ppermute`` (one ICI hop per
step) while a streaming (online-softmax) accumulator folds each block in —
attention memory stays O(S/n) per chip and communication overlaps compute.
This is the blockwise/ring pattern referenced in SURVEY.md sections 2.7/5
(the reference engine has no model execution; its closest analog is window
buffers bounding context) expressed with XLA collectives instead of NCCL.

Numerics: scores/softmax accumulate in float32 regardless of input dtype;
causal masking uses global positions derived from the shard index.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG = -1e30


def _ring_attention_local(q, k, v, axis_name: str, causal: bool):
    """Runs inside shard_map: q/k/v local chunks [B, Sl, H, Dh]."""
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, sl, h, dh = q.shape
    scale = 1.0 / math.sqrt(dh)
    qf = q.astype(jnp.float32)

    q_pos = idx * sl + jnp.arange(sl)  # global positions of local queries

    def step(i, carry):
        o, m, l, k_cur, v_cur = carry
        src = (idx - i) % n  # whose K/V block we hold at this step
        scores = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32)) * scale
        if causal:
            k_pos = src * sl + jnp.arange(sl)
            allowed = k_pos[None, :] <= q_pos[:, None]  # [Sq, Sk]
            scores = jnp.where(allowed[None, None], scores, _NEG)
        m_new = jnp.maximum(m, scores.max(axis=-1))
        p = jnp.exp(scores - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_cur.astype(jnp.float32))
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return o_new, m_new, l_new, k_nxt, v_nxt

    o0 = jnp.zeros((b, h, sl, dh), jnp.float32)
    m0 = jnp.full((b, h, sl), _NEG, jnp.float32)
    l0 = jnp.zeros((b, h, sl), jnp.float32)
    o, m, l, _, _ = lax.fori_loop(0, n, step, (o0, m0, l0, k, v))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


def make_ring_attention_spec(mesh: Mesh, sp_axis: str = "sp",
                             batch_axis: str | None = None,
                             head_axis: str | None = None, causal: bool = False):
    """Ring attention for use inside a sharded model forward.

    Inputs/outputs are [B, S, H, Dh]: the sequence dim rings over ``sp_axis``;
    the batch dim may be dp-sharded (``batch_axis``) and the head dim
    tp-sharded (``head_axis``) — each tp shard rings only its own heads, so
    attention memory/FLOPs stay O(S/n_sp * H/n_tp) per chip.
    """
    spec = P(batch_axis, sp_axis, head_axis, None)
    return jax.shard_map(
        partial(_ring_attention_local, axis_name=sp_axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def make_ring_attention(mesh: Mesh, axis: str = "sp", causal: bool = False):
    """Jittable ring attention over ``mesh[axis]`` (sequence-sharded only)."""
    return make_ring_attention_spec(mesh, sp_axis=axis, causal=causal)


def reference_attention(q, k, v, causal: bool = False):
    """Unsharded reference for testing: [B, S, H, Dh]."""
    dh = q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    scores = scores / math.sqrt(dh)
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask[None, None], scores, _NEG)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)
