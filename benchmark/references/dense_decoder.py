"""Plain reference for ``decoder_lm`` at dense GQA + SwiGLU + RoPE sizes (Mistral-7B: Jiang et al. 2023), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernels, no cache, no batching policy,
written from the published description and independent of the program's
model code. It reads only the program's parameter tree (the weights are the
program's, made from the seed). The rotary embedding pairs dimension ``i`` with ``i + d/2`` (the HF layout), as the program's does.

Random weights give near-tied outputs, so equality is asked only where the
reference decides (rules copied from ``chip_smoke.py``, PR 21).

``judge(ctx)`` is what the harness calls, after the drain, outside the
window. ``ctx`` carries the program's processor (for its tokenizer, and for
the generate path its placed float32 master weights), the configuration
file, the processor mapping as run, the pools of rows and what the sink
collected.
"""

from __future__ import annotations

import math

import numpy as np

_BF16_EPS = 2.0 ** -8
#: rows sampled for the comparison (each is one forward over up to
#: max_input + max_new_tokens positions)
SAMPLE_ROWS = 6


def logit_tolerance(ref_logits) -> float:
    """How far a bf16-served logit may sit from the float32 reference: 4
    bf16 ulps of the largest reference logit (copied from
    ``tpu/serving_core.bf16_logit_tolerance``)."""
    return 4 * _BF16_EPS * max(1.0, float(np.abs(np.asarray(ref_logits)).max()))


def _dense(p, x):
    import jax.numpy as jnp

    y = x @ p["w"].astype(jnp.float32)
    return y + p["b"].astype(jnp.float32) if "b" in p else y

# -- dense GQA + SwiGLU + RoPE decoder (Mistral-7B: Jiang et al. 2023) ---------


def _rms_norm(p, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * p["scale"]


def _rope(x, theta):
    import jax.numpy as jnp

    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def decoder_logits(params, input_ids, at, *, new: int, heads: int,
                   kv_heads: int, rope_theta: float, norm_eps: float):
    """[1, S] ids -> float32 logits [new, vocab] of the ``new`` positions
    from ``at`` on, causal. The final norm and the output head are applied
    to those positions only: the whole [S, vocab] table would be hundreds of
    MB a row. Layers are stacked on a leading axis of the tree."""
    import jax
    import jax.numpy as jnp

    b, s = input_ids.shape
    x = params["embed"]["table"][input_ids].astype(jnp.float32)
    dh = x.shape[-1] // heads
    group = heads // kv_heads
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]

    def layer(x, lp):
        y = _rms_norm(lp["attn_norm"], x, norm_eps)
        q = _rope(_dense(lp["wq"], y).reshape(b, s, heads, dh), rope_theta)
        k = _rope(_dense(lp["wk"], y).reshape(b, s, kv_heads, dh), rope_theta)
        v = _dense(lp["wv"], y).reshape(b, s, kv_heads, dh)
        k, v = jnp.repeat(k, group, 2), jnp.repeat(v, group, 2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        scores = jnp.where(causal, scores, -1e30)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + _dense(lp["wo"], attn.reshape(b, s, heads * dh))
        y = _rms_norm(lp["mlp_norm"], x, norm_eps)
        x = x + _dense(lp["w_down"],
                       jax.nn.silu(_dense(lp["w_gate"], y)) * _dense(lp["w_up"], y))
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    x = jax.lax.dynamic_slice_in_dim(x[0], at, new, axis=0)
    return _dense(params["lm_head"], _rms_norm(params["norm_out"], x, norm_eps))


def teacher_forced_logits(params, *, heads: int, kv_heads: int,
                          rope_theta: float, norm_eps: float,
                          prompt_ids: list, tokens: list, width: int,
                          mesh=None) -> list:
    """For each sampled row, the reference logits that predict each served
    token: one plain forward over prompt + served tokens, right-padded to
    the fixed ``width`` (so one shape compiles; causal attention never looks
    at the padding), sliced from the position that predicts the first served
    token."""
    import contextlib

    import jax

    new = max(len(t) for t in tokens)
    fn = jax.jit(lambda p, x, at: decoder_logits(
        p, x, at, new=new, heads=heads, kv_heads=kv_heads,
        rope_theta=rope_theta, norm_eps=norm_eps))
    out = []
    with (mesh or contextlib.nullcontext()), \
            jax.default_matmul_precision("highest"):
        for pids, toks in zip(prompt_ids, tokens):
            row = np.zeros((1, width), np.int32)
            row[0, :len(pids)] = pids
            row[0, len(pids):len(pids) + len(toks)] = toks
            logits = jax.device_get(fn(params, row, np.int32(len(pids) - 1)))
            out.append(np.asarray(logits)[:len(toks)])
    return out


def judge_tokens(served: list, ref_logits: list) -> dict:
    """Near-tie rule: walking each row's served tokens, the token must equal
    the reference argmax wherever the reference's top-2 margin exceeds twice
    the logit tolerance. Teacher forcing feeds the SERVED tokens, so a
    near-tie the served run resolved the other way does not end the walk:
    every later position is still judged on the served history."""
    tol = max(logit_tolerance(r) for r in ref_logits)
    checked = decided = ties = wrong = 0
    first_wrong = None
    for r, (toks, ref) in enumerate(zip(served, ref_logits)):
        top2 = np.partition(ref, -2, axis=-1)[:, -2:]
        margin = top2[:, 1] - top2[:, 0]
        want = ref.argmax(-1)
        for i, tok in enumerate(toks):
            checked += 1
            if margin[i] > 2 * tol:
                decided += 1
                if int(tok) != int(want[i]):
                    wrong += 1
                    first_wrong = first_wrong or (
                        f"row {r} step {i}: token {tok} vs reference "
                        f"{int(want[i])} at margin {margin[i]:.4f}")
            elif int(tok) != int(want[i]):
                ties += 1
    return {"ok": bool(wrong == 0 and decided > 0),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "wrong_on_decided": wrong,
            "first_wrong": first_wrong, "logit_tol": tol}


def judge(ctx) -> dict:
    """Teacher-force a seeded sample of the rows written through the plain
    forward and hold the served tokens to it; every written row must carry
    exactly ``max_new_tokens`` tokens (``eos_id`` -1: no early exit)."""
    proc_cfg = ctx.proc_cfg
    want = int(proc_cfg["max_new_tokens"])
    served: dict[int, list[int]] = {}
    short = 0
    for ids, texts in zip(ctx.out_rows, ctx.out_a):
        for i, text in zip(ids.tolist(), texts):
            toks = [int(t) for t in (text or "").split()]
            short += int(len(toks) != want)
            if i >= 0:
                served.setdefault(i, toks)
    if not served:
        return {"ok": False, "why": "nothing was written"}
    rng = np.random.default_rng([int(ctx.seed), 0x70C5])
    keys = np.array(sorted(served))
    sample = rng.choice(keys, min(SAMPLE_ROWS, len(keys)), replace=False)
    proc = ctx.processor
    max_input = int(proc_cfg["max_input"])
    tok_ids, mask = proc.tokenizer.encode_batch(
        [ctx.pool.texts[i] for i in sample], max_input)
    plens = mask.sum(axis=1).astype(int)
    cfg = proc.cfg
    ref = teacher_forced_logits(
        proc.params, heads=cfg.heads, kv_heads=cfg.kv_heads,
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        prompt_ids=[tok_ids[j, :plens[j]].tolist() for j in range(len(sample))],
        tokens=[served[i] for i in sample], width=max_input + want,
        mesh=proc.mesh)
    verdict = judge_tokens([served[i] for i in sample], ref)
    verdict["rows_sampled"] = int(len(sample))
    verdict["rows_with_wrong_token_count"] = short
    verdict["ok"] = bool(verdict["ok"] and short == 0)
    return verdict
