"""Plain reference for ``bert_classifier`` (BERT: Devlin et al. 2018; encoder, pooler, classifier), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernels, no cache, no batching policy,
written from the published description and independent of the program's
model code. It reads only the program's parameter tree (the weights are the
program's, made from the seed). Departure from the published model, shared with the program: GELU is the tanh approximation.

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

#: served scores are bf16 computations of a value in [0.5, 1): four bf16
#: steps there (2^-8 each)
SCORE_TOL = 2.0 ** -6
#: rows sampled for the comparison
SAMPLE_ROWS = 256

def _dense(p, x):
    import jax.numpy as jnp

    y = x @ p["w"].astype(jnp.float32)
    return y + p["b"].astype(jnp.float32) if "b" in p else y

# -- BERT (Devlin et al. 2018, encoder + pooler + classifier) -----------------


def _layer_norm(p, x, eps):
    import jax.numpy as jnp

    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def bert_classify(params, input_ids, attention_mask, *, heads: int,
                  ln_eps: float):
    """[B, S] ids and mask -> (label [B], score [B]): the probability of the
    likelier class. Layers are stacked on a leading axis of the tree."""
    import jax
    import jax.numpy as jnp

    b, s = input_ids.shape
    e = params["embed"]
    x = (e["word"]["table"][input_ids] + e["position"]["table"][jnp.arange(s)][None]
         + e["token_type"]["table"][0][None, None])
    x = _layer_norm(e["ln"], x.astype(jnp.float32), ln_eps)
    hidden = x.shape[-1]
    dh = hidden // heads
    keep = attention_mask.astype(bool)[:, None, None, :]

    def layer(x, lp):
        q = _dense(lp["q"], x).reshape(b, s, heads, dh)
        k = _dense(lp["k"], x).reshape(b, s, heads, dh)
        v = _dense(lp["v"], x).reshape(b, s, heads, dh)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        scores = jnp.where(keep, scores, -1e30)
        attn = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = _layer_norm(lp["attn_ln"],
                        x + _dense(lp["attn_out"], attn.reshape(b, s, hidden)),
                        ln_eps)
        ff = _dense(lp["ffn_out"],
                    jax.nn.gelu(_dense(lp["ffn_in"], x), approximate=True))
        return _layer_norm(lp["ffn_ln"], x + ff, ln_eps), None

    # the stacked layers, one after the other (scan: one traced body)
    x, _ = jax.lax.scan(layer, x, params["layers"])
    pooled = jnp.tanh(_dense(params["pooler"], x[:, 0, :]))
    probs = jax.nn.softmax(_dense(params["classifier"], pooled), -1)
    return jnp.argmax(probs, -1), jnp.max(probs, -1)


def classify_reference(params, *, heads: int, ln_eps: float, ids, mask,
                       seq_buckets) -> tuple[np.ndarray, np.ndarray]:
    """Labels and scores of the rows ``ids``/``mask`` ([N, max_seq], already
    tokenized), each row padded to the smallest seq bucket that holds it
    (padding is masked, so the bucket does not change the answer)."""
    import jax

    fn = jax.jit(lambda p, i, m: bert_classify(p, i, m, heads=heads,
                                               ln_eps=ln_eps))
    lens = mask.sum(axis=1)
    seq_of = np.array([min(b for b in seq_buckets if b >= n) for n in lens])
    labels = np.zeros(len(ids), np.int64)
    scores = np.zeros(len(ids), np.float64)
    with jax.default_matmul_precision("highest"):
        for sb in sorted(set(seq_of.tolist())):
            rows = np.nonzero(seq_of == sb)[0]
            # fixed chunk of 32 rows: one compiled shape per seq bucket
            for at in range(0, len(rows), 32):
                chunk = rows[at:at + 32]
                pad = np.concatenate([chunk, np.repeat(chunk[-1:], 32 - len(chunk))])
                lab, sc = jax.device_get(fn(params, ids[pad, :sb], mask[pad, :sb]))
                labels[chunk] = lab[:len(chunk)]
                scores[chunk] = sc[:len(chunk)]
    return labels, scores


def judge_classify(ref_labels, ref_scores, got_labels, got_scores) -> dict:
    """Served against reference, row by row: every score within
    ``SCORE_TOL``; the label equal wherever the reference decides."""
    diff = np.abs(np.asarray(got_scores, np.float64) - ref_scores)
    decided = (ref_scores - 0.5) > SCORE_TOL
    flips = int(((np.asarray(got_labels) != ref_labels) & decided).sum())
    worst = float(diff.max()) if len(diff) else float("nan")
    ok = bool(len(diff) > 0 and np.isfinite(worst) and worst <= SCORE_TOL
              and flips == 0 and decided.sum() > 0)
    return {"ok": ok, "rows_checked": int(len(diff)),
            "rows_decided": int(decided.sum()), "label_flips_on_decided": flips,
            "max_abs_score_diff": worst, "score_tol": SCORE_TOL}




def judge(ctx) -> dict:
    """Hold a seeded sample of the rows written to the float32 reference:
    every serving of a sampled row (the pool cycles, so a row is served
    several times) must agree with it."""
    import jax

    from arkflow_tpu.models import get_model
    from arkflow_tpu.tpu.runner import init_host_params

    proc_cfg = ctx.proc_cfg
    rows = np.concatenate(ctx.out_rows) if ctx.out_rows else np.zeros(0, np.int64)
    labels = np.concatenate(ctx.out_a) if ctx.out_a else np.zeros(0, np.int64)
    scores = np.concatenate(ctx.out_b) if ctx.out_b else np.zeros(0)
    seen = np.unique(rows[rows >= 0])
    if len(seen) == 0:
        return {"ok": False, "why": "nothing was written"}
    rng = np.random.default_rng([int(ctx.seed), 0x5A3F])
    sample = np.sort(rng.choice(seen, min(SAMPLE_ROWS, len(seen)), replace=False))
    fam = get_model(proc_cfg["model"])
    cfg = fam.make_config(**(proc_cfg.get("model_config") or {}))
    masters = jax.device_put(
        init_host_params(fam, cfg, int(proc_cfg.get("seed", 0))),
        jax.devices()[0])
    ids, mask = ctx.processor.tokenizer.encode_batch(
        [ctx.pool.texts[i] for i in sample], int(proc_cfg["max_seq"]))
    ref_l, ref_s = classify_reference(
        masters, heads=cfg.heads, ln_eps=cfg.ln_eps, ids=ids, mask=mask,
        seq_buckets=list(proc_cfg["seq_buckets"]))
    where = np.searchsorted(sample, rows)
    where = np.clip(where, 0, len(sample) - 1)
    hit = sample[where] == rows
    verdict = judge_classify(ref_l[where[hit]], ref_s[where[hit]],
                             labels[hit], scores[hit])
    verdict["rows_sampled"] = int(len(sample))
    return verdict
