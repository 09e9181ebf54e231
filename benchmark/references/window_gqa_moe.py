"""Plain reference for ``decoder_lm`` with a layer pattern of per-head (GQA) layers — 128-token sliding-window layers beside full layers — and a held share of routed experts (K-EXAONE-236B-A23B, LG AI Research 2026, ``model_type: exaone_moe``), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, experts
as a loop with masks — written from the layer equations of the
configuration file (``benchmark/configs/k-exaone-236b-a23b-l5-ep8.json``:
the model's ``config.json`` for every size, EXAONE 4.0's conventions for
what the keys do not state) and independent of ``arkflow_tpu/models``. It
reads only the program's parameter tree, in the values the configuration
states: bfloat16-rounded weights; float32 router, selection bias and norm
scales. Attention is computed a block of queries at a time, so an
8,448-token row fits.

One layer on ``x`` [S, hidden], ``n`` RMSNorm (``_layer``: the one place
that states where the norms sit — pre-norm, as every other ``decoder_lm``
block; EXAONE 4.0 itself norms each sub-layer's OUTPUT, see the
configuration's ``assumed``)::

    y = n(x)
    q = y Wq -> [64, 128];  k, v = y Wk, y Wv -> [8, 128]       (no bias)
    q = n_128(q), k = n_128(k)          per head, one scale set for q, one for k
    sliding layer: q, k = rope(q, k; theta, split halves);  full layer: none
    a = softmax(q k^T / sqrt(128) over s <= t, and t - window < s on a
        sliding layer) v;   query head h reads K/V head h // (64 / 8)
    x = x + a Wo
    y = n(x)
    leading dense layers:  x = x + W2(silu(y W1) * (y W3))
    later layers:  s = sigmoid(y Wr) float32; the top-k of s + b chosen;
                   w = s / sum(s over the chosen) * scaling factor
                   x = x + sum over the chosen experts HELD here of
                       w_e E_e(y)  +  E_shared(y)

``experts_held`` is the chip's share of an 8-way expert-parallel deployment:
the router keeps its published outputs and choices, weights are normalised
over ALL the chosen, and what absent experts would add is left out — here as
in the program (``tests/test_window_gqa_moe.py`` adds the eight shares up to
the uncut layer).

Departures from the publication, noted as the guide asks:

1. The multi-token-prediction module (``num_nextn_predict_layers`` 1) is a
   draft head that does not enter the model's own logits: neither served nor
   referenced (ROADMAP R9).
2. Pre-norm placement, QK norm, rotation on sliding layers only, the
   window's bound and the selection bias are ``assumed`` in the
   configuration file, each with its reason.
3. Layout only: layers stack on a leading axis by dense | routed
   (``dense_layers``, ``layers``), both kinds of attention in one stack;
   weights are [in, out]; ``experts`` holds the held routed experts first
   and the shared expert after them; weights and the selection bias are
   random from the seed.

``judge(ctx)`` teacher-forces a seeded sample of the rows written through
this forward and holds the served tokens to its logits under the rules of
``mla_moe_decoder.py``, each a tolerance with its reason:

(a) bf16 logit tolerance — the served path multiplies in bfloat16 with
    float32 accumulation, so a served logit may sit 4 bf16 ulps of the
    largest reference logit away; a served token is acceptable where its
    reference logit lies within twice that of the largest.
(b) router near-tie — the served router is float32, but its INPUT went
    through bfloat16 products: where two biased scores on either side of
    the selection boundary lie closer than ``ROUTER_DELTA`` the served path
    may rightly choose the other expert. A served token that rule (a)
    refuses is held to the reference RE-ROUTED (one expert of the chosen
    swapped for a runner-up within ``ROUTER_DELTA``, at any of that
    position's expert layers, the likeliest first); counted and limited
    (``REROUTED_SHARE``), and what no admitted re-routing explains is
    limited too (``UNEXPLAINED_SHARE``).
(c) the leaves the configuration states float32 (router, selection bias,
    every norm scale — the per-head ones too) are served as stated: the
    placed values equal the float32 masters bit for bit.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.mla_moe_decoder import (logit_tolerance, reroutings,
                                                  stated_float32_leaves_differ)

#: rows sampled for the comparison: one plain forward each over the row's
#: own length (rounded up to a quarter of the longest), one more for each
#: round of re-routing a row needs
SAMPLE_ROWS = 4
#: queries a block of the blocked attention takes ([heads, block, keys]
#: float32 scores: 138 MB at 64 heads and 8,448 keys)
BLOCK = 64
#: rule (b): as ``mla_moe_decoder.ROUTER_DELTA`` (the same router: float32
#: sigmoid scores of a bfloat16 residual stream, 128 outputs, seeded bias)
ROUTER_DELTA = 6e-3
#: a position tries at most this many re-routings, likeliest first. With 16
#: of 128 experts held most swaps move two ABSENT experts and change only
#: the normalisation of the held ones' weights
REROUTE_ROUNDS = 8
#: largest share of the positions checked that may be accepted only
#: re-routed, and largest share that no admitted re-routing explains: each
#: between the largest reading of the served program over its seeds (8 runs
#: on the chip, 1,024 positions each: 0.0029 and 0.0) and the control's (the
#: products' weights at 3 mantissa bits: 0.0234 and 0.0537; the window wider
#: by one page: 0.0137 and 0.2793), PERF.md §6, PR 40
REROUTED_SHARE = 0.01
UNEXPLAINED_SHARE = 0.02

_FULL, _SLIDING = "full_attention", "sliding_attention"


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _rms_norm(scale, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rope_halves(x, theta, pos):
    """Rotary embedding over the pairs (i, i + d/2) of the last axis, the
    row on the FIRST axis at position ``pos`` [S]. x: [S, heads, d]."""
    import jax.numpy as jnp

    d = x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = (pos.astype(jnp.float32)[:, None] * inv[None, :])[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang)], axis=-1)


def _swiglu(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def _blocks(fn, s: int, *per_query):
    """``fn(first query, block of each array)`` over blocks of ``BLOCK``
    queries, one after another; the results joined on the query axis."""
    import jax
    import jax.numpy as jnp

    if s <= BLOCK or s % BLOCK:
        return fn(0, *per_query)
    n = s // BLOCK
    out = jax.lax.map(
        lambda xs: fn(xs[0], *xs[1:]),
        (jnp.arange(n) * BLOCK,
         *[a.reshape(n, BLOCK, *a.shape[1:]) for a in per_query]))
    return out.reshape(s, *out.shape[2:])


def gqa_attention(lp, y, hp, kind: str):
    """One layer's grouped-query attention over [S, hidden]: keys and values
    of every position projected once, the queries a block at a time."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, kvh, dh = hp["heads"], hp["kv_heads"], hp["dh"]
    pos = jnp.arange(s)
    rotate = kind == _SLIDING or hp["full_rope"]
    window = hp["window"] if kind == _SLIDING else s + 1

    def heads(w, n, at, norm):
        t = (w).reshape(-1, n, dh)
        if hp["qk_norm"]:
            t = _rms_norm(lp[norm]["scale"], t, hp["eps"])
        return _rope_halves(t, hp["theta"], at) if rotate else t

    k = heads(y @ _f32(lp["wk"]["w"]), kvh, pos, "k_head_norm")   # [S, kv, dh]
    v = (y @ _f32(lp["wv"]["w"])).reshape(s, kvh, dh)

    def block(q0, yb):
        at = q0 + jnp.arange(yb.shape[0])
        q = heads(yb @ _f32(lp["wq"]["w"]), h, at, "q_head_norm")
        q = q.reshape(-1, kvh, h // kvh, dh)      # head h reads K/V head h // group
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(dh)
        mask = (pos[None, :] <= at[:, None]) & (pos[None, :] > at[:, None] - window)
        p = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v)
        return o.reshape(-1, h * dh) @ _f32(lp["wo"]["w"])

    return _blocks(block, s, y)


def route(lp, y, hp, swap=None):
    """(chosen experts [S, k] of ALL the router's outputs, their weights
    [S, k], ``near``: the biased scores [S, 4] and the experts [S, 4] of the
    two last chosen and the two first not chosen). ``swap`` [S, 2] re-routes:
    where a position's chosen experts hold ``swap[:, 0]`` it is replaced by
    ``swap[:, 1]`` (-1: none)."""
    import jax
    import jax.numpy as jnp

    k = hp["top_k"]
    scores = jax.nn.sigmoid(y @ _f32(lp["router"]["w"]))
    top, idx = jax.lax.top_k(scores + _f32(lp["router_bias"]), k + 2)
    near = (top[:, k - 2:], idx[:, k - 2:])
    idx = idx[:, :k]
    if swap is not None:
        idx = jnp.where(idx == swap[:, :1], swap[:, 1:], idx)
    w = jnp.take_along_axis(scores, idx, axis=-1)                 # unbiased
    return idx, w / w.sum(-1, keepdims=True) * hp["scaling"], near


def routed_experts(lp, y, hp, swap=None):
    """The held experts' part of the weighted sum (one expert at a time
    over every token with a mask) plus the shared experts' SwiGLUs.
    ``lp["experts"]`` is the layer's experts or (the stack's, the layer's
    index): an expert's three matrices are then read out of the stack one
    expert at a time, and no layer's 1.5 GB is copied."""
    import jax
    import jax.numpy as jnp

    first, held = hp["held"]
    idx, w, near = route(lp, y, hp, swap)
    ex, layer = lp["experts"] if isinstance(lp["experts"], tuple) else (
        jax.tree_util.tree_map(lambda a: a[None], lp["experts"]), 0)

    def expert(i):
        return [ex[k][layer, i] for k in ("w_gate", "w_up", "w_down")]

    def one_expert(acc, i):
        weight = jnp.where(idx == first + i, w, 0.0).sum(-1, keepdims=True)
        return acc + weight * _swiglu(y, *expert(i)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    for j in range(held, ex["w_gate"].shape[1]):
        out = out + _swiglu(y, *expert(j))
    return out, near


def _layer(x, lp, hp, kind: str, ffn):
    """One block with the norms where this configuration puts them: before
    each sub-layer, the residual taken from the un-normed stream."""
    x = x + gqa_attention(lp, _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"]),
                          hp, kind)
    return x + ffn(_rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"]))


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read
    as a bag of keys; none of the program's code runs)."""
    return {
        "heads": cfg.heads, "kv_heads": cfg.kv_heads,
        "dh": cfg.head_dim or cfg.dim // cfg.heads, "theta": cfg.rope_theta,
        "eps": cfg.norm_eps, "window": cfg.sliding_window,
        "qk_norm": cfg.qk_norm, "full_rope": cfg.full_attention_rope,
        "kinds": list((cfg.layer_types or [_FULL] * cfg.layers)[:cfg.layers]),
        "dense": cfg.first_k_dense_replace, "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]). ``swaps`` [S, expert layers, 2]
    re-routes (``route``). Layers are visited one by one in the model's
    order, each read out of its dense | routed stack, so one layer's float32
    copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near = []
    for i, kind in enumerate(hp["kinds"]):
        routed = i >= hp["dense"]
        name, j = ("layers", i - hp["dense"]) if routed else ("dense_layers", i)
        lp = jax.tree_util.tree_map(lambda a: a[j], {
            k: v for k, v in params[name].items() if k != "experts"})
        if routed:
            lp["experts"] = (params[name]["experts"], j)

            def ffn(y, lp=lp, e=len(near)):
                out, n = routed_experts(
                    lp, y, hp, None if swaps is None else swaps[:, e])
                near.append(n)
                return out
        else:
            def ffn(y, lp=lp):
                return _swiglu(y, lp["w_gate"]["w"], lp["w_up"]["w"],
                               lp["w_down"]["w"])
        x = _layer(x, lp, hp, kind, ffn)
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    near = tuple(jax.lax.dynamic_slice_in_dim(
        jnp.stack([n[j] for n in near], axis=1), at, new, axis=0)
        for j in (0, 1))
    x = _rms_norm(params["norm_out"]["scale"], x, hp["eps"])
    return x @ _f32(params["lm_head"]["w"]), near


def _row_forward(hp: dict, new: int):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions."""
    import jax
    import jax.numpy as jnp

    def fn(params, row, at, served, swaps):
        logits, (near_s, near_e) = decoder_logits(
            params, row, at, new=new, hp=hp, swaps=swaps)
        top2 = jax.lax.top_k(logits, 2)[0]
        return {"best": top2[:, 0], "second": top2[:, 1],
                "served": jnp.take_along_axis(logits, served[:, None], 1)[:, 0],
                "absmax": jnp.abs(logits).max(), "near_scores": near_s,
                "near_experts": near_e}

    return jax.jit(fn)


def row_width(n: int, longest: int) -> int:
    """The padded width a row of ``n`` positions is run at: a multiple of
    ``BLOCK`` near a quarter, a half, ... of the longest (few shapes compile,
    and a short row does not pay for the longest)."""
    step = -(-longest // (4 * BLOCK)) * BLOCK
    return min(-(-n // step) * step, -(-longest // BLOCK) * BLOCK)


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               delta: float = ROUTER_DELTA, shares: float = 1.0) -> dict:
    """Rules (a) and (b) of the module docstring over the sampled rows. Each
    row is one plain forward over prompt + served tokens, right-padded
    (causal attention never looks at the padding, and a token's routing
    depends on no other token). Teacher forcing feeds the SERVED tokens. A
    row with refused tokens is run again, each of them re-routed by its next
    candidate; accepted re-routings stay in place (they are what the served
    run did, and later positions attend over them). ``shares`` scales the
    two limits (a rehearsal's, see ``judge``)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = len(hp["kinds"]) - hp["dense"]
    fn = _row_forward(hp, new)

    def run(r, swaps):
        pids, toks = prompt_ids[r], tokens[r]
        width = row_width(len(pids) + len(toks), longest)
        row = np.zeros((width,), np.int32)
        row[:len(pids)] = pids
        row[len(pids):len(pids) + len(toks)] = toks
        served = np.zeros((new,), np.int32)
        served[:len(toks)] = toks
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(fn(params, row, np.int32(len(pids) - 1),
                                    served, swaps[:width]))
        return {k: np.asarray(v) for k, v in out.items()}

    none = np.full((longest + 4 * BLOCK, layers, 2), -1, np.int32)
    first = [run(r, none) for r in range(len(tokens))]
    tol = max(logit_tolerance(o["absmax"]) for o in first)
    checked = decided = ties = unexplained = near_ties = forwards = 0
    gaps, worst = [], 0.0  # the widest score gap of each accepted re-routing
    first_unexplained = None
    for r, (toks, out) in enumerate(zip(tokens, first)):
        n, at = len(toks), len(prompt_ids[r]) - 1
        gap = (out["best"] - out["served"])[:n]
        margin = (out["best"] - out["second"])[:n]
        checked += n
        decided += int((margin > 2 * tol).sum())
        ties += int(((gap > 0) & (gap <= 2 * tol)).sum())
        worst = max(worst, float(gap.max()))
        near = out["near_scores"][:n]
        near_ties += int(((near[..., 1] - near[..., 2]).min(-1) < delta).sum())
        pending = {int(i): reroutings(out["near_scores"][i],
                                      out["near_experts"][i], delta)[:REROUTE_ROUNDS]
                   for i in np.flatnonzero(gap > 2 * tol)}
        closest = {i: float(gap[i]) for i in pending}
        swaps = none.copy()
        for _ in range(REROUTE_ROUNDS):
            trying = {i: c.pop(0) for i, c in pending.items() if c}
            if not trying:
                break
            trial = swaps.copy()
            for i, (_, _, moves) in trying.items():
                for layer, drop, add in moves:
                    trial[at + i, layer] = (drop, add)
            again = run(r, trial)
            forwards += 1
            for i, (_, gap_i, moves) in trying.items():
                closest[i] = min(closest[i],
                                 float(again["best"][i] - again["served"][i]))
                if closest[i] <= 2 * tol:
                    gaps.append(round(gap_i, 6))
                    for layer, drop, add in moves:
                        swaps[at + i, layer] = (drop, add)
                    del pending[i]
        unexplained += len(pending)
        for i in sorted(pending)[:1]:
            first_unexplained = first_unexplained or (
                f"row {r} step {i}: token {toks[i]} lies {gap[i]:.4f} under "
                f"the reference's largest logit, {closest[i]:.4f} under the "
                f"nearest re-routing's (admitted: {2 * tol:.4f}); gaps across "
                f"the selection boundary by expert layer "
                f"{np.round(near[i][:, 1] - near[i][:, 2], 5).tolist()}")
    n = max(checked, 1)
    return {"ok": bool(decided > 0 and len(gaps) <= shares * REROUTED_SHARE * n
                       and unexplained <= shares * UNEXPLAINED_SHARE * n),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "unexplained": unexplained,
            "unexplained_share": unexplained / n,
            "rerouted": len(gaps), "rerouted_share": len(gaps) / n,
            "widest_gap_rerouted": max(gaps, default=0.0),
            "largest_distance_under_best": worst,
            "router_delta": delta, "router_near_tie_share": near_ties / n,
            "reroute_forwards": forwards,
            "first_unexplained": first_unexplained, "logit_tol": tol}


def judge(ctx) -> dict:
    """Teacher-force a seeded sample of the rows written and hold the served
    tokens to the plain forward; every written row must carry exactly
    ``max_new_tokens`` tokens (``eos_id`` -1: no early exit). A rehearsal
    (hidden 64, 4 of 16 experts: nearly every position has a choice within
    a rounding of its boundary) holds the control flow, the counts and the
    stated leaves, and the shares to 25 times their limits."""
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
    rng = np.random.default_rng([int(ctx.seed), 0xE8A0])
    keys = np.array(sorted(served))
    sample = rng.choice(keys, min(SAMPLE_ROWS, len(keys)), replace=False)
    proc = ctx.processor
    max_input = int(proc_cfg["max_input"])
    tok_ids, mask = proc.tokenizer.encode_batch(
        [ctx.pool.texts[i] for i in sample], max_input)
    plens = mask.sum(axis=1).astype(int)
    verdict = judge_rows(
        proc.params, hyper(proc.cfg),
        prompt_ids=[tok_ids[j, :plens[j]].tolist() for j in range(len(sample))],
        tokens=[served[i] for i in sample], longest=max_input + want,
        shares=25.0 if getattr(ctx, "rehearse", False) else 1.0)
    verdict["rows_sampled"] = int(len(sample))
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
