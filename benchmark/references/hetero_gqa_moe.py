"""Plain reference for ``decoder_lm`` with per-head (GQA) layers whose sizes go by kind — full layers and 128-token sliding-window layers of different K/V head counts, keys wider than values, partial rotation at a base a kind, a learned sink in the sliding layers' softmax — and a held share of sigmoid-routed experts (MiMo-V2.5, Xiaomi 2026, ``model_type: mimo_v2``), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, no key
held in parts, experts as a loop with masks — written from the layer
equations of the configuration file
(``benchmark/configs/mimo-v2.5-l7-ep16.json``: the model's ``config.json``
for every size, its ``assumed`` for what the keys do not state) and
independent of ``arkflow_tpu/models``. It reads only the program's parameter
tree, in the values the configuration states: bfloat16-rounded weights;
float32 router, selection bias, sinks and norm scales. Attention and the
dense SwiGLU are computed a block of queries at a time, so a 13,312-token
row fits beside the server's pools.

One layer on ``x`` [S, hidden], ``n`` RMSNorm, kind k of the layer (full |
sliding), G_k its K/V heads (4 | 8)::

    y = n(x)
    q = y Wq -> [64, 192];  k = y Wk -> [G_k, 192];  v = y Wv -> [G_k, 128]
    the first 64 values of every q and k head rotated (split halves
        (i, i + 32)) at base theta_k (1e7 | 1e4); the other 128 as they are
    v = 0.707 v
    s = q k^T / sqrt(192) over j <= t, and t - 128 < j on a sliding layer;
        query head h reads K/V head h // (64 / G_k)
    full:    p = softmax(s)
    sliding: p_j = exp(s_j - m) / (sum_i exp(s_i - m) + exp(b_h - m)),
             b_h the head's sink logit: probability taken, no value added
    x = x + (p v) Wo                                  (64 x 128 -> hidden)
    y = n(x)
    layer 0:       x = x + W2(silu(y W1) * (y W3))
    later layers:  z = sigmoid(y Wr) float32 over 256; the 8 largest of
                   z + b chosen; w = z / sum(z over the chosen)
                   x = x + sum over the chosen experts HELD here of w_e E_e(y)

``experts_held`` is the chip's share of a 16-way expert-parallel
deployment: the router keeps its published outputs and choices, weights are
normalised over ALL the chosen, and what absent experts would add is left
out — here as in the program (``tests/test_hetero_gqa_moe.py`` adds the
sixteen shares up to the uncut layer). There is no shared expert.

Departures from the publication, noted as the guide asks: the vision and
audio towers and the multi-token-prediction layers of the model card have no
key in ``config.json`` and are neither served nor referenced; the sink's
form, pre-norm placement, the rotation's pairing, the window's bound and the
selection bias are ``assumed`` in the configuration file, each with its
reason; layout only: layers stack on a leading axis by (dense | routed) x
(full | sliding) (``dense_layers``, ``layers``, ``swa_dense_layers``,
``swa_layers``), weights are [in, out] and ``wq`` / ``wk`` / ``wv`` apart
where the checkpoint fuses them.

``judge(ctx)`` teacher-forces a seeded sample of the rows written through
this forward and holds the served tokens to its logits under the rules of
``window_gqa_moe.py`` — (a) the bf16 logit tolerance, (b) the router's
near-tie re-routing, counted and limited, (c) the stated float32 leaves (the
sinks among them) served as stated — with this cell's own limits below. Of
the sampled rows the SHORTEST and the LONGEST are judged: a 13k-position
forward in float32 at ``highest`` precision is what the comparison costs,
and the two ends of the lengths are where a window, a ring's wrap or a page
table's last columns would go wrong.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.mla_moe_decoder import (logit_tolerance, reroutings,
                                                  stated_float32_leaves_differ)
from benchmark.references.window_gqa_moe import (BLOCK, REROUTE_ROUNDS,
                                                 ROUTER_DELTA, _blocks, _f32,
                                                 _rms_norm, _rope_halves,
                                                 _swiglu, routed_experts,
                                                 row_width)

#: rows drawn for the comparison; the shortest and the longest are judged
SAMPLE_ROWS = 4
#: largest share of the positions checked that may be accepted only
#: re-routed, and largest share that no admitted re-routing explains. The
#: served program read 0.0 and 0.0 in every one of its builder's runs on the
#: chip (2,048 positions each). The controls: the products' weights at 3
#: mantissa bits read 0.0078 and 0.0205 unexplained (0.0029 and 0.0044
#: re-routed: under its limit, so the control is refused by ONE limit); the
#: sink left out read 0.0845 unexplained (PERF.md §6, PR 42)
REROUTED_SHARE = 0.01
UNEXPLAINED_SHARE = 0.003

_FULL, _SLIDING = "full_attention", "sliding_attention"
#: the program's stack of a layer, by (kind, routed?)
_STACKS = {(_FULL, False): "dense_layers", (_FULL, True): "layers",
           (_SLIDING, False): "swa_dense_layers", (_SLIDING, True): "swa_layers"}


def _rotated(x, pos, theta: float, r: int):
    """The first ``r`` values of every head rotated in split halves at base
    ``theta``, the rest as they are. x: [S, heads, d]."""
    import jax.numpy as jnp

    if not r:
        return x
    return jnp.concatenate([_rope_halves(x[..., :r], theta, pos), x[..., r:]], -1)


def hetero_attention(lp, y, hp, kind: str):
    """One layer's grouped-query attention over [S, hidden] at its kind's
    sizes: keys and values of every position projected once, the queries a
    block at a time; a kind with a sink appends its logit to every query's
    scores and drops the column after the softmax."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, dk, dv = hp["heads"], hp["dk"], hp["dv"]
    kv, theta, window, sink = (hp[kind][k] for k in ("kv_heads", "theta",
                                                     "window", "sink"))
    pos = jnp.arange(s)
    window = window or s + 1
    k = _rotated((y @ _f32(lp["wk"]["w"])).reshape(s, kv, dk), pos, theta,
                 hp["rotary"])
    v = (y @ _f32(lp["wv"]["w"])).reshape(s, kv, dv) * hp["value_scale"]

    def block(q0, yb):
        at = q0 + jnp.arange(yb.shape[0])
        q = _rotated((yb @ _f32(lp["wq"]["w"])).reshape(-1, h, dk), at, theta,
                     hp["rotary"])
        q = q.reshape(-1, kv, h // kv, dk)        # head h reads K/V head h // group
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(dk)
        mask = (pos[None, :] <= at[:, None]) & (pos[None, :] > at[:, None] - window)
        scores = jnp.where(mask[None, None], scores, -1e30)
        if sink:
            b = _f32(lp["attn_sink"]).reshape(kv, h // kv, 1, 1)
            scores = jnp.concatenate(
                [scores, jnp.broadcast_to(b, scores.shape[:3] + (1,))], -1)
        p = jax.nn.softmax(scores, -1)[..., :s]
        o = jnp.einsum("kgqs,skd->qkgd", p, v)
        return o.reshape(-1, h * dv) @ _f32(lp["wo"]["w"])

    return _blocks(block, s, y)


def _layer(x, lp, hp, kind: str, ffn):
    """One block with the norms where this configuration puts them: before
    each sub-layer, the residual taken from the un-normed stream."""
    x = x + hetero_attention(
        lp, _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"]), hp, kind)
    return x + ffn(_rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"]))


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read
    as a bag of keys; none of the program's code runs)."""
    dk = cfg.head_dim or cfg.dim // cfg.heads
    kinds = list((cfg.layer_types or [_FULL] * cfg.layers)[:cfg.layers])
    return {
        "heads": cfg.heads, "dk": dk, "dv": cfg.v_head_dim or dk,
        "rotary": int(dk * cfg.partial_rotary_factor),
        "value_scale": cfg.attention_value_scale, "eps": cfg.norm_eps,
        _FULL: {"kv_heads": cfg.kv_heads, "theta": cfg.rope_theta, "window": 0,
                "sink": cfg.add_full_attention_sink_bias},
        _SLIDING: {"kv_heads": cfg.swa_kv_heads or cfg.kv_heads,
                   "theta": cfg.swa_rope_theta or cfg.rope_theta,
                   "window": cfg.sliding_window,
                   "sink": cfg.add_swa_attention_sink_bias},
        "kinds": kinds, "dense": cfg.first_k_dense_replace,
        "top_k": cfg.num_experts_per_tok, "scaling": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]). ``swaps`` [S, expert layers, 2]
    re-routes (``window_gqa_moe.route``). Layers are visited one by one in
    the model's order, each read out of its stack, so one layer's float32
    copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near, seen = [], {}
    for i, kind in enumerate(hp["kinds"]):
        routed = i >= hp["dense"]
        name = _STACKS[kind, routed]
        j = seen[name] = seen.get(name, -1) + 1
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
                return _blocks(
                    lambda _, yb: _swiglu(yb, lp["w_gate"]["w"], lp["w_up"]["w"],
                                          lp["w_down"]["w"]), y.shape[0], y)
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


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               delta: float = ROUTER_DELTA, shares: float = 1.0) -> dict:
    """Rules (a) and (b) over the given rows, as ``window_gqa_moe.
    judge_rows`` applies them (this model's forward, this cell's limits):
    each row is one plain forward over prompt + served tokens, right-padded;
    teacher forcing feeds the SERVED tokens; a row with refused tokens is
    run again, each of them re-routed by its next candidate, accepted
    re-routings staying in place. ``shares`` scales the two limits (a
    rehearsal's, see ``judge``)."""
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


def sinks_differ(placed, masters) -> int:
    """Rule (c) for the sinks (``stated_float32_leaves_differ`` reads the
    ``router*`` and ``*norm*`` leaves): the number of ``attn_sink`` values
    whose placed value is not the float32 master, bit for bit."""
    differ = 0
    for name, stack in placed.items():
        if isinstance(stack, dict) and "attn_sink" in stack:
            a = np.asarray(stack["attn_sink"])
            b = np.asarray(masters[name]["attn_sink"], np.float32)
            differ += int(a.size if a.dtype != np.float32
                          else (a.view(np.uint32) != b.view(np.uint32)).sum())
    return differ


def judge(ctx) -> dict:
    """Teacher-force the shortest and the longest of a seeded sample of the
    rows written and hold the served tokens to the plain forward; every
    written row must carry exactly ``max_new_tokens`` tokens (``eos_id``
    -1: no early exit). A rehearsal (hidden 64, 4 of 16 experts: nearly
    every position has a choice within a rounding of its boundary) holds
    the control flow, the counts and the stated leaves, and the shares to 25
    times their limits."""
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
    rng = np.random.default_rng([int(ctx.seed), 0x3130])
    keys = np.array(sorted(served))
    sample = rng.choice(keys, min(SAMPLE_ROWS, len(keys)), replace=False)
    by_length = sorted(sample, key=lambda i: int(ctx.pool.tokens[i]))
    sample = by_length[:1] + by_length[-1:] if len(by_length) > 1 else by_length
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
    verdict["prompt_tokens_judged"] = [int(n) for n in plens]
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params) + sinks_differ(proc.params,
                                                      proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
