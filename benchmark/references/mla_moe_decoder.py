"""Plain reference for ``decoder_lm`` at latent-attention + routed-expert sizes (DeepSeek-V3 layout: Kanana-2-30B-A3B, kakaocorp 2025), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, no
absorbed products, experts as a loop with masks — written from the published
description (DeepSeek-V2 for the latent attention, DeepSeek-V3 for the
routing, the model's ``config.json`` for the sizes) and independent of
``arkflow_tpu/models``. It reads only the program's parameter tree, in the
values the configuration states: bfloat16-rounded weights, float32 router,
selection bias and norm scales (what the processor placed; cast up here).

Per layer, on ``x`` [S, hidden], pre-norm, residual after each half:

* latent attention — ``q = x_n W_q`` (heads x (nope | rope));
  ``[c | k_r] = x_n W_kva``; ``c <- RMSNorm(c)``; ``[k_nope | v] = c W_kvb``
  per head; rotary on ``q_rope`` and on the ONE ``k_r`` every head shares,
  over the pairs (2i, 2i+1); ``softmax((q_nope . k_nope + q_rope . k_r) /
  sqrt(nope + rope))`` causal; ``o = sum p v``; ``W_o``.
* leading ``first_k_dense_replace`` layers — dense SwiGLU.
* later layers — ``s = sigmoid(x_n W_r)``; the top ``num_experts_per_tok``
  of ``s + b`` are chosen; the chosen are weighed by ``s`` (not ``s + b``),
  normalised to sum 1, times ``routed_scaling_factor``; the weighted sum of
  the chosen experts' SwiGLUs plus the shared experts' SwiGLU.

Departures from the publication, all of layout and none of arithmetic:

1. The tree stacks layers on a leading axis, in two stacks (``dense_layers``
   then ``layers``); weights are [in, out].
2. ``experts`` holds the routed experts first and then ``n_shared_experts``
   slabs of the same width: the published shared MLP of width
   ``n_shared_experts x moe_intermediate_size`` is their concatenation along
   the width (a SwiGLU's down-projection sums over the width, so the sum of
   the slabs' SwiGLUs IS the wide SwiGLU). The reference concatenates them
   back and computes the wide one.
3. The published implementation permutes each rope vector from interleaved
   to half-split order and rotates that; scores are invariant under a
   permutation applied to queries and keys alike, so the pairs are rotated
   in place here.
4. Weights are random from the seed, so is the selection bias (non-zero).

``judge(ctx)`` is what the harness calls after the drain, outside the
window: a seeded sample of the rows written is teacher-forced through this
forward, and the served tokens are held to its logits under three rules,
each a tolerance with its reason (PERF.md §6, PR 27, has both readings of
every limit: the served program over its seeds, and controls served through
the timed path):

(a) bf16 logit tolerance — the served path multiplies in bfloat16 with
    float32 accumulation, so a served logit may sit 4 bf16 ulps of the
    largest reference logit away (``logit_tolerance``, the rule of
    ``dense_decoder.py``); a served token is acceptable where its reference
    logit lies within twice that of the largest.
(b) router near-tie — the served router is float32 too, but its INPUT went
    through bfloat16 products, so a served score may differ from the
    reference's in the fourth decimal: where two biased scores on either
    side of the selection boundary lie closer than ``ROUTER_DELTA`` the
    served path may rightly choose the other expert, and that position's
    logits are then another function's. No position is dropped for that: a
    served token that rule (a) refuses is held to the reference RE-ROUTED
    — one expert of the chosen ``k`` swapped for a runner-up whose score
    lies within ``ROUTER_DELTA``, at any of that position's expert layers,
    the likeliest combinations first — and is wrong unless one of those
    forwards accepts it under rule (a). Both kinds are counted and
    reported, and each has its limit: a run is not ``correct`` where the
    positions accepted only re-routed exceed ``REROUTED_SHARE`` of the
    positions checked, or the positions no admitted re-routing explains
    exceed ``UNEXPLAINED_SHARE`` (the served program leaves about one in
    2,500 unexplained — the token 1.05 to 2.3 times the admitted distance
    under the largest logit, at positions whose own routing is clear; not
    resolved, PERF.md §7 — and weights a precision lower leave one in
    five).
(c) the leaves the configuration states float32 (router, selection bias,
    norm scales) are served as stated: the placed values equal the float32
    masters bit for bit. Rules (a) and (b) cannot see a router rounded to
    bfloat16 — its scores move by a sixth of what the bfloat16 residual
    stream already moves them — so the statement is held directly.
"""

from __future__ import annotations

import math

import numpy as np

_BF16_EPS = 2.0 ** -8
#: rows sampled for the comparison (one plain forward each over up to
#: max_input + max_new_tokens positions, and one more for each round of
#: re-routing a row needs)
SAMPLE_ROWS = 8
#: two biased scores across the selection boundary closer than this may
#: swap after the served path's bfloat16 products. Readings off the chip at
#: published widths, through the timed path (PERF.md §6, PR 27): the served
#: program's re-routings cross gaps up to 2.6e-3 and once, of ~390, 4.2e-3;
#: served from weights rounded to e4m3's 3 mantissa bits they need 2.0e-2
ROUTER_DELTA = 6e-3
#: a position tries at most this many re-routings, likeliest first
REROUTE_ROUNDS = 24
#: largest share of the positions checked that may be accepted only
#: re-routed: 0.022-0.042 over the served program's seeds, 0.186 from the
#: e4m3 weights
REROUTED_SHARE = 0.08
#: largest share that no admitted re-routing explains: 0-0.002 over the
#: served program's seeds (0 to 2 of 1,024; 5 of 12,288), 0.183 from the e4m3
#: weights
UNEXPLAINED_SHARE = 0.01


def logit_tolerance(ref_logits) -> float:
    """4 bf16 ulps of the largest reference logit (as ``dense_decoder``)."""
    return 4 * _BF16_EPS * max(1.0, float(np.abs(np.asarray(ref_logits)).max()))


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _rms_norm(scale, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _rope_pairs(x, theta):
    """Rotary embedding over the pairs (2i, 2i+1) of the last axis; the
    position is the index on the FIRST axis. x: [S, ..., d]."""
    import jax.numpy as jnp

    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _swiglu(x, w_gate, w_up, w_down):
    import jax

    return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) @ _f32(w_down)


def latent_attention(lp, x_n, hp):
    """The published (expanded) latent attention over [S, hidden]."""
    import jax
    import jax.numpy as jnp

    s = x_n.shape[0]
    h, nope, rope, vd, lat = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                              hp["kv_lora_rank"])
    q = (x_n @ _f32(lp["wq"]["w"])).reshape(s, h, nope + rope)
    kv = x_n @ _f32(lp["wkv_a"]["w"])
    c = _rms_norm(lp["kv_norm"]["scale"], kv[:, :lat], hp["eps"])
    k_r = _rope_pairs(kv[:, lat:], hp["theta"])                   # [S, rope]
    kv_up = (c @ _f32(lp["wkv_b"]["w"])).reshape(s, h, nope + vd)
    k_nope, v = kv_up[..., :nope], kv_up[..., nope:]
    q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], hp["theta"])
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) / math.sqrt(nope + rope)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -1e30)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(s, h * vd) @ _f32(lp["wo"]["w"])


def route(lp, x_n, hp, swap=None):
    """(chosen experts [S, k], their weights [S, k], ``near``: the biased
    scores [S, 4] and the experts [S, 4] of the two last chosen and the two
    first not chosen, in falling order of score). ``swap`` [S, 2] re-routes:
    where a position's chosen experts hold ``swap[:, 0]`` it is replaced by
    ``swap[:, 1]`` (-1: none)."""
    import jax
    import jax.numpy as jnp

    k = hp["top_k"]
    scores = jax.nn.sigmoid(x_n @ _f32(lp["router"]["w"]))        # [S, E]
    biased = scores + _f32(lp["router_bias"])
    top, idx = jax.lax.top_k(biased, k + 2)
    near = (top[:, k - 2:], idx[:, k - 2:])
    idx = idx[:, :k]
    if swap is not None:
        idx = jnp.where(idx == swap[:, :1], swap[:, 1:], idx)
    w = jnp.take_along_axis(scores, idx, axis=-1)                 # unbiased
    w = w / w.sum(-1, keepdims=True)
    return idx, w * hp["scaling"], near


def routed_experts(lp, x_n, hp, swap=None):
    """Weighted sum of the chosen experts, one expert at a time over every
    token with a mask, plus the shared experts' one wide SwiGLU."""
    import jax
    import jax.numpy as jnp

    e = hp["experts"]
    idx, w, near = route(lp, x_n, hp, swap)
    ex = lp["experts"]

    def one_expert(acc, xs):
        i, wg, wu, wd = xs
        weight = jnp.where(idx == i, w, 0.0).sum(-1, keepdims=True)  # [S, 1]
        return acc + weight * _swiglu(x_n, wg, wu, wd), None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x_n),
        (jnp.arange(e), ex["w_gate"][:e], ex["w_up"][:e], ex["w_down"][:e]))
    wide = [jnp.concatenate(list(_f32(ex[k][e:])), axis=ax)
            for k, ax in (("w_gate", 1), ("w_up", 1), ("w_down", 0))]
    return out + _swiglu(x_n, *wide), near


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config."""
    return {
        "heads": cfg.heads, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "v": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "theta": cfg.rope_theta,
        "eps": cfg.norm_eps, "experts": cfg.n_routed_experts,
        "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor,
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on, ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]). ``swaps`` [S, expert layers, 2]
    re-routes (see ``route``). The output head is applied to those positions
    only. Layers are visited one by one (a Python loop over the stacked
    trees), so one layer's float32 copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near = []
    for stack, routed in ((params["dense_layers"], False),
                          (params["layers"], True)):
        for i in range(stack["attn_norm"]["scale"].shape[0]):
            lp = jax.tree_util.tree_map(lambda a: a[i], stack)
            x = x + latent_attention(
                lp, _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"]), hp)
            x_n = _rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"])
            if routed:
                out, n = routed_experts(
                    lp, x_n, hp, None if swaps is None else swaps[:, i])
                near.append(n)
            else:
                out = _swiglu(x_n, lp["w_gate"]["w"], lp["w_up"]["w"],
                              lp["w_down"]["w"])
            x = x + out
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    near = tuple(jax.lax.dynamic_slice_in_dim(
        jnp.stack([n[j] for n in near], axis=1), at, new, axis=0)
        for j in (0, 1))
    x = _rms_norm(params["norm_out"]["scale"], x, hp["eps"])
    return x @ _f32(params["lm_head"]["w"]), near


def _row_forward(hp: dict, new: int):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions: the largest logit,
    the runner-up, the served token's, the largest magnitude, and ``near``."""
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


def reroutings(near_scores, near_experts, delta: float) -> list:
    """The re-routings of one position that rule (b) admits, likeliest (the
    smallest sum of score gaps) first, at most ``REROUTE_ROUNDS`` of them:
    each (that sum, the widest gap, its moves (expert layer, expert dropped,
    expert added)), at most one move an expert layer, every gap under
    ``delta``. near_*: [expert layers, 4]."""
    import itertools

    layers = []
    for layer, (s, e) in enumerate(zip(near_scores, near_experts)):
        moves = [(0.0, None)]     # or none at this layer
        for a in (1, 0):          # the last chosen, the one before
            for b in (2, 3):      # the first not chosen, the next
                if s[a] - s[b] < delta:
                    moves.append((float(s[a] - s[b]), (layer, int(e[a]), int(e[b]))))
        layers.append(moves)
    out = [(sum(g for g, _ in pick), max(g for g, _ in pick),
            tuple(m for _, m in pick if m is not None))
           for pick in itertools.product(*layers)]
    return sorted(c for c in out if c[2])[:REROUTE_ROUNDS]


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, width: int,
               delta: float = ROUTER_DELTA) -> dict:
    """Rules (a) and (b) of the module docstring over the sampled rows. Each
    row is one plain forward over prompt + served tokens, right-padded to
    ``width`` (one shape compiles; causal attention never looks at the
    padding, and a token's routing depends on no other token). Teacher
    forcing feeds the SERVED tokens, so a position the served run resolved
    the other way does not end the walk. A row with refused tokens is run
    again, each of them re-routed by its next candidate (accepted
    re-routings stay in place: they are what the served run did, and later
    positions attend over them)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = int(params["layers"]["attn_norm"]["scale"].shape[0])
    fn = _row_forward(hp, new)

    def run(r, swaps):
        pids, toks = prompt_ids[r], tokens[r]
        row = np.zeros((width,), np.int32)
        row[:len(pids)] = pids
        row[len(pids):len(pids) + len(toks)] = toks
        served = np.zeros((new,), np.int32)
        served[:len(toks)] = toks
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(fn(params, row, np.int32(len(pids) - 1),
                                    served, swaps))
        return {k: np.asarray(v) for k, v in out.items()}

    none = np.full((width, layers, 2), -1, np.int32)
    first = [run(r, none) for r in range(len(tokens))]
    tol = max(logit_tolerance(o["absmax"]) for o in first)
    checked = decided = ties = unexplained = near_ties = forwards = 0
    gaps = []  # the widest score gap of each accepted re-routing
    first_unexplained = None
    for r, (toks, out) in enumerate(zip(tokens, first)):
        n, at = len(toks), len(prompt_ids[r]) - 1
        gap = (out["best"] - out["served"])[:n]
        margin = (out["best"] - out["second"])[:n]
        checked += n
        decided += int((margin > 2 * tol).sum())
        ties += int(((gap > 0) & (gap <= 2 * tol)).sum())
        near = out["near_scores"][:n]
        near_ties += int(((near[..., 1] - near[..., 2]).min(-1) < delta).sum())
        pending = {int(i): reroutings(out["near_scores"][i],
                                      out["near_experts"][i], delta)
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
                closest[i] = min(closest[i], float(again["best"][i] - again["served"][i]))
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
    return {"ok": bool(decided > 0 and len(gaps) <= REROUTED_SHARE * n
                       and unexplained <= UNEXPLAINED_SHARE * n),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "unexplained": unexplained,
            "unexplained_share": unexplained / n,
            "rerouted": len(gaps), "rerouted_share": len(gaps) / n,
            "widest_gap_rerouted": max(gaps, default=0.0),
            "router_delta": delta, "router_near_tie_share": near_ties / n,
            "reroute_forwards": forwards,
            "first_unexplained": first_unexplained, "logit_tol": tol}


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 — every leaf of a ``router*`` or ``*norm*`` entry — whose
    placed value is not the float32 master, bit for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if not any("router" in k or "norm" in k for k in keys):
            continue
        master = masters
        for k in path:
            master = master[k.key]
        a, b = np.asarray(leaf), np.asarray(master, np.float32)
        differ += int(a.size if a.dtype != np.float32
                      else (a.view(np.uint32) != b.view(np.uint32)).sum())
    return differ


def judge(ctx) -> dict:
    """Teacher-force a seeded sample of the rows written and hold the served
    tokens to the plain forward; every written row must carry exactly
    ``max_new_tokens`` tokens (``eos_id`` -1: no early exit)."""
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
    verdict = judge_rows(
        proc.params, hyper(proc.cfg),
        prompt_ids=[tok_ids[j, :plens[j]].tolist() for j in range(len(sample))],
        tokens=[served[i] for i in sample], width=max_input + want)
    verdict["rows_sampled"] = int(len(sample))
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
