"""Plain reference for ``decoder_lm`` with several residual streams mixed by manifold-constrained hyper-connections around YaRN-extended latent attention and sigmoid-routed experts (Xing4.0-29B-A4B, XingChen-AGI 2026), and the comparison that decides ``correct``.

The forward pass in straightforward ``jax.numpy`` and float32 at ``highest``
matmul precision: no kernel, no cache, no batching, no absorbed products —
written from the published descriptions (mHC: arXiv:2512.24880, the
constrained form of Hyper-Connections, arXiv:2409.19606; DeepSeek-V2 for the
latent attention, DeepSeek-V3 for the routing and for YaRN; the model's
``config.json`` for the sizes and the mixing's keys) and independent of
``arkflow_tpu``: it reads only the program's parameter tree, in the values
the configuration states (bfloat16-rounded weights; router, selection bias,
norm scales and the mixing's leaves float32), and shares helpers with the
other references only.

A token's residual is ``X`` [n, C] (n = ``hc_mult`` = 4). After the table
every stream is the embedding; before the final norm the streams are summed.
Each layer has two sub-layers F (latent attention; then the dense SwiGLU or
the expert block, each behind its own RMSNorm), each with its own leaves
``phi`` [n C, n n + 2 n], ``b``, ``alpha`` = (pre, post, res)::

    x = vec(X);  m = (x phi) / sqrt(mean(x^2) + rms_norm_eps)
    Hpre = sigmoid(a_pre m[0:n] + b[0:n]);  Hpost = 2 sigmoid(a_post m[n:2n] + b[n:2n])
    M = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M <- M / (colsum M + hc_eps);  M <- M / (rowsum M + hc_eps)
    u = sum_j Hpre[j] X_j;   y = F(norm(u));   X'_i = sum_j M[i, j] X_j + Hpost[i] y

written out for ONE token (``mix_in`` / ``mix_back``) and mapped over the
row. What the source does not settle, and this file assumes with the
configuration file (``assumed``): columns before rows inside an iteration;
``hc_eps`` in those denominators and nowhere else; the clamp before the
``exp``; the projection's norm has no scale and the model's eps; expansion by
copy and collapse by sum; ``phi``'s columns are pre | post | res row-major.

Latent attention as ``mla_moe_decoder.py`` with a low-rank query (``cq =
RMSNorm(x W_qa)``, ``q = cq W_qb``), rotary over the pairs (2i, 2i + 1) at
YaRN's frequencies (``yarn_frequencies``: DeepSeek-V3's, written out pair by
pair) and the softmax scale ``(nope + rope)^-0.5 g(mscale_all_dim)^2``;
queries a block at a time, so a 15,872-position row costs a block's scores
and not the square's. Routing as there, over the share of the experts held
(``window_gqa_moe.routed_experts``: what absent experts would add is left
out, here and in the program).

``judge(ctx)``: the shortest and the longest of a seeded sample of the rows
written are teacher-forced through this forward and held to its logits by
``mla_moe_decoder.py``'s rules — (a) the bf16 logit tolerance, (b) a router
near-tie's re-routing, each with its limit below, (c) the leaves stated
float32 served as their masters bit for bit, the mixing's among them: a
mixing projected or normalised in bfloat16 moves a coefficient in its third
digit, which (a) alone would see only through twenty sub-layers.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.mla_moe_decoder import (logit_tolerance, reroutings,
                                                  stated_float32_leaves_differ)
from benchmark.references.window_gqa_moe import (BLOCK, ROUTER_DELTA, _blocks,
                                                 _f32, _rms_norm, _swiglu,
                                                 routed_experts, row_width)

#: rows drawn for the comparison; the shortest and the longest are judged
SAMPLE_ROWS = 4
#: a position tries at most this many re-routings, likeliest first (each is
#: one more plain forward of its row; with 16 of 64 experts held most swaps
#: move two absent experts and change the held ones' normalisation only)
REROUTE_ROUNDS = 6
#: largest share of the positions checked that may be accepted only
#: re-routed, and largest share that no admitted re-routing explains. Each
#: between the served program's largest reading over its builder's runs on
#: the chip (1,024 positions each; under the file's ``embed_init_std`` 0.5,
#: nineteen runs: 0.0088 and 0.0; under the table's earlier 0.02, twelve:
#: 0.0127 and 0.0) and the control's (every product's left operand at 3
#: mantissa bits, through the timed path: 0.0244 and 0.0068; earlier 0.0195
#: and 0.0156), PERF.md section 6, PR 53
REROUTED_SHARE = 0.016
UNEXPLAINED_SHARE = 0.004


def yarn_frequencies(d: int, theta: float, y: dict) -> np.ndarray:
    """The ``d / 2`` rotary frequencies under YaRN as DeepSeek-V3's modelling
    code computes them, one pair at a time. ``f_i = theta^(-2i/d)``; with
    ``L`` the original positions, ``corr(b) = d ln(L / (2 pi b)) / (2 ln
    theta)``: pairs below ``low = floor(corr(beta_fast))`` keep ``f_i``,
    pairs above ``high = ceil(corr(beta_slow))`` turn at ``f_i / factor``,
    those between are interpolated linearly."""
    length, factor = float(y["original_max_position_embeddings"]), float(y["factor"])

    def corr(rotations):
        return d * math.log(length / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(corr(float(y["beta_fast"]))), 0)
    high = min(math.ceil(corr(float(y["beta_slow"]))), d // 2 - 1)
    if low == high:
        high += 0.001
    out = []
    for i in range(d // 2):
        f = float(theta) ** (-2.0 * i / d)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return np.asarray(out, np.float32)


def yarn_g(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _rope_pairs(x, pos, freqs, mult: float):
    """Rotary embedding over the pairs (2i, 2i + 1) of the last axis at
    positions ``pos`` [S]; cos and sin times ``mult``. x: [S, ..., d]."""
    import jax.numpy as jnp

    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(freqs)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (x.shape[-1] // 2,))
    cos, sin = jnp.cos(ang) * mult, jnp.sin(ang) * mult
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def latent_attention(lp, y, hp):
    """The published (expanded) latent attention over [S, hidden]: keys and
    values of every position once, the queries a block at a time."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, nope, rope, vd, lat = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                              hp["kv_lora_rank"])
    pos = jnp.arange(s)
    kv = y @ _f32(lp["wkv_a"]["w"])
    c = _rms_norm(lp["kv_norm"]["scale"], kv[:, :lat], hp["eps"])
    k_r = _rope_pairs(kv[:, lat:], pos, hp["freqs"], hp["rope_mult"])   # [S, rope]
    kv_up = (c @ _f32(lp["wkv_b"]["w"])).reshape(s, h, nope + vd)
    k_nope, v = kv_up[..., :nope], kv_up[..., nope:]

    def block(q0, yb):
        at = q0 + jnp.arange(yb.shape[0])
        src = yb
        if hp["q_lora_rank"]:
            src = _rms_norm(lp["q_norm"]["scale"], yb @ _f32(lp["wq_a"]["w"]),
                            hp["eps"])
        q = (src @ _f32(lp["wq"]["w"])).reshape(-1, h, nope + rope)
        q_rope = _rope_pairs(q[..., nope:], at, hp["freqs"], hp["rope_mult"])
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) * hp["softmax_scale"]
        scores = jnp.where((pos[None, :] <= at[:, None])[None], scores, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        return o.reshape(-1, h * vd) @ _f32(lp["wo"]["w"])

    return _blocks(block, s, y)


def coefficients(leaves, x_tok, hp):
    """One token's (Hpre [n], Hpost [n], Hres [n, n]) from its streams
    ``x_tok`` [n, C] under one sub-layer's ``leaves``."""
    import jax
    import jax.numpy as jnp

    n = hp["hc_mult"]
    x = x_tok.reshape(-1)
    m = (x @ _f32(leaves["phi"])) / jnp.sqrt(jnp.mean(x * x) + hp["eps"])
    a_pre, a_post, a_res = (_f32(leaves["alpha"])[i] for i in range(3))
    b = _f32(leaves["b"])
    h_pre = jax.nn.sigmoid(a_pre * m[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a_post * m[n:2 * n] + b[n:2 * n])
    lo, hi = hp["hc_clamp"]
    mat = jnp.exp(jnp.clip(a_res * m[2 * n:].reshape(n, n)
                           + b[2 * n:].reshape(n, n), lo, hi))
    for _ in range(hp["hc_iters"]):  # all of them, whatever has converged
        mat = mat / (mat.sum(axis=0, keepdims=True) + hp["hc_eps"])
        mat = mat / (mat.sum(axis=1, keepdims=True) + hp["hc_eps"])
    return h_pre, h_post, mat


def mix_in(leaves, x_tok, hp):
    """One token: (the sub-layer's input u [C], Hpost, Hres)."""
    h_pre, h_post, h_res = coefficients(leaves, x_tok, hp)
    return h_pre @ x_tok, h_post, h_res


def mix_back(x_tok, y_tok, h_post, h_res):
    """One token: the streams after the sub-layer's output ``y_tok`` [C]."""
    return h_res @ x_tok + h_post[:, None] * y_tok[None, :]


def sub_layer(leaves, x, hp, fn):
    """``fn`` (norm included) around the streams ``x`` [S, n, C]."""
    import jax

    u, h_post, h_res = jax.vmap(lambda t: mix_in(leaves, t, hp))(x)
    return jax.vmap(mix_back)(x, fn(u), h_post, h_res)


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read as
    a bag of keys; none of the program's code runs)."""
    y = dict(cfg.rope_scaling) if cfg.rope_scaling else None
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    hp = {
        "heads": cfg.heads, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "v": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "q_lora_rank": cfg.q_lora_rank,
        "eps": cfg.norm_eps, "dense": cfg.first_k_dense_replace,
        "layers": cfg.layers, "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
        "hc_mult": cfg.hc_mult, "hc_iters": cfg.hc_sinkhorn_iters,
        "hc_eps": cfg.hc_eps,
        "hc_clamp": (float(cfg.mhc_h_res_clamp_min), float(cfg.mhc_h_res_clamp_max)),
        "softmax_scale": qk ** -0.5, "rope_mult": 1.0,
    }
    if y is None:
        d = cfg.qk_rope_head_dim
        hp["freqs"] = np.asarray([float(cfg.rope_theta) ** (-2.0 * i / d)
                                  for i in range(d // 2)], np.float32)
    else:
        hp["freqs"] = yarn_frequencies(cfg.qk_rope_head_dim, float(cfg.rope_theta), y)
        g_all = yarn_g(float(y["factor"]), float(y["mscale_all_dim"]))
        hp["softmax_scale"] = qk ** -0.5 * g_all * g_all
        hp["rope_mult"] = yarn_g(float(y["factor"]), float(y["mscale"])) / g_all
    return hp


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]). ``swaps`` [S, expert layers, 2]
    re-routes (``window_gqa_moe.route``). Layers are visited one by one — a
    ``lax.scan`` over each of the two stacks, the layer's leaves read out of
    the stack by its index, an expert's matrices one expert at a time — so
    one layer's float32 copies live at a time and one layer of each stack is
    compiled (ten unrolled layers took a minute a padded width)."""
    import jax
    import jax.numpy as jnp

    n = hp["hc_mult"]
    e = _f32(params["embed"]["table"][input_ids])
    x = jnp.broadcast_to(e[:, None, :], (e.shape[0], n, e.shape[1]))
    s = x.shape[0]
    expert_layers = hp["layers"] - hp["dense"]
    if swaps is None:
        swaps = jnp.full((s, expert_layers, 2), -1, jnp.int32)

    def layer(name, routed):
        stack = {k: v for k, v in params[name].items() if k != "experts"}

        def step(x, xs):
            j, swap = xs
            lp = jax.tree_util.tree_map(lambda a: a[j], stack)
            x = sub_layer(lp["mhc_attn"], x, hp, lambda u: latent_attention(
                lp, _rms_norm(lp["attn_norm"]["scale"], u, hp["eps"]), hp))
            close = []

            def ffn(u):
                y = _rms_norm(lp["mlp_norm"]["scale"], u, hp["eps"])
                if not routed:
                    return _blocks(lambda _, yb: _swiglu(
                        yb, lp["w_gate"]["w"], lp["w_up"]["w"], lp["w_down"]["w"]),
                        s, y)
                out, near = routed_experts(
                    {**lp, "experts": (params[name]["experts"], j)}, y, hp, swap)
                close.append(near)
                return out

            x = sub_layer(lp["mhc_mlp"], x, hp, ffn)
            return x, (close[0] if routed else None)

        return step

    x, _ = jax.lax.scan(layer("dense_layers", False), x, (
        jnp.arange(hp["dense"]), jnp.zeros((hp["dense"], s, 2), jnp.int32)))
    x, near = jax.lax.scan(layer("layers", True), x, (
        jnp.arange(expert_layers), jnp.moveaxis(swaps, 1, 0)))
    x = jax.lax.dynamic_slice_in_dim(x.sum(axis=1), at, new, axis=0)
    near = tuple(jax.lax.dynamic_slice_in_dim(
        jnp.moveaxis(c, 0, 1), at, new, axis=0) for c in near)
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
    """Rules (a) and (b) over the given rows, as ``hetero_gqa_moe.judge_rows``
    applies them (this model's forward, this cell's limits): each row is one
    plain forward over prompt + served tokens, right-padded; teacher forcing
    feeds the SERVED tokens; a row with refused tokens is run again, each of
    them re-routed by its next candidate, accepted re-routings staying in
    place. ``shares`` scales the two limits (a rehearsal's, see ``judge``)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = hp["layers"] - hp["dense"]
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


def mixing_leaves_differ(placed, masters) -> int:
    """Rule (c) for the mixing (``stated_float32_leaves_differ`` reads the
    ``router*`` and ``*norm*`` leaves): the number of ``mhc_*`` values whose
    placed value is not the float32 master, bit for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        if not any(str(getattr(k, "key", k)).startswith("mhc_") for k in path):
            continue
        master = masters
        for k in path:
            master = master[k.key]
        a, b = np.asarray(leaf), np.asarray(master, np.float32)
        differ += int(a.size if a.dtype != np.float32
                      else (a.view(np.uint32) != b.view(np.uint32)).sum())
    return differ


def judge(ctx) -> dict:
    """Teacher-force the shortest and the longest of a seeded sample of the
    rows written and hold the served tokens to the plain forward; every
    written row must carry exactly ``max_new_tokens`` tokens (``eos_id``
    -1: no early exit). A rehearsal (hidden 128, 4 of 16 experts: nearly
    every position has a choice within a rounding of its boundary) holds the
    control flow, the counts and the stated leaves, and the shares to 25
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
    rng = np.random.default_rng([int(ctx.seed), 0x4D4843])
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
        proc.params, proc.host_params) + mixing_leaves_differ(
            proc.params, proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
