"""Plain reference for ``decoder_lm`` with a layer pattern of latent layers — sparse-indexed full layers beside sliding-window layers — and a held share of routed experts (dots3-note-prev, dots-studio 2026), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, no
absorbed products, experts as a loop with masks — written from the layer
equations of the model's description (DeepSeek-V2's latent attention with a
low-rank query, DeepSeek-V3.2's lightning indexer, DeepSeek-V3's routing, a
headwise sigmoid gate, the model's ``config.json`` for every size) and
independent of ``arkflow_tpu/models``. It reads only the program's parameter
tree, in the values the configuration states: bfloat16-rounded weights;
float32 router, selection bias, indexer and norm scales. Attention is
computed a block of queries at a time, so a 12k-token row fits.

Per layer, on ``x`` [S, hidden], ``n`` RMSNorm: ``x += Attn_kind(n(x))``,
``x += FFN(n(x))``. Both kinds of attention, on ``y = n(x)``:

* low-rank query — ``cq = n_q(y W_qa) sqrt(hidden / q_lora_rank)``,
  ``q = cq W_qb`` (heads x (nope | rope)), rope on the rope part;
* latent keys and values — ``[c | k_r] = y W_kva``; ``c = n_kv(c)
  sqrt(hidden / kv_lora_rank)``; ``[k_nope | v] = c W_kvb`` per head; ``k_r``
  rotated, ONE for all heads; ``softmax((q_nope . k_nope + q_rope . k_r) /
  sqrt(nope + rope))`` over the ALLOWED set; ``o = sum p v``;
* headwise gate — ``o_h <- sigmoid(y W_g)_h o_h``; then ``W_o``.

The allowed set of the query at ``t``:

* sliding layer — ``t - window < s <= t`` (``window`` keys, itself included),
  at the layer kind's own sizes and rope base;
* full layer — the ``min(top-k, t + 1)`` positions ``s <= t`` of largest
  ``I(t, s) = sum_j w_j(t) relu(q_j(t) . k(s))`` (of equal scores the
  earlier position): ``q = cq W_iq`` (index
  heads x index dim), ``k = LayerNorm(y W_ik)``, rope on the first ``rope``
  dims of both in split halves, ``w = y W_iw / sqrt(heads x dim)``. Index
  keys are cached in bfloat16 (stated in the configuration), so ``k`` is
  rounded to bfloat16 here too.

FFN: layer 0 dense SwiGLU; later layers ``s = sigmoid(y W_r)``, the top-k of
``s + b`` chosen, weighed by ``s`` normalised over ALL the chosen, times the
scaling factor; the sum over the chosen experts HELD here (``experts_held``:
the chip's share — what absent experts would add is left out, here as in the
program) plus the shared expert.

Departures from the publication, noted as the guide asks:

1. The indexer's Hadamard rotation is left out: it is orthogonal and applied
   to index queries and keys alike, so no score changes. Its fp8 key cache
   is left out too (bfloat16 keys; ROADMAP: fp8 index keys).
2. The multi-token-prediction modules are draft heads that do not enter the
   model's own logits, and the vision and audio towers are not the language
   model: neither is served or referenced.
3. Layout only: layers stack on a leading axis by (kind, dense | routed);
   weights are [in, out]; ``experts`` holds the held routed experts first
   and the shared expert after them; rope pairs (2i, 2i+1) are rotated in
   place in attention (the published code permutes to halves first — scores
   are invariant); weights and the selection bias are random from the seed.

``judge(ctx)`` teacher-forces a seeded sample of the rows written through
this forward and holds the served tokens to its logits under the rules of
``mla_moe_decoder.py`` — (a) the bf16 logit tolerance, (b) re-routing across
router near-ties, (c) the stated float32 leaves (here the indexer's too) —
and one more of the same shape:

(d) index near-tie — the served indexer's INPUT went through bfloat16
    products, so two index scores on either side of the top-k boundary that
    lie closer than ``INDEX_DELTA`` (of the spread of the query's scores)
    may swap, and the position then attends another set. A served token
    that rule (a) refuses is held to the reference RE-SELECTED: the index
    scores computed as the served path computes them (bfloat16 operands),
    admitted only where every position that changed sides lies within
    ``INDEX_DELTA`` of the boundary. Counted, reported and limited
    (``RESELECTED_SHARE``).
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.mla_moe_decoder import logit_tolerance, reroutings

#: rows sampled for the comparison: one plain forward each over the row's
#: own length (rounded up to a quarter of the longest), more for each round
#: of re-selection / re-routing a row needs
SAMPLE_ROWS = 4
#: queries a block of the blocked attention takes ([heads, block, keys]
#: float32 scores: 0.4 GB at 128 heads and 12,544 keys, beside 9.6 GB served)
BLOCK = 64
#: rule (b): as ``mla_moe_decoder.ROUTER_DELTA`` (same router, same reason).
#: One re-routing a position: with 32 of 256 experts held a swap seldom
#: touches an expert held, and re-routing explained 0-5 of 1,024 positions in
#: every run at published widths (PERF.md §6, PR 31); the limit is 3 times
#: that
ROUTER_DELTA = 6e-3
REROUTE_ROUNDS = 1
REROUTED_SHARE = 0.015
#: largest share of the positions checked that no admitted alternative
#: explains; ``guarantees.answers`` of the configuration states it. Readings
#: off the chip at published widths through the timed path (PERF.md §6, PR
#: 31), 1,024 positions a run: the served program 0.006-0.045 over eleven
#: seeds (mean 0.025); weights rounded to e4m3's 3 mantissa bits 0.312. What
#: they are (PERF.md §6, the replay on the chip): choices. The served
#: indexer reads a bfloat16 residual stream, and its selection differs from
#: this forward's at EVERY position past top-k, by keys up to 0.09 score
#: spreads from the boundary; rule (d) emulates the last product's bfloat16
#: operands alone (flips <= 0.0075) and rule (b) tries the next expert, so
#: some positions the program resolved a third way, at the position or at
#: an earlier one whose keys it reads. With the program's own selections
#: and routing replayed through this forward (``decoder_logits(forced=)``,
#: ``tests/replay_choices.py``) its logits lie within 0.049 (1.1 tolerances)
#: of this forward's at every one of 768 positions x 19,008 logits, against
#: 0.46-0.66 under this forward's own choices. A judge cannot replay from
#: the tokens alone, so the share is limited instead: at twice the largest
#: sound reading, under a third of the control's
UNEXPLAINED_SHARE = 0.09
#: rule (d): an index score within this share of the query's score spread
#: (standard deviation over its context) of the top-k boundary may change
#: sides: 3 times the widest flip any accepted re-selection needed (0.0075).
#: Accepted re-selected: 0.001-0.036 of the positions over the served
#: program's seeds (0.024 from the e4m3 weights: this share does not tell
#: them apart, it is limited — at 3 times the largest sound reading — so
#: that the rule cannot carry a run)
INDEX_DELTA = 0.025
RESELECTED_SHARE = 0.10


def _f32(w):
    import jax.numpy as jnp

    return w.astype(jnp.float32)


def _rms_norm(scale, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * _f32(scale)


def _layer_norm(p, x, eps):
    import jax.numpy as jnp

    x = x - x.mean(-1, keepdims=True)
    return (x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps)
            * _f32(p["scale"]) + _f32(p["bias"]))


def _angles(pos, d, theta):
    import jax.numpy as jnp

    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    return pos.astype(jnp.float32)[:, None] * inv[None, :]


def _rope_pairs(x, theta, pos):
    """Rotary embedding over the pairs (2i, 2i+1) of the last axis, the row
    on the FIRST axis at position ``pos`` [S]. x: [S, ..., d]."""
    import jax.numpy as jnp

    s, d = x.shape[0], x.shape[-1]
    ang = _angles(pos, d, theta).reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], axis=-1)
    return out.reshape(x.shape)


def _rope_halves(x, theta, pos):
    """Rotary embedding over the pairs (i, i + d/2) (the indexer's)."""
    import jax.numpy as jnp

    s, d = x.shape[0], x.shape[-1]
    ang = _angles(pos, d, theta).reshape((s,) + (1,) * (x.ndim - 2) + (d // 2,))
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
    return jax.tree_util.tree_map(lambda o: o.reshape(s, *o.shape[2:]), out)


def query_latent(lp, y, hp):
    return (_rms_norm(lp["q_norm"]["scale"], y @ _f32(lp["wq_a"]["w"]), hp["eps"])
            * math.sqrt(hp["hidden"] / hp["q_lora_rank"]))


def index_keys(lp, y, hp):
    """The ONE index key a token, ``LayerNorm(y W_k)`` with rope on its first
    ``rope`` dims, rounded to bfloat16 as the cache holds it. [S, index dim]."""
    import jax.numpy as jnp

    rope, pos = hp["rope"], jnp.arange(y.shape[0])
    key = _layer_norm(lp["index_k_norm"], y @ _f32(lp["index_wk"]["w"]), hp["eps"])
    key = jnp.concatenate(
        [_rope_halves(key[:, :rope], hp["theta"], pos), key[:, rope:]], -1)
    return key.astype(jnp.bfloat16).astype(jnp.float32)


def index_allowed(lp, y, cq, at, key, hp, reselect: bool = False, forced=None):
    """The full layer's allowed set of a block of queries (normed input
    ``y``, query latent ``cq``, positions ``at`` [B]) over the index keys
    ``key`` [S, index dim]: (mask [B, S], (widest flip, boundary margin)
    [B, 2]). ``reselect`` computes the scores from bfloat16 operands too and
    selects by those (rule d); ``forced`` [B, S] bool is a selection GIVEN
    (a served program's, replayed) and taken instead. ``widest flip`` is
    then, per query, the largest distance from the float32 boundary — in
    units of the query's score spread — of a key that changed sides, so a
    selection from anywhere can be held to the near-tie band.
    ``boundary margin`` is the
    distance between the last key chosen and the first not chosen, in the
    same units (inf while the context holds no more than top-k keys)."""
    import jax
    import jax.numpy as jnp

    s = key.shape[0]
    hi, di, rope, k = hp["index_heads"], hp["index_dim"], hp["rope"], hp["index_topk"]
    q = (cq @ _f32(lp["index_wq"]["w"])).reshape(-1, hi, di)
    q = jnp.concatenate(
        [_rope_halves(q[..., :rope], hp["theta"], at), q[..., rope:]], -1)
    w = (y @ _f32(lp["index_w"]["w"])) / math.sqrt(hi * di)
    causal = jnp.arange(s)[None, :] <= at[:, None]

    def choose(scores):
        """(mask, the last chosen score, the first not chosen, scores)."""
        masked = jnp.where(causal, scores, -jnp.inf)
        if s <= k:
            inf = jnp.full(scores.shape[:1], jnp.inf)
            return causal, -inf, -inf, masked
        # exactly k: among equal scores the earlier position (top_k is stable)
        top, idx = jax.lax.top_k(masked, k + 1)
        rows = jnp.arange(scores.shape[0])[:, None]
        mask = jnp.zeros(scores.shape, bool).at[rows, idx[:, :k]].set(True)
        return mask & causal, top[:, k - 1], top[:, k], masked

    def scored(q):
        return jnp.einsum("qhk,qh->qk", jax.nn.relu(
            jnp.einsum("qhd,kd->qhk", q, key)), w)

    scores = scored(q)
    mask, kth, nxt, masked = choose(scores)
    n = jnp.maximum(causal.sum(-1), 1)
    mean = jnp.where(causal, scores, 0).sum(-1) / n
    spread = jnp.sqrt(jnp.where(causal, jnp.square(scores - mean[:, None]),
                                0).sum(-1) / n) + 1e-30
    margin = jnp.where(jnp.isfinite(nxt), (kth - nxt) / spread, jnp.inf)
    if forced is not None:
        again = forced & causal
    elif not reselect:
        return mask, jnp.stack([jnp.zeros_like(margin), margin], -1)
    else:
        again = choose(scored(q.astype(jnp.bfloat16).astype(jnp.float32)))[0]
    flip = jnp.where(mask != again, jnp.abs(masked - kth[:, None]), 0.0)
    return again, jnp.stack([flip.max(-1) / spread, margin], -1)


def latent_attention(lp, y, hp, reselect: bool = False, forced=None):
    """One kind's latent attention over [S, hidden] in the published
    (expanded) form. The keys and values of every position are expanded
    once; the queries go a block at a time (projection, rope, allowed set,
    softmax, gate, ``W_o``), so a 12k-token row's scores never exist whole.
    ``forced`` [S, S] bool: the indexed layer's selection given (row t: the
    keys query t attends). Returns (output [S, hidden], (widest index flip,
    boundary margin) of each query [S, 2] — (0, inf) without an indexer)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, nope, rope, vd, lat = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                              hp["kv_lora_rank"])
    pos = jnp.arange(s)
    kv = y @ _f32(lp["wkv_a"]["w"])
    c = (_rms_norm(lp["kv_norm"]["scale"], kv[:, :lat], hp["eps"])
         * math.sqrt(hp["hidden"] / lat))
    k_r = _rope_pairs(kv[:, lat:], hp["theta"], pos)              # [S, rope]
    w_kvb = _f32(lp["wkv_b"]["w"]).reshape(lat, h, nope + vd)
    k_nope = jnp.einsum("sl,lhd->shd", c, w_kvb[..., :nope])
    v = jnp.einsum("sl,lhd->shd", c, w_kvb[..., nope:])
    indexed = hp["index_topk"] > 0
    key = index_keys(lp, y, hp) if indexed else None

    def block(q0, yb, fb=None):
        at = q0 + jnp.arange(yb.shape[0])
        cq = query_latent(lp, yb, hp)
        q = (cq @ _f32(lp["wq"]["w"])).reshape(-1, h, nope + rope)
        q_nope, q_rope = q[..., :nope], _rope_pairs(q[..., nope:], hp["theta"], at)
        if indexed:
            mask, flip = index_allowed(lp, yb, cq, at, key, hp, reselect, fb)
        else:
            mask = (pos[None, :] <= at[:, None]) & (pos[None, :] > at[:, None] - hp["window"])
            flip = jnp.stack([jnp.zeros(yb.shape[0]),
                              jnp.full(yb.shape[0], jnp.inf)], -1)
        scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                  + jnp.einsum("qhd,kd->hqk", q_rope, k_r)) / math.sqrt(nope + rope)
        scores = jnp.where(mask[None], scores, -1e30)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
        if hp["gate"]:
            o = o * jax.nn.sigmoid(yb @ _f32(lp["w_head_gate"]["w"]))[..., None]
        return o.reshape(-1, h * vd) @ _f32(lp["wo"]["w"]), flip

    if indexed and forced is not None:
        return _blocks(block, s, y, forced)
    return _blocks(block, s, y)


def route(lp, y, hp, swap=None, forced=None):
    """As ``mla_moe_decoder.route``: (chosen experts [S, k] of ALL the
    router's outputs, their weights [S, k], ``near``). ``forced`` [S, k]:
    the chosen experts given (a served program's, replayed); the weights
    stay this forward's own scores of them."""
    import jax
    import jax.numpy as jnp

    k = hp["top_k"]
    scores = jax.nn.sigmoid(y @ _f32(lp["router"]["w"]))
    top, idx = jax.lax.top_k(scores + _f32(lp["router_bias"]), k + 2)
    near = (top[:, k - 2:], idx[:, k - 2:])
    idx = idx[:, :k] if forced is None else forced
    if swap is not None:
        idx = jnp.where(idx == swap[:, :1], swap[:, 1:], idx)
    w = jnp.take_along_axis(scores, idx, axis=-1)                 # unbiased
    return idx, w / w.sum(-1, keepdims=True) * hp["scaling"], near


def routed_experts(lp, y, hp, swap=None, forced=None):
    """The held experts' part of the weighted sum (one expert at a time
    over every token with a mask) plus the shared experts' wide SwiGLU.
    ``lp["experts"]`` is the layer's experts or (the stack's, the layer's
    index): an expert's three matrices are then read out of the stack one
    expert at a time, and no layer's 1.6 GB is copied."""
    import jax
    import jax.numpy as jnp

    first, held = hp["held"]
    idx, w, near = route(lp, y, hp, swap, forced)
    ex, layer = lp["experts"] if isinstance(lp["experts"], tuple) else (
        jax.tree_util.tree_map(lambda a: a[None], lp["experts"]), 0)

    def expert(i):
        return [ex[k][layer, i] for k in ("w_gate", "w_up", "w_down")]

    def one_expert(acc, i):
        weight = jnp.where(idx == first + i, w, 0.0).sum(-1, keepdims=True)
        return acc + weight * _swiglu(y, *expert(i)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    shared = ex["w_gate"].shape[1] - held
    wide = [jnp.concatenate([_f32(m) for m in ms], axis=ax) for ms, ax in zip(
        zip(*[expert(held + j) for j in range(shared)]), (1, 1, 0))]
    return out + _swiglu(y, *wide), near


_FULL, _SLIDING = "full_attention", "sliding_attention"
_STACKS = {(_FULL, False): "dense_layers", (_FULL, True): "layers",
           (_SLIDING, False): "swa_dense_layers", (_SLIDING, True): "swa_layers"}


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read
    as a bag of the published keys; none of the program's code runs)."""
    common = {"hidden": cfg.dim, "eps": cfg.norm_eps,
              "gate": False, "window": 0, "index_topk": 0}
    full = dict(common, heads=cfg.heads, nope=cfg.qk_nope_head_dim,
                rope=cfg.qk_rope_head_dim, v=cfg.v_head_dim,
                kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
                theta=cfg.rope_theta, gate=cfg.attention_gate_type == "headwise",
                index_topk=cfg.index_topk, index_heads=cfg.index_n_heads,
                index_dim=cfg.index_head_dim, window=1 << 30)
    sliding = dict(common, heads=cfg.swa_heads, nope=cfg.swa_qk_nope_head_dim,
                   rope=cfg.swa_qk_rope_head_dim, v=cfg.swa_v_head_dim,
                   kv_lora_rank=cfg.swa_kv_lora_rank,
                   q_lora_rank=cfg.swa_q_lora_rank, theta=cfg.swa_rope_theta,
                   gate=cfg.swa_attention_gate_type == "headwise",
                   window=cfg.sliding_window)
    kinds = list(cfg.layer_types[:cfg.layers])
    return {
        _FULL: full, _SLIDING: sliding, "kinds": kinds, "eps": cfg.norm_eps,
        "dense": cfg.first_k_dense_replace, "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None,
                   reselect: bool = False, forced=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]; (the widest index flip, the smallest
    boundary margin) of each of them over the indexed layers [new, 2]).
    ``swaps`` [S, expert layers, 2] re-routes
    (``route``); ``reselect`` re-selects (rule d); ``forced`` = (selections
    [indexed layers, S, S] bool, chosen experts [expert layers, S, k]) replays
    choices given — a served program's — in place of this forward's own, the
    flips still measured against its own boundary. Layers are visited one by
    one in the model's order, each read out of its (kind, dense | routed)
    stack, so one layer's float32 copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near, seen, e, f = [], {}, 0, 0
    flips = jnp.stack([jnp.zeros(x.shape[0]), jnp.full(x.shape[0], jnp.inf)], -1)
    for i, kind in enumerate(hp["kinds"]):
        routed = i >= hp["dense"]
        name = _STACKS[kind, routed]
        j = seen.get(name, 0)
        seen[name] = j + 1
        lp = jax.tree_util.tree_map(lambda a: a[j], {
            k: v for k, v in params[name].items() if k != "experts"})
        if routed:
            lp["experts"] = (params[name]["experts"], j)
        given = None
        if forced is not None and hp[kind]["index_topk"]:
            given, f = forced[0][f], f + 1
        out, flip = latent_attention(
            lp, _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"]), hp[kind],
            reselect, given)
        x = x + out
        flips = jnp.stack([jnp.maximum(flips[:, 0], flip[:, 0]),
                           jnp.minimum(flips[:, 1], flip[:, 1])], -1)
        y = _rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"])
        if routed:
            out, n = routed_experts(
                lp, y, hp, None if swaps is None else swaps[:, e],
                None if forced is None else forced[1][e])
            near.append(n)
            e += 1
        else:
            out = _swiglu(y, lp["w_gate"]["w"], lp["w_up"]["w"], lp["w_down"]["w"])
        x = x + out
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    near = tuple(jax.lax.dynamic_slice_in_dim(
        jnp.stack([n[j] for n in near], axis=1), at, new, axis=0)
        for j in (0, 1))
    x = _rms_norm(params["norm_out"]["scale"], x, hp["eps"])
    return (x @ _f32(params["lm_head"]["w"]), near,
            jax.lax.dynamic_slice_in_dim(flips, at, new, axis=0))


def _row_forward(hp: dict, new: int, reselect: bool):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions."""
    import jax
    import jax.numpy as jnp

    def fn(params, row, at, served, swaps):
        logits, (near_s, near_e), flips = decoder_logits(
            params, row, at, new=new, hp=hp, swaps=swaps, reselect=reselect)
        top2 = jax.lax.top_k(logits, 2)[0]
        return {"best": top2[:, 0], "second": top2[:, 1],
                "served": jnp.take_along_axis(logits, served[:, None], 1)[:, 0],
                "absmax": jnp.abs(logits).max(), "near_scores": near_s,
                "near_experts": near_e, "flips": flips[:, 0],
                "index_margin": flips[:, 1]}

    return jax.jit(fn)


def row_width(n: int, longest: int) -> int:
    """The padded width a row of ``n`` positions is run at: a multiple of
    ``BLOCK`` near a quarter, a half, ... of the longest (few shapes compile,
    and a short row does not pay for the longest)."""
    step = -(-longest // (4 * BLOCK)) * BLOCK
    return min(-(-n // step) * step, -(-longest // BLOCK) * BLOCK)


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               delta: float = ROUTER_DELTA, index_delta: float = INDEX_DELTA,
               shares: float = 1.0) -> dict:
    """Rules (a), (b) and (d) of the module docstring over the sampled rows.
    Each row is one plain forward over prompt + served tokens, right-padded
    (causal attention never looks at the padding). Teacher forcing feeds the
    SERVED tokens. A row with refused tokens is run again re-selected (d),
    then its still-refused tokens re-routed by their next candidates (b),
    accepted re-routings staying in place, as in ``mla_moe_decoder``.
    ``shares`` scales the three limits (a rehearsal's, see ``judge``)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = len(hp["kinds"]) - hp["dense"]
    fns: dict = {}

    def run(r, swaps, reselect=False):
        pids, toks = prompt_ids[r], tokens[r]
        width = row_width(len(pids) + len(toks), longest)
        row = np.zeros((width,), np.int32)
        row[:len(pids)] = pids
        row[len(pids):len(pids) + len(toks)] = toks
        served = np.zeros((new,), np.int32)
        served[:len(toks)] = toks
        if (reselect,) not in fns:
            fns[reselect,] = _row_forward(hp, new, reselect)
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(fns[reselect,](
                params, row, np.int32(len(pids) - 1), served, swaps[:width]))
        return {k: np.asarray(v) for k, v in out.items()}

    none = np.full((longest + 4 * BLOCK, layers, 2), -1, np.int32)
    first = [run(r, none) for r in range(len(tokens))]
    tol = max(logit_tolerance(o["absmax"]) for o in first)
    checked = decided = ties = unexplained = near_ties = forwards = 0
    reselected, flips_admitted, gaps = 0, [], []
    moved = compared = 0
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
        refused = set(map(int, np.flatnonzero(gap > 2 * tol)))
        closest = {i: float(gap[i]) for i in refused}
        mode = False
        if refused and any(hp[k]["index_topk"] for k in hp["kinds"]):
            again = run(r, none, reselect=True)               # rule (d)
            forwards += 1
            # how far ONE other resolution of the index near-ties moves a
            # row: positions whose distance under the largest logit changed
            # by more than the admitted distance (reported, not judged)
            compared += n
            moved += int((np.abs((again["best"] - again["served"])[:n] - gap)
                          > 2 * tol).sum())
            took = [i for i in refused
                    if again["best"][i] - again["served"][i] <= 2 * tol
                    and again["flips"][i] <= index_delta]
            for i in took:
                flips_admitted.append(float(again["flips"][i]))
                refused.discard(i)
            reselected += len(took)
            if took:  # later positions attend what the served run attended
                mode, out = True, again
        pending = {i: reroutings(out["near_scores"][i], out["near_experts"][i],
                                 delta)[:REROUTE_ROUNDS] for i in refused}
        swaps = none.copy()
        for _ in range(REROUTE_ROUNDS):                       # rule (b)
            trying = {i: c.pop(0) for i, c in pending.items() if c}
            if not trying:
                break
            trial = swaps.copy()
            for i, (_, _, moves) in trying.items():
                for layer, drop, add in moves:
                    trial[at + i, layer] = (drop, add)
            again = run(r, trial, reselect=mode)
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
                f"nearest admitted alternative's (admitted: {2 * tol:.4f}); "
                f"router gaps by expert layer "
                f"{np.round(near[i][:, 1] - near[i][:, 2], 5).tolist()}")
    n = max(checked, 1)
    return {"ok": bool(decided > 0 and len(gaps) <= shares * REROUTED_SHARE * n
                       and reselected <= shares * RESELECTED_SHARE * n
                       and unexplained <= shares * UNEXPLAINED_SHARE * n),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "unexplained": unexplained,
            "unexplained_share": unexplained / n,
            "rerouted": len(gaps), "rerouted_share": len(gaps) / n,
            "widest_gap_rerouted": max(gaps, default=0.0),
            "reselected": reselected, "reselected_share": reselected / n,
            "widest_flip_reselected": max(flips_admitted, default=0.0),
            "moved_by_reselection_share": moved / max(compared, 1),
            "router_delta": delta, "index_delta": index_delta,
            "router_near_tie_share": near_ties / n,
            "extra_forwards": forwards,
            "first_unexplained": first_unexplained, "logit_tol": tol}


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 — every leaf of a ``router*``, ``index_*`` or ``*norm*``
    entry — whose placed value is not the float32 master, bit for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if not any("router" in k or "norm" in k or "index_" in k for k in keys):
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
    ``max_new_tokens`` tokens (``eos_id`` -1: no early exit). The shares'
    limits are those of the published widths: at a rehearsal's (hidden 64,
    16 of ~160 keys, 4 of 16 experts) ONE choice that rounding turns at an
    earlier position moves every later logit by tenths through the keys it
    writes (``tests/test_sparse_window_moe.py``), which no alternative of
    the position itself explains — a rehearsal holds the control flow, the
    counts and the stated leaves, and the shares to 25 times their limits."""
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
    rng = np.random.default_rng([int(ctx.seed), 0xD075])
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
