"""Plain reference for ``decoder_lm`` with Gated DeltaNet layers among gated grouped-query attention layers of 256-wide heads, softmax-routed experts of which a share is held, and a sigmoid-gated shared expert (Qwen3-Next-80B-A3B-Instruct, Qwen 2025, ``model_type: qwen3_next``), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, NO chunked
form — the delta rule runs TOKEN BY TOKEN as the configuration file writes it
—, attention as a masked softmax over every earlier key (a block of queries at
a time, so a 9,216-token row fits), every held expert over every token with a
mask. Written from the layer equations of the configuration file
(``benchmark/configs/qwen3-next-80b-a3b-l8-ep8.json``: the model's
``config.json`` for every size, its ``assumed`` for what the keys do not
state) and independent of ``arkflow_tpu/models``. It reads only the program's
parameter tree, in the values the configuration states: bfloat16-rounded
weights; float32 router, shared gate, ``A_log``, ``dt_bias`` and norm scales.

One layer on ``x`` [S, hidden]; ``n`` is RMSNorm with the scale held as an
offset from one, ``x / sqrt(mean(x^2) + 1e-6) * (1 + w)``; no bias anywhere::

    y = n(x)
    linear_attention layer (16 key heads, 32 value heads of 128, conv 4):
        [q | k | v | z] = y W_qkvz;  [b | a] = y W_ba
        c_t = silu(sum_{j=0..3} w[:, j] * [q | k | v]_{t-3+j})   (depthwise,
                                                  causal, zeros before the
                                                  sequence, no bias)
        q, k <- x * rsqrt(sum x^2 + 1e-6) a head; key head j serves value
            heads 2j, 2j + 1;  q <- q * 128^-0.5
        beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)
        per value head, S [128 key, 128 value], from zeros:
            S <- exp(g_t) S;  u = S^T k_t;  d = beta_t (v_t - u)
            S <- S + k_t (x) d;  o_t = S^T q_t
        o <- o / sqrt(mean(o^2) + 1e-6) * w_n * silu(z)   a head (plain w_n)
        x = x + o W_out
    full_attention layer (16 query heads, 2 K/V heads of 256):
        q = y Wq, gate = y Wg -> [16, 256];  k, v = y Wk, y Wv -> [2, 256]
        q = n_256(q), k = n_256(k)    a head, one scale set each (1 + w)
        the first 64 values of a head rotated in split halves (i, i + 32) at
            base 1e7
        a = softmax(q k^T / sqrt(256) over j <= t) v;  head h reads K/V
            head h // 8
        x = x + (a * sigmoid(gate)) Wo
    y = n(x)
    p = softmax(y Wr) float32 over 512; the 10 largest chosen (no bias);
        w = p / sum(p over the chosen)
    x = x + sum over the chosen experts HELD here of w_e E_e(y)
          + sigmoid(y w_sg) E_shared(y)                        (width 512)

After the last layer a final ``n`` and the head; embedding rows unscaled.
``experts_held`` is the chip's share of an 8-way expert-parallel deployment:
the router keeps its 512 outputs and 10 choices, weights are normalised over
ALL the chosen, and what absent experts would add is left out — here as in
the program (``tests/test_gdn_gqa_moe.py`` adds the eight shares, the shared
expert counted once, up to the uncut layer). Layout only: layers stack on a
leading axis by kind (``gdn_layers``, ``layers``), weights are [in, out],
``experts`` holds the held routed experts first and the shared one after
them, the query projection's gate half is a leaf of its own.

``judge(ctx)`` holds what the TIMED path wrote to this forward:

(a) bf16 logit tolerance, (b) router near-tie re-routing, (c) the stated
    float32 leaves served as stated — the rules of ``window_gqa_moe.py`` —
    over the rows that a seeded sample of SLOTS held last, the one of
    shortest and the one of longest prompt (each a second or later tenant of
    its slot). Rule (b) reads the router's LOGITS (softmax keeps their
    order): two on either side of the selection boundary closer than
    ``ROUTER_DELTA`` may be chosen the other way by the served path, whose
    router input went through bfloat16 products (re-routed positions are
    counted and reported; what no admitted re-routing explains is limited:
    ``UNEXPLAINED_SHARE``). Among 512 outputs the 10th
    and the 11th logit lie ~0.02 apart, so most positions have a near-tie at
    one of eight routers; seven swaps in eight move two ABSENT experts and
    change only the normalisation of the held ones' weights. Limits hold over
    all positions and over each judged row of its own.
(d) the state and the conv window each sampled row left in its slot's row of
    the ``gdn`` pool when the run drained (``GenerationServer.slot_state``)
    are this forward's after the positions the row FED (its prompt and all
    but the last of its tokens). The first linear layer lies ahead of every
    router: its largest relative distance over heads (and its window's) is
    held to ``STATE_REL_ERR``. Behind routers a near-tie the served path
    rightly chose the other way (rule (b)) moves a position's inputs by
    tenths, and a state is its last few positions': about every second row
    carries one such position in some layer (PERF.md §6, PR 49: the largest
    (layer, head) distance of a sound run reads 0.09 to 0.26), so there the
    MEDIAN over (row, layer) of a layer's distance is held, to
    ``STATE_REL_ERR_BEHIND`` (the probe's one token, which has no context:
    ``PROBE_REL_ERR_BEHIND``): what moves EVERY position (a gate left out,
    a norm's scale) moves every pair, a near-tie's choice a few. With
    the family's seeding of ``A_log`` a head forgets within a few tokens, so
    a state is its last tokens' and the rounding of a state HELD IN BFLOAT16
    (0.1 % rms) lies under the served inputs' own (0.2 %): no distance tells
    the two apart. The pool is float32 as the configuration states, and that
    is held as rule (c) holds the leaves: ``state_bf16_values_share``, the
    share of a state's values that a bfloat16 holds exactly, is ~2^-16 of a
    float32 accumulator's and 1 of a bfloat16 state's.
(e) after the drain ONE more request goes through the served program, a
    one-token prompt asking for one token: its chunk starts a sequence in a
    slot that was held before, so the window it leaves is zeros before its
    one input and the state ``k (x) beta v`` of that token alone, in every
    linear layer — a state or a window that survives a slot's reuse shows
    here (a slow head keeps half of its last tenant's state a token) and
    nowhere else. The token is chosen for it (``reuse_probe``): the one of 64
    seeded candidates this forward routes furthest from a tie that involves
    a held expert, so that behind routers its states are continuous values,
    where a gate left out shows by tenths and the tokens' rules are coarse.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.mla_moe_decoder import logit_tolerance, reroutings
from benchmark.references.window_gqa_moe import (BLOCK, REROUTE_ROUNDS, _blocks,
                                                 _f32, _swiglu, row_width)

#: slots drawn for the comparison; of the rows that held them last the one
#: of shortest and the one of longest prompt are held to the token rules,
#: every one to rule (d)
SAMPLE_SLOTS = 4
#: rule (b), in LOGIT units: the served router's input went through bfloat16
#: products, its logits' error stays within a few 1e-3
ROUTER_DELTA = 0.02
#: largest share of the positions checked that no admitted re-routing
#: explains, over all positions and over each judged row of
#: ``ROW_POSITIONS`` or more. On the chip (PERF.md §6, PR 49) the served
#: program reads 0.012 to 0.023 over all positions and at most 0.0254 on a
#: row of 1,024 (twenty runs, forty rows); the controls read: the attention
#: gate left out 0.059 (0.070 and 0.047 on its rows of 256), delta without
#: - u 0.125, products at 3 mantissa bits 0.49, every other 0.98 to 1.0. The
#: limit stands 1.6 x over the largest sound row (six of the rows' standard
#: deviations over their mean) and 1.5 x under the smallest control. The share
#: accepted only RE-ROUTED is reported and limits nothing: no control moves
#: it (the program 0.012–0.020, the controls 0.0–0.045)
UNEXPLAINED_SHARE = 0.04
ROW_POSITIONS = 64
#: rule (d), (e): largest relative distance, over the first linear layer's
#: heads, of a state in the pool from this forward's (and of that layer's
#: conv window): the layer ahead of every router. And the median, over (row,
#: linear layer behind a router), of a layer's distance (norms over all its
#: heads; its window's where larger). On the chip (PERF.md §6, PR 49) the
#: served program reads 0.0040 to 0.0043 ahead of the routers (rows and the
#: probe alike, fourteen runs); products at 3 mantissa bits 0.048, delta
#: without - u 0.074, q / k not normalised 1.8, the conv a row late 1.6, w
#: for 1 + w 1.03, no decay 32: the limit stands 3.5 x over the largest sound
#: reading and 3.2 x under the smallest control's. Behind routers the rows'
#: median reads 0.035 to 0.061 sound and 1.06 / 1.41 with the shared / the
#: output gate left out (the gates are what it is for; the attention gate's
#: 0.075 is the tokens' and the probe's to refuse); the PROBE's one token,
#: chosen far from every tie, reads 0.020 to 0.029 sound (a RANDOM token
#: read 0.110 once in eighteen runs) and 0.36 (3 mantissa bits), 0.54
#: (attention gate), 0.97 / 1.40 (shared / output gate) under the controls
STATE_REL_ERR = 0.015
STATE_REL_ERR_BEHIND = 0.25
PROBE_REL_ERR_BEHIND = 0.08
#: rule (e): seeded one-token prompts this forward routes, and the gap
#: across the selection boundary (logit units) over which a choice counts as
#: far from a tie: twice ``ROUTER_DELTA``
PROBE_CANDIDATES = 64
PROBE_DELTA = 0.04
#: rule (d): a float32 accumulator's values are bfloat16's with chance 2^-16
STATE_BF16_SHARE = 0.01

_FULL, _LINEAR = "full_attention", "linear_attention"
#: the program's stack of a layer's kind (every layer routes)
_STACKS = {_FULL: "layers", _LINEAR: "gdn_layers"}


def _norm1(scale, x, eps):
    """RMSNorm with the scale held as an offset from one."""
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * (
        1.0 + _f32(scale))


def _rope_first(x, theta, pos, rotary: int):
    """The first ``rotary`` values of a head rotated in split halves (i, i +
    rotary / 2), the rest as they are. x: [S, heads, d]; pos [S]."""
    import jax.numpy as jnp

    inv = 1.0 / (float(theta) ** (jnp.arange(0, rotary, 2, dtype=jnp.float32)
                                  / rotary))
    ang = (pos.astype(jnp.float32)[:, None] * inv[None, :])[:, None, :]
    a, b = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            a * jnp.sin(ang) + b * jnp.cos(ang),
                            x[..., rotary:]], axis=-1)


def gated_delta_net(lp, y, hp, fed):
    """A linear_attention layer's mixer over [S, hidden] from the sequence's
    start. Returns (its output [S, hidden], the state after ``fed``
    positions [value heads, key dim, value dim], the conv's inputs of the
    ``taps - 1`` positions before position ``fed`` [taps - 1, channels],
    zeros before the sequence)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    nk, nv, dk, dv, taps = (hp["key_heads"], hp["value_heads"], hp["key_dim"],
                            hp["value_dim"], hp["taps"])
    conv = 2 * nk * dk + nv * dv
    u = y @ _f32(lp["gdn_in"]["w"])
    qkv, z = u[:, :conv], u[:, conv:]
    ba = y @ _f32(lp["gdn_ba"]["w"])
    ext = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    w = _f32(lp["gdn_conv_w"])                                    # [channels, taps]
    c = jax.nn.silu(sum(ext[j:j + s] * w[:, j] for j in range(taps)))

    def unit(x):
        x = x.reshape(s, nk, dk)
        x = x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)
        return jnp.repeat(x, nv // nk, axis=1)

    q = unit(c[:, :nk * dk]) * dk ** -0.5
    k = unit(c[:, nk * dk:2 * nk * dk])
    v = c[:, 2 * nk * dk:].reshape(s, nv, dv)
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(_f32(lp["gdn_A_log"])) * jax.nn.softplus(
        ba[:, nv:] + _f32(lp["gdn_dt_bias"]))

    def token(carry, xs):
        state, kept = carry
        q_t, k_t, v_t, g_t, b_t, t = xs
        state = jnp.exp(g_t)[:, None, None] * state
        back = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - back))[:, None, :]
        kept = jnp.where(t == fed - 1, state, kept)
        return (state, kept), jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((nv, dk, dv), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero),
                                (q, k, v, g, beta, jnp.arange(s)))
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True) + hp["eps"])
    o = (o * _f32(lp["gdn_norm"]["scale"])).reshape(s, nv * dv) * jax.nn.silu(z)
    # ext row i is position i - (taps - 1): the window before position fed
    return (o @ _f32(lp["gdn_out"]["w"]), kept,
            jax.lax.dynamic_slice_in_dim(ext, fed, taps - 1, axis=0))


def gated_attention(lp, y, hp):
    """A full_attention layer's mixer over [S, hidden]: keys and values of
    every position projected once, the queries a block at a time."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, kvh, dh = hp["heads"], hp["kv_heads"], hp["dh"]
    pos = jnp.arange(s)

    def heads(t, n, at, norm):
        t = _norm1(lp[norm]["scale"], t.reshape(-1, n, dh), hp["eps"])
        return _rope_first(t, hp["theta"], at, hp["rotary"])

    k = heads(y @ _f32(lp["wk"]["w"]), kvh, pos, "k_head_norm")   # [S, kv, dh]
    v = (y @ _f32(lp["wv"]["w"])).reshape(s, kvh, dh)

    def block(q0, yb):
        at = q0 + jnp.arange(yb.shape[0])
        q = heads(yb @ _f32(lp["wq"]["w"]), h, at, "q_head_norm")
        q = q.reshape(-1, kvh, h // kvh, dh)      # head h reads K/V head h // group
        scores = jnp.einsum("qkgd,skd->kgqs", q, k) / math.sqrt(dh)
        mask = pos[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], scores, -1e30), -1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(-1, h * dh)
        return (o * jax.nn.sigmoid(yb @ _f32(lp["w_out_gate"]["w"]))
                ) @ _f32(lp["wo"]["w"])

    return _blocks(block, s, y)


def route(lp, y, hp, swap=None):
    """(chosen experts [S, k] of ALL the router's outputs, their weights
    [S, k], ``near``: the router logits [S, 4] and the experts [S, 4] of the
    two last chosen and the two first not chosen). ``swap`` [S, 2]
    re-routes: where a position's chosen experts hold ``swap[:, 0]`` it is
    replaced by ``swap[:, 1]`` (-1: none)."""
    import jax
    import jax.numpy as jnp

    k = hp["top_k"]
    logits = y @ _f32(lp["router"]["w"])
    p = jax.nn.softmax(logits, axis=-1)
    top, idx = jax.lax.top_k(logits, k + 2)
    near = (top[:, k - 2:], idx[:, k - 2:])
    idx = idx[:, :k]
    if swap is not None:
        idx = jnp.where(idx == swap[:, :1], swap[:, 1:], idx)
    w = jnp.take_along_axis(p, idx, axis=-1)
    return idx, w / w.sum(-1, keepdims=True), near


def routed_experts(lp, y, hp, swap=None):
    """The held experts' part of the weighted sum (one expert at a time over
    every token with a mask) plus the shared expert's SwiGLU under its
    sigmoid gate. ``lp["experts"]`` is (the stack's experts, the layer's
    index): an expert's three matrices are read out of the stack one expert
    at a time."""
    import jax
    import jax.numpy as jnp

    first, held = hp["held"]
    idx, w, near = route(lp, y, hp, swap)
    ex, layer = lp["experts"]

    def expert(i):
        return [ex[k][layer, i] for k in ("w_gate", "w_up", "w_down")]

    def one_expert(acc, i):
        weight = jnp.where(idx == first + i, w, 0.0).sum(-1, keepdims=True)
        return acc + weight * _swiglu(y, *expert(i)), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y), jnp.arange(held))
    gate = jax.nn.sigmoid(y @ _f32(lp["shared_gate"]["w"]))       # [S, 1]
    for j in range(held, ex["w_gate"].shape[1]):
        out = out + gate * _swiglu(y, *expert(j))
    return out, near


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read as
    a bag of keys; none of the program's code runs)."""
    dh = cfg.head_dim or cfg.dim // cfg.heads
    return {
        "heads": cfg.heads, "kv_heads": cfg.kv_heads, "dh": dh,
        "theta": cfg.rope_theta, "rotary": int(dh * cfg.partial_rotary_factor),
        "eps": cfg.norm_eps, "kinds": list(cfg.layer_types[:cfg.layers]),
        "key_heads": cfg.linear_num_key_heads,
        "value_heads": cfg.linear_num_value_heads,
        "key_dim": cfg.linear_key_head_dim, "value_dim": cfg.linear_value_head_dim,
        "taps": cfg.linear_conv_kernel_dim, "top_k": cfg.num_experts_per_tok,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None,
                   fed=0):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: router
    logits and experts [new, layers, 4]; the linear layers' states after
    ``fed`` positions [linear layers, value heads, key dim, value dim] and
    their conv windows before position ``fed`` [linear layers, taps - 1,
    channels]). ``swaps`` [S, layers, 2] re-routes (``route``). Layers are
    visited one by one in the model's order, each read out of its kind's
    stack, so one layer's float32 copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near, states, windows, seen = [], [], [], {}
    for i, kind in enumerate(hp["kinds"]):
        name = _STACKS[kind]
        j = seen[name] = seen.get(name, -1) + 1
        lp = jax.tree_util.tree_map(lambda a: a[j], {
            k: v for k, v in params[name].items() if k != "experts"})
        y = _norm1(lp["attn_norm"]["scale"], x, hp["eps"])
        if kind == _LINEAR:
            out, state, window = gated_delta_net(lp, y, hp, fed)
            states.append(state)
            windows.append(window)
        else:
            out = gated_attention(lp, y, hp)
        x = x + out
        y = _norm1(lp["mlp_norm"]["scale"], x, hp["eps"])
        lp["experts"] = (params[name]["experts"], j)
        out, n = routed_experts(lp, y, hp,
                                None if swaps is None else swaps[:, i])
        near.append(n)
        x = x + out
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    near = tuple(jax.lax.dynamic_slice_in_dim(
        jnp.stack([n[j] for n in near], axis=1), at, new, axis=0)
        for j in (0, 1))
    x = _norm1(params["norm_out"]["scale"], x, hp["eps"])
    return (x @ _f32(params["lm_head"]["w"]), near, jnp.stack(states),
            jnp.stack(windows))


def _row_forward(hp: dict, new: int):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions."""
    import jax
    import jax.numpy as jnp

    def fn(params, row, at, served, swaps, fed):
        logits, (near_s, near_e), states, windows = decoder_logits(
            params, row, at, new=new, hp=hp, swaps=swaps, fed=fed)
        top2 = jax.lax.top_k(logits, 2)[0]
        return {"best": top2[:, 0], "second": top2[:, 1],
                "served": jnp.take_along_axis(logits, served[:, None], 1)[:, 0],
                "absmax": jnp.abs(logits).max(), "near_scores": near_s,
                "near_experts": near_e, "states": states, "windows": windows}

    return jax.jit(fn)


def head_rel_err(got, want) -> np.ndarray:
    """|got - want| / |want| a (layer, head) of states [layers, heads, key
    dim, value dim] (norms over a head's matrix)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.sqrt(np.square(got - want).sum(axis=(2, 3)))
    return diff / np.maximum(np.sqrt(np.square(want).sum(axis=(2, 3))), 1e-30)


def layer_rel_err(got, want) -> np.ndarray:
    """|got - want| / |want| a layer of conv windows [layers, rows, channels]."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.sqrt(np.square(got - want).sum(axis=(1, 2)))
    return diff / np.maximum(np.sqrt(np.square(want).sum(axis=(1, 2))), 1e-30)


def bf16_values_share(state) -> float:
    """Share of a float32 state's non-zero values that a bfloat16 holds
    exactly (their low 16 bits are zero); 1.0 for any other dtype."""
    state = np.asarray(state)
    if state.dtype != np.float32:
        return 1.0
    bits = state.view(np.uint32)[state != 0]
    return float(((bits & 0xFFFF) == 0).mean()) if bits.size else 1.0


def state_verdict(states, windows, want_states, want_windows) -> dict:
    """Rule (d) / (e) over one row: the served states and windows against
    the forward's — ``ahead``: the first linear layer's largest distance
    over heads (and its window's); ``behind``: each later linear layer's
    distance over all its heads (its window's where larger);
    ``behind_head``: the largest over those layers' heads."""
    heads = head_rel_err(states, want_states)
    rows = layer_rel_err(windows, want_windows)
    whole = layer_rel_err(np.asarray(states).reshape(len(heads), 1, -1),
                          np.asarray(want_states).reshape(len(heads), 1, -1))
    return {"ahead": float(max(heads[0].max(), rows[0])),
            "behind": np.maximum(whole, rows)[1:].tolist(),
            "behind_head": float(heads[1:].max()) if len(heads) > 1 else 0.0,
            "bf16_share": bf16_values_share(states)}


def _median(values) -> float:
    return float(np.median(values)) if len(values) else 0.0


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               states=None, windows=None, delta: float = ROUTER_DELTA,
               shares: float = 1.0, token_rows=None) -> dict:
    """Rules (a), (b) and (d) over the given rows: each row is one plain
    forward over prompt + served tokens, right-padded (causal layers never
    look at the padding, and a token's routing depends on no other token);
    teacher forcing feeds the SERVED tokens; a row with refused tokens is
    run again, each of them re-routed by its next candidate, accepted
    re-routings staying in place. ``states`` / ``windows``: what each row
    left in the pool, held to the forward's after the positions the row
    fed. ``token_rows``: the rows rules (a) and (b) cover (all unless
    given; rule (d) holds every row). ``shares`` scales the limits (a
    rehearsal's, see ``judge``)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = len(hp["kinds"])
    fn = _row_forward(hp, new)
    # ONE padded width for the rows judged (the longest's): a second program
    # costs a cold run its compile
    width = row_width(max(len(p) + len(t) for p, t in zip(prompt_ids, tokens)),
                      longest)

    def run(r, swaps):
        pids, toks = prompt_ids[r], tokens[r]
        row = np.zeros((width,), np.int32)
        row[:len(pids)] = pids
        row[len(pids):len(pids) + len(toks)] = toks
        served = np.zeros((new,), np.int32)
        served[:len(toks)] = toks
        # the last decode step fed all but the last token
        fed = len(pids) + len(toks) - 1
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(fn(params, row, np.int32(len(pids) - 1),
                                    served, swaps[:width], np.int32(fed)))
        return {k: np.asarray(v) for k, v in out.items()}

    none = np.full((longest + 4 * BLOCK, layers, 2), -1, np.int32)
    first = [run(r, none) for r in range(len(tokens))]
    token_rows = range(len(tokens)) if token_rows is None else token_rows
    tol = max(logit_tolerance(first[r]["absmax"]) for r in token_rows)
    checked = decided = ties = unexplained = near_ties = forwards = 0
    gaps, worst, by_row = [], 0.0, []
    first_unexplained = None
    for r in token_rows:
        toks, out = tokens[r], first[r]
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
        swaps, accepted = none.copy(), 0
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
                    accepted += 1
                    for layer, drop, add in moves:
                        swaps[at + i, layer] = (drop, add)
                    del pending[i]
        unexplained += len(pending)
        by_row.append([len(pending), accepted, n, len(prompt_ids[r])])
        for i in sorted(pending)[:1]:
            first_unexplained = first_unexplained or (
                f"row {r} step {i}: token {toks[i]} lies {gap[i]:.4f} under "
                f"the reference's largest logit, {closest[i]:.4f} under the "
                f"nearest re-routing's (admitted: {2 * tol:.4f}); gaps across "
                f"the selection boundary by layer "
                f"{np.round(near[i][:, 1] - near[i][:, 2], 5).tolist()}")
    n = max(checked, 1)
    held = [state_verdict(st, win, out["states"], out["windows"])
            for st, win, out in zip(states or (), windows or (), first)]
    ahead = max((h["ahead"] for h in held), default=0.0)
    behind = _median([e for h in held for e in h["behind"]])
    bf16 = max((h["bf16_share"] for h in held), default=0.0)
    limit = shares * UNEXPLAINED_SHARE
    return {"ok": bool(decided > 0 and unexplained <= limit * n
                       and all(left <= limit * of for left, _, of, _ in by_row
                               if of >= ROW_POSITIONS)
                       and ahead <= STATE_REL_ERR
                       and behind <= shares * STATE_REL_ERR_BEHIND
                       and bf16 <= STATE_BF16_SHARE),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "unexplained": unexplained,
            "unexplained_share": unexplained / n,
            "unexplained_by_row": by_row,
            "rerouted": len(gaps), "rerouted_share": len(gaps) / n,
            "widest_gap_rerouted": max(gaps, default=0.0),
            "largest_distance_under_best": worst,
            "router_delta": delta, "router_near_tie_share": near_ties / n,
            "reroute_forwards": forwards, "state_rel_err": ahead,
            "state_rel_err_behind_routers": behind,
            "state_rel_err_behind_routers_largest_head": max(
                (h["behind_head"] for h in held), default=0.0),
            "state_bf16_values_share": bf16, "rows_with_states_held": len(held),
            "first_unexplained": first_unexplained, "logit_tol": tol}


def probe_candidates(params, hp: dict, tokens) -> dict:
    """This forward over each of ``tokens`` as a one-token sequence (a second
    position of padding behind it), reduced to what rule (e) reads: ``near``
    scores and experts [tokens, layers, 4], the linear layers' states after
    the token and their windows."""
    import jax
    import jax.numpy as jnp

    def one(params, token):
        row = jnp.stack([token, jnp.zeros_like(token)])
        _, (near_s, near_e), states, windows = decoder_logits(
            params, row, 0, new=1, hp=hp, fed=1)
        return {"near_scores": near_s[0], "near_experts": near_e[0],
                "states": states, "windows": windows}

    # the weights are an ARGUMENT: closed over, 4 GB of them would be
    # constants of the program
    with jax.default_matmul_precision("highest"):
        out = jax.jit(jax.vmap(one, in_axes=(None, 0)))(
            params, jnp.asarray(tokens, jnp.int32))
    return {k: np.asarray(v) for k, v in out.items()}


def routed_far_from_a_tie(near_scores, near_experts, held: tuple) -> np.ndarray:
    """[tokens, layers] bool: True where no choice across the selection
    boundary closer than ``PROBE_DELTA`` involves an expert HELD here (a swap
    of two absent experts moves only the held ones' normalisation, by the
    difference of two near-equal probabilities)."""
    first, count = held
    here = (near_experts >= first) & (near_experts < first + count)
    far = np.ones(near_scores.shape[:2], bool)
    for a in (0, 1):          # the two last chosen
        for b in (2, 3):      # the two first not chosen
            close = near_scores[..., a] - near_scores[..., b] < PROBE_DELTA
            far &= ~(close & (here[..., a] | here[..., b]))
    return far


def reuse_probe(server, params, hp: dict, seed: int, vocab: int,
                shares: float = 1.0) -> dict:
    """Rule (e): a one-token prompt asking for one token through the served
    program, after the drain, then what its chunk left in its slot: the
    window's rows before the sequence ZERO, its last row the token's own
    projected input, and the state the one token's ``k (x) beta v`` from a
    ZERO state. A one-token sequence has no context, so a linear layer's
    state depends on the token's OWN routing at the layers ahead of it
    alone: the token is the one of ``PROBE_CANDIDATES`` seeded ones whose
    leading layers this forward routes FURTHEST from a tie (no choice within
    ``PROBE_DELTA`` that involves a held expert), and behind routers the
    linear layers behind those layers only are held (their median, to
    ``PROBE_REL_ERR_BEHIND``: one token whose served router resolved a
    near-tie of a held expert the other way read 0.110 on an otherwise sound
    run, PERF.md §6, PR 49); ``shares`` scales that limit (a rehearsal's,
    see ``judge``)."""
    import asyncio

    rng = np.random.default_rng([int(seed), 0x50524F42])
    tokens = rng.choice(np.arange(1, vocab), PROBE_CANDIDATES, replace=False)
    cand = probe_candidates(params, hp, tokens)
    far = routed_far_from_a_tie(cand["near_scores"], cand["near_experts"],
                                hp["held"])
    lead = np.cumprod(far, axis=1).sum(axis=1)   # leading layers far from a tie
    best = int(np.argmax(lead))
    token = int(tokens[best])
    asyncio.run(server.generate([token], max_new_tokens=1))
    found = [st for st in map(server.slot_state, range(server.slots))
             if st["prompt"] is not None and list(st["prompt"]) == [token]
             and not st["tokens"][1:]]
    if len(found) != 1:
        return {"ok": False, "why": f"the probe holds {len(found)} slots"}
    window = np.asarray(found[0]["window"], np.float32)
    before = float(np.abs(window[:, :-1]).max())
    got = state_verdict(found[0]["state"], window, cand["states"][best],
                        cand["windows"][best])
    # a linear layer at place i of the model lies behind routers 0 .. i - 1
    places = [i for i, kind in enumerate(hp["kinds"]) if kind == _LINEAR][1:]
    held = [e for i, e in zip(places, got["behind"]) if i <= lead[best]]
    behind = _median(held)
    return {"ok": bool(before == 0.0 and got["ahead"] <= STATE_REL_ERR
                       and behind <= shares * PROBE_REL_ERR_BEHIND
                       and found[0]["tenancy"] >= 2),
            "token": token, "tenancy": int(found[0]["tenancy"]),
            "before_abs_max": before, "state_rel_err": got["ahead"],
            "state_rel_err_behind_routers": behind,
            "state_rel_err_behind_routers_largest_head": got["behind_head"],
            "layers_routed_far_from_a_tie": int(lead[best]),
            "linear_layers_held_behind_routers": len(held)}


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 — the router, the shared gate, ``A_log``, ``dt_bias`` and
    every norm scale — whose placed value is not the float32 master, bit
    for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if not any("router" in k or "norm" in k or k in (
                "shared_gate", "gdn_A_log", "gdn_dt_bias") for k in keys):
            continue
        master = masters
        for k in path:
            master = master[k.key]
        a, b = np.asarray(leaf), np.asarray(master, np.float32)
        differ += int(a.size if a.dtype != np.float32
                      else (a.view(np.uint32) != b.view(np.uint32)).sum())
    return differ


def last_tenants(server, slots, written: dict, want: int) -> tuple:
    """Of each slot of ``slots``: the row that held it last — its prompt, its
    tokens, which tenant of the slot it was — and the state and the window
    it left in the slot's row of the pool. Returns (rows, why not): a slot
    that no finished row holds, or whose row was not written with these
    tokens, is a fault."""
    rows = []
    for slot in map(int, slots):
        st = server.slot_state(slot)
        if st["prompt"] is None:
            return [], f"slot {slot} was never held"
        prompt, tokens = list(st["prompt"]), list(st["tokens"])
        if len(tokens) != want or tokens not in written.get(tuple(prompt), []):
            return [], (f"slot {slot}'s last tenant ({len(tokens)} tokens) "
                        "is not a row that was written")
        rows.append({"slot": slot, "tenancy": int(st["tenancy"]),
                     "prompt": prompt, "tokens": tokens, "state": st["state"],
                     "window": st.get("window")})
    return rows, None


def judge(ctx) -> dict:
    """Sample slots, teacher-force the rows of shortest and longest prompt
    among those that held them last and hold their served tokens, and every
    sampled row's state and window, to the plain forward; probe a slot's
    reuse; every written row must carry exactly ``max_new_tokens`` tokens
    (``eos_id`` -1: no early exit). A rehearsal (hidden 64, 4 of 16 experts:
    nearly every position has a choice within a rounding of its boundary)
    holds the control flow, the counts, the states ahead of the routers and
    the stated leaves, and the shares — the states' behind routers too,
    where a tiny model's every position carries a near-tie's choice — to 25
    times their limits."""
    proc_cfg = ctx.proc_cfg
    want = int(proc_cfg["max_new_tokens"])
    served: dict[int, list] = {}
    short = 0
    for ids, texts in zip(ctx.out_rows, ctx.out_a):
        for i, text in zip(ids.tolist(), texts):
            toks = [int(t) for t in (text or "").split()]
            short += int(len(toks) != want)
            if i >= 0:
                served.setdefault(i, []).append(toks)
    if not served:
        return {"ok": False, "why": "nothing was written"}
    proc = ctx.processor
    max_input = int(proc_cfg["max_input"])
    keys = sorted(served)
    tok_ids, mask = proc.tokenizer.encode_batch(
        [ctx.pool.texts[i] for i in keys], max_input)
    written: dict[tuple, list] = {}
    for j, i in enumerate(keys):
        written.setdefault(tuple(tok_ids[j, :int(mask[j].sum())].tolist()),
                           []).extend(served[i])
    server = proc._server
    rng = np.random.default_rng([int(ctx.seed), 0x47444E])
    slots = rng.choice(server.slots, min(SAMPLE_SLOTS, server.slots), replace=False)
    rows, why = last_tenants(server, slots, written, want)
    if why:
        return {"ok": False, "why": why}
    rows.sort(key=lambda r: len(r["prompt"]))
    ends = sorted({0, len(rows) - 1})   # the token rules' rows: shortest, longest
    hp = hyper(proc.cfg)
    shares = 25.0 if getattr(ctx, "rehearse", False) else 1.0
    verdict = judge_rows(
        proc.params, hp, [r["prompt"] for r in rows], [r["tokens"] for r in rows],
        max_input + want, [r["state"] for r in rows], [r["window"] for r in rows],
        token_rows=ends, shares=shares)
    verdict["rows_sampled"] = len(ends)
    verdict["prompt_tokens_judged"] = [len(rows[r]["prompt"]) for r in ends]
    verdict["least_tenancy"] = min(r["tenancy"] for r in rows)
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["reuse_probe"] = reuse_probe(server, proc.params, hp, ctx.seed,
                                         proc.cfg.vocab_size, shares)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["least_tenancy"] >= 2
                         and verdict["reuse_probe"]["ok"]
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
