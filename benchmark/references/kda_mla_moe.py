"""Plain reference for ``decoder_lm`` with Kimi Delta Attention layers beside position-free latent (MLA) layers, a leading dense SwiGLU and sigmoid-routed experts of which a share is held (Kimi-Linear-48B-A3B-Instruct, Moonshot 2025, ``model_type: kimi_linear``; the mixer: arXiv:2510.26692), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, NO chunked
form — the delta rule runs TOKEN BY TOKEN as the configuration file writes it
—, latent attention in its published (expanded) form as a masked softmax over
every earlier key (a block of queries at a time, so a 17,152-token row fits),
every held expert over every token with a mask (the expert layers in one
``lax.scan`` whose body switches on the layer's kind, and a padded row's
blocks behind its last position skipped: the same arithmetic a position, a
third of the compile and a row's own length of work: a cold run compiles and
runs this forward inside the check's time). Written from the layer
equations of the configuration file
(``benchmark/configs/kimi-linear-48b-a3b-l8-ep8.json``: the model's
``config.json`` for every size, its ``assumed`` for what the keys do not
state) and independent of ``arkflow_tpu/models``. It reads only the program's
parameter tree, in the values the configuration states: bfloat16-rounded
weights; float32 router, selection bias, ``A_log``, ``dt_bias`` and norm
scales.

One layer on ``x`` [S, hidden]; ``n`` is a plain RMSNorm, ``x / sqrt(mean(x^2)
+ 1e-5) * w``; no bias anywhere::

    y = n(x)
    linear_attention layer (KDA: 32 heads of 128, three convs of 4 taps):
        [q~ | k~ | v~] = y W_qkv          (three matrices side by side)
        c_t = silu(sum_{j=0..3} w[:, j] * [q~ | k~ | v~]_{t-3+j})   (depthwise,
                              causal, zeros before the sequence, no bias)
        q, k <- x * rsqrt(sum x^2 + 1e-6) a head;  q <- q * 128^-0.5
        g = -exp(A_log_h) * softplus((y W_fa) W_fb + dt_bias)   [32, 128]: a
            log-decay a head AND key channel;  beta = sigmoid(y W_b)   [32]
        per head, S [128 key, 128 value], from zeros:
            S <- Diag(exp(g_t)) S;  u = S^T k_t;  d = beta_t (v_t - u)
            S <- S + k_t (x) d;  o_t = S^T q_t
        z = (y W_ga) W_gb
        o <- o / sqrt(mean(o^2) + 1e-5) * w_n * sigmoid(z)   a head (one w_n)
        x = x + o W_o
    full_attention layer (MLA, 32 heads, NO rotation: ``mla_use_nope``):
        q = y W_q -> a head [q_n 128 | q_r 64]
        [c~ | k_r] = y W_kva (512 | 64);  c = n_512(c~)
        [k_n | v] = c W_kvb a head (128 | 128)
        a = softmax((q_n . k_n + q_r . k_r) / sqrt(192) over j <= t) v
        x = x + a W_o         (k_r: ONE 64-wide key a token for all heads,
                               cached and scored as projected)
    y = n(x)
    layer 0: x = x + SwiGLU_9216(y)
    layers 1..: s = sigmoid(y W_r) float32 over 256; the 8 largest of s + bias
        chosen; w = s / sum(s over the chosen) * 2.446
        x = x + sum over the chosen experts HELD here of w_e E_e(y)
              + E_shared(y)                                   (width 1,024)

After the last layer a final ``n`` and the head; embedding rows unscaled.
``experts_held`` is the chip's share of an 8-way expert-parallel deployment:
the router keeps its 256 outputs and 8 choices, weights are normalised over
ALL the chosen, and what absent experts would add is left out — here as in
the program (``tests/test_kda_mla_moe.py`` adds the eight shares, the shared
expert counted once, up to the uncut layer). Departures from the published
description, each a layout and no arithmetic: layers stack on a leading axis
by kind and MLP (``kda_dense_layers``, ``kda_layers``, ``layers``), weights
are [in, out], the three input projections are one leaf ``kda_qkv`` and the
three convs one leaf ``kda_conv_w`` (side by side), ``experts`` holds the held
routed experts first and the shared one after them.

``judge(ctx)`` holds what the TIMED path wrote to this forward, by the rules
of ``gdn_gqa_moe.py`` (whose helpers it shares):

(a) bf16 logit tolerance, (b) router near-tie re-routing (the DeepSeek-V3
    router of ``mla_moe_decoder.py``: two BIASED scores across the selection
    boundary closer than ``ROUTER_DELTA`` may be chosen the other way;
    re-routed positions counted, what no admitted re-routing explains limited
    over all positions and over each judged row), (c) the stated float32
    leaves served bit for bit — over the rows that a seeded sample of SLOTS
    held last, the one of shortest and the one of longest prompt (each a
    second or later tenant of its slot).
(d) the float32 state and the conv window each sampled row left in its
    slot's row of the ``kda`` pool when the run drained are this forward's
    after the positions the row FED. The first TWO linear layers lie ahead of
    every router (layer 0's MLP is dense; layer 1 routes after its mixer):
    their largest relative distance over heads (and their windows') is held
    to ``STATE_REL_ERR``; behind routers the MEDIAN over (row, layer) of a
    layer's distance, to ``STATE_REL_ERR_BEHIND`` (a near-tie the served
    path rightly chose the other way moves a few positions' inputs by
    tenths). With THIS seeding (``A_log`` = log U(1, 16), a step log-uniform
    in [1e-3, 1e-1] a channel) a channel's memory spans tens to thousands of
    tokens: a state is most of its sequence's, and one HELD IN BFLOAT16
    drifts from the forward's by the roundings of every token it carried —
    which ``STATE_REL_ERR`` refuses by distance (``qwen3next_l8``'s judge
    could not); ``state_bf16_values_share`` holds the pool's dtype beside it.
(e) after the drain ONE more request goes through the served program, a
    one-token prompt asking for one token: its chunk starts a sequence in a
    slot that was held before, so the window it leaves is zeros before its
    one input and the state ``k (x) beta v`` of that token alone — a state
    or a window that survives a slot's reuse shows here, where a slow channel
    keeps 99.9 % of its last tenant's state a token.

(f) the latent layers are POSITION-FREE as served, held on what the served
    programs WROTE: after the drain one more request goes through them, a
    seeded prompt of 600 ids (two chunks, the second padded) asking four
    tokens, and the rows it left in its latent pages — the normed latent row
    and the 64-wide shared key of every position it fed, the prompt's written
    by the compiled chunk program, the last three by the decode program — are
    this forward's ``[c | k_r] = y W_kva`` at both latent layers (the median
    over positions of a position's distance, to ``LATENT_REL_ERR``; the lanes
    behind the key zeros). No token rule can see a position in a latent layer
    here: under seeded weights attention is near uniform over thousands of
    keys, and with ``k_r`` and the queries' 64 ROTATED the logits move by less
    than the bfloat16 tolerance (on the chip: ``unexplained_share`` 0, the
    largest distance under the best logit 0.056 of 0.099 admitted, every
    state as a sound run's; PERF.md section 6, PR 59 (4)) — the cached key
    moves by its whole length. What this rule does NOT hold is the QUERY's
    side of that product (a rotated ``q_r``, a dropped ``q_r . k_r`` term
    with the keys cached as projected): PERF.md section 7.

Every written row carries exactly ``max_new_tokens`` tokens (``eos_id`` -1),
and rows written = rows read is ``run.py``'s own check beside this one.
"""

from __future__ import annotations

import math

import numpy as np

from benchmark.references.gdn_gqa_moe import (_median, bf16_values_share,
                                              head_rel_err, last_tenants,
                                              layer_rel_err,
                                              routed_far_from_a_tie)
from benchmark.references.mla_moe_decoder import logit_tolerance, reroutings
from benchmark.references.window_gqa_moe import (BLOCK, REROUTE_ROUNDS, _blocks,
                                                 _f32, _rms_norm, _swiglu,
                                                 row_width)

#: rows a block of the recurrence and of the experts' products (a padded
#: row's blocks behind its last position are skipped: ``_live_blocks``);
#: ``judge_rows`` pads a row to a whole number of them
ROWS = 256
#: positions from a row's start whose cached latent rows the forward gives
#: back (rule (f) reads a probe's few hundred)
LATENT_ROWS = 1024
#: slots drawn for the comparison; of the rows that held them last the one
#: of shortest and the one of longest prompt are held to the token rules,
#: every one to rule (d)
SAMPLE_SLOTS = 4
#: rule (b): as ``mla_moe_decoder.ROUTER_DELTA`` (the same router, letter for
#: letter: float32 sigmoid scores of a bfloat16 residual stream plus a seeded
#: selection bias; readings there: the served program's re-routings cross
#: gaps up to 2.6e-3 and once 4.2e-3)
ROUTER_DELTA = 6e-3
#: largest share of the positions checked that no admitted re-routing
#: explains, over all positions and over each judged row of
#: ``ROW_POSITIONS`` or more; the share accepted only RE-ROUTED is reported
#: and limits nothing. On the chip (PERF.md section 6, PR 59) the served
#: program reads 0.0 over all positions and on every row of 1,024 (twenty
#: runs, forty rows; re-routed 0 to 0.0024); the controls read: ``dt_bias``
#: left out 0.867 (0.876 and 0.858 on its rows), one decay a head for a
#: decay a key channel 0.761 (0.765, 0.758). The limit is
#: ``gdn_gqa_moe.py``'s, whose router resolves ties less cleanly (0.012 to
#: 0.025 sound): it stands 19 x under the smaller control
UNEXPLAINED_SHARE = 0.04
ROW_POSITIONS = 64
#: rule (d), (e): largest relative distance, over the heads of the linear
#: layers AHEAD of every router, of a state in the pool from this forward's
#: (and of those layers' conv windows); the median, over (row, linear layer
#: behind a router), of a layer's distance; the same of the probe's one
#: token. On the chip (PERF.md section 6, PR 59) the served program reads
#: 0.00562 to 0.00635 ahead of the routers (rows of 128 to 12,733 prompt
#: tokens and the probe alike, nineteen runs: the bfloat16 products' own
#: rounding, the same at every length); A STATE HELD IN BFLOAT16 0.0156 (rows
#: of 1,039 and 3,330 tokens: every write rounds what a slow channel keeps
#: for thousands of tokens), the reference computed in bfloat16 0.096,
#: ``dt_bias`` left out 0.994, one decay a head 0.881: the limit stands 1.5 x
#: over the largest sound reading and 1.6 x under the bfloat16 state's (its
#: geometric middle; ``state_bf16_values_share`` refuses that control too,
#: at 1.0 against 4.6e-5). Behind routers the rows' median reads 0.016 to
#: 0.026 sound (the probe 0.0086 to 0.0096) and 0.85 / 0.99 under the two
#: decay controls, 0.054 with the reference in bfloat16: ``gdn_gqa_moe.py``'s
#: limits, 10 x over sound, which no control of this cell needs tighter
STATE_REL_ERR = 0.0095
STATE_REL_ERR_BEHIND = 0.25
PROBE_REL_ERR_BEHIND = 0.08
#: rule (e): seeded one-token prompts this forward routes, and the gap across
#: the selection boundary (score units) over which a choice counts as far
#: from a tie: twice ``ROUTER_DELTA``
PROBE_CANDIDATES = 64
PROBE_DELTA = 2 * ROUTER_DELTA
#: rule (d): a float32 accumulator's values are bfloat16's with chance 2^-16
STATE_BF16_SHARE = 0.01
#: rule (f): the probe's prompt (two chunks of the cell's 512, the second
#: padded) and the tokens it asks (its last three rows are the decode
#: program's), and the largest median distance of a latent layer's cached
#: rows from this forward's. On the chip (PERF.md section 6, PR 59, review
#: round; twenty-four runs) the served program reads 0.0123 to 0.0158 over
#: the chunk program's 600 rows and 0.0109 to 0.0477 over the decode
#: program's three (a median of three: one re-routed row of the three shows;
#: the largest single position 0.075 to 0.121), and with the shared key
#: ROTATED 1.151 and 1.288 (every other rule sound): the limit stands 3.1 x
#: over the largest sound reading and 7.7 x under the control
LATENT_PROBE_TOKENS = 600
LATENT_PROBE_NEW = 4
LATENT_REL_ERR = 0.15

_FULL, _LINEAR = "full_attention", "linear_attention"
#: the program's stack of a layer, by (kind, routes?)
_STACKS = {(_LINEAR, False): "kda_dense_layers", (_LINEAR, True): "kda_layers",
           (_FULL, False): "dense_layers", (_FULL, True): "layers"}


def _live_blocks(fn, s: int, live, *per_row, block: int = BLOCK):
    """``fn(first row, block of each array)`` over blocks of ``block`` rows,
    one after another; the results (a tree of arrays) joined on the row axis.
    A block wholly at or past row ``live`` — a padded row's padding, which no
    causal layer lets a live position see — is not computed and reads zeros:
    a row costs its own length, whatever width it is padded to."""
    import jax
    import jax.numpy as jnp

    if s <= block or s % block:
        return fn(0, *per_row)
    n = s // block

    def one(xs):
        like = jax.eval_shape(fn, *xs)
        return jax.lax.cond(
            xs[0] < live, lambda: fn(*xs),
            lambda: jax.tree_util.tree_map(
                lambda a: jnp.zeros(a.shape, a.dtype), like))

    out = jax.lax.map(one, (jnp.arange(n) * block,
                            *[a.reshape(n, block, *a.shape[1:]) for a in per_row]))
    return jax.tree_util.tree_map(lambda a: a.reshape(s, *a.shape[2:]), out)


def kimi_delta_attention(lp, y, hp, fed, live=None):
    """A linear_attention layer's mixer over [S, hidden] from the sequence's
    start. Returns (its output [S, hidden], the state after ``fed`` positions
    [heads, key dim, value dim], the convs' inputs of the ``taps - 1``
    positions before position ``fed`` [taps - 1, channels], zeros before the
    sequence). The recurrence stops where the ``live`` positions do (all,
    unless given): behind them the output reads zeros."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, d, taps = hp["kda_heads"], hp["kda_dim"], hp["taps"]
    qkv = y @ _f32(lp["kda_qkv"]["w"])                            # [S, 3 h d]
    ext = jnp.pad(qkv, ((taps - 1, 0), (0, 0)))
    w = _f32(lp["kda_conv_w"])                                    # [channels, taps]
    c = jax.nn.silu(sum(ext[j:j + s] * w[:, j] for j in range(taps)))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.square(x).sum(-1, keepdims=True) + 1e-6)

    q, k, v = (c[:, i * h * d:(i + 1) * h * d].reshape(s, h, d) for i in range(3))
    q, k = unit(q) * d ** -0.5, unit(k)
    beta = jax.nn.sigmoid(y @ _f32(lp["kda_b"]["w"]))             # [S, h]
    a = (y @ _f32(lp["kda_fa"]["w"])) @ _f32(lp["kda_fb"]["w"])
    g = -jnp.exp(_f32(lp["kda_A_log"]))[:, None] * jax.nn.softplus(
        a + _f32(lp["kda_dt_bias"])).reshape(s, h, d)             # [S, h, d]

    def token(carry, xs):
        state, kept = carry
        q_t, k_t, v_t, g_t, b_t, t = xs
        state = jnp.exp(g_t)[:, :, None] * state                  # a key channel
        back = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[:, :, None] * (b_t[:, None] * (v_t - back))[:, None, :]
        kept = jnp.where(t == fed - 1, state, kept)
        return (state, kept), jnp.einsum("hkv,hk->hv", state, q_t)

    zero = jnp.zeros((h, d, d), q.dtype)      # float32: an accumulator over the row
    per_token = (q, k, v, g, beta, jnp.arange(s))
    if live is None or s <= ROWS or s % ROWS:
        (_, kept), o = jax.lax.scan(token, (zero, zero), per_token)
    else:      # token by token still, a block of tokens after another
        def tokens(carry, xs):
            return jax.lax.cond(
                xs[-1][0] < live, lambda: jax.lax.scan(token, carry, xs),
                lambda: (carry, jnp.zeros((ROWS, h, d), q.dtype)))

        (_, kept), o = jax.lax.scan(tokens, (zero, zero), tuple(
            x.reshape(s // ROWS, ROWS, *x.shape[1:]) for x in per_token))
        o = o.reshape(s, h, d)
    z = (y @ _f32(lp["kda_ga"]["w"])) @ _f32(lp["kda_gb"]["w"])
    o = o * jax.lax.rsqrt(jnp.square(o).mean(-1, keepdims=True) + hp["eps"])
    o = (o * _f32(lp["kda_norm"]["scale"])).reshape(s, h * d) * jax.nn.sigmoid(z)
    # ext row i is position i - (taps - 1): the window before position fed
    return (o @ _f32(lp["kda_out"]["w"]), kept,
            jax.lax.dynamic_slice_in_dim(ext, fed, taps - 1, axis=0))


def latent_attention(lp, y, hp, live=None):
    """A full_attention layer's mixer over [S, hidden], the published
    (expanded) form with NO rotation: keys and values of every position
    expanded once, the queries a block at a time (the ``live`` ones: all,
    unless given). Returns (its output [S, hidden], what a cache of this
    layer holds of the first ``LATENT_ROWS`` positions: the normed latent
    row beside the shared key, [rows, kv_lora_rank + rope])."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, nope, rope, vd, lat = (hp["heads"], hp["nope"], hp["rope"], hp["v"],
                              hp["kv_lora_rank"])
    kv = y @ _f32(lp["wkv_a"]["w"])
    c = _rms_norm(lp["kv_norm"]["scale"], kv[:, :lat], hp["eps"])
    k_r = kv[:, lat:]                                             # [S, rope]
    kv_up = (c @ _f32(lp["wkv_b"]["w"])).reshape(s, h, nope + vd)
    k_n, v = kv_up[..., :nope], kv_up[..., nope:]
    pos = jnp.arange(s)

    def block(q0, yb):
        at = q0 + jnp.arange(yb.shape[0])
        q = (yb @ _f32(lp["wq"]["w"])).reshape(-1, h, nope + rope)
        scores = (jnp.einsum("qhd,khd->hqk", q[..., :nope], k_n)
                  + jnp.einsum("qhd,kd->hqk", q[..., nope:], k_r)
                  ) / math.sqrt(nope + rope)
        mask = pos[None, :] <= at[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], scores, -1e30), -1)
        return jnp.einsum("hqk,khd->qhd", p, v).reshape(-1, h * vd) @ _f32(
            lp["wo"]["w"])

    return (_live_blocks(block, s, s if live is None else live, y),
            jnp.concatenate([c, k_r], -1)[:LATENT_ROWS])


def route(lp, y, hp, swap=None):
    """(chosen experts [S, k] of ALL the router's outputs, their weights
    [S, k], ``near``: the BIASED scores [S, 4] and the experts [S, 4] of the
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


def routed_experts(lp, y, hp, swap=None, live=None):
    """The held experts' part of the weighted sum (one expert at a time over
    every token of a block with a mask, a block of ``ROWS`` tokens after
    another: the ``live`` ones, all unless given) plus the shared expert's
    SwiGLU. ``lp["experts"]`` is (the stack's experts, the layer's index): an
    expert's three matrices are read out of the stack one expert at a time."""
    import jax
    import jax.numpy as jnp

    first, held = hp["held"]
    ex, layer = lp["experts"]
    s = y.shape[0]
    if swap is None:
        swap = jnp.full((s, 2), -1, jnp.int32)

    def expert(i):
        return [ex[k][layer, i] for k in ("w_gate", "w_up", "w_down")]

    def block(_, yb, swap_b):
        idx, w, near = route(lp, yb, hp, swap_b)

        def one_expert(acc, i):
            weight = jnp.where(idx == first + i, w, 0.0).sum(-1, keepdims=True)
            return acc + weight * _swiglu(yb, *expert(i)), None

        out, _ = jax.lax.scan(one_expert, jnp.zeros_like(yb), jnp.arange(held))
        for j in range(held, ex["w_gate"].shape[1]):
            out = out + _swiglu(yb, *expert(j))
        return out, near

    return _live_blocks(block, s, s if live is None else live, y, swap, block=ROWS)


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read as
    a bag of keys; none of the program's code runs)."""
    kda = dict(cfg.linear_attn_config)
    kinds = list(cfg.layer_types[:cfg.layers])
    return {
        "heads": cfg.heads, "nope": cfg.qk_nope_head_dim,
        "rope": cfg.qk_rope_head_dim, "v": cfg.v_head_dim,
        "kv_lora_rank": cfg.kv_lora_rank, "eps": cfg.norm_eps, "kinds": kinds,
        "dense": cfg.first_k_dense_replace,
        "kda_heads": kda["num_heads"], "kda_dim": kda["head_dim"],
        "taps": kda["short_conv_kernel_size"],
        "top_k": cfg.num_experts_per_tok, "scaling": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
    }


def linear_layers_ahead(hp: dict) -> int:
    """Linear layers whose mixer runs before the model's first router."""
    return sum(kind == _LINEAR for kind in hp["kinds"][:hp["dense"] + 1])


def _layer(params, name: str, j, x, hp: dict, fed, live, swap=None):
    """Layer ``j`` of the stack ``name`` on ``x`` [S, hidden]: (x after it;
    ``near`` of its router, None for a dense MLP; the state and the window a
    linear layer leaves after ``fed`` positions, and the rows a latent
    layer's cache holds — zeros from the other kind, so that either kind
    returns the same tree)."""
    import jax
    import jax.numpy as jnp

    kind, routes = next(k for k, v in _STACKS.items() if v == name)
    lp = jax.tree_util.tree_map(lambda a: a[j], {
        k: v for k, v in params[name].items() if k != "experts"})
    h, d, taps = hp["kda_heads"], hp["kda_dim"], hp["taps"]
    state = jnp.zeros((h, d, d), x.dtype)
    window = jnp.zeros((taps - 1, 3 * h * d), x.dtype)
    rows = jnp.zeros((min(x.shape[0], LATENT_ROWS),
                      hp["kv_lora_rank"] + hp["rope"]), x.dtype)
    y = _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"])
    if kind == _LINEAR:
        out, state, window = kimi_delta_attention(lp, y, hp, fed, live)
    else:
        out, rows = latent_attention(lp, y, hp, live)
    x = x + out
    y = _rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"])
    if not routes:
        out = _swiglu(y, lp["w_gate"]["w"], lp["w_up"]["w"], lp["w_down"]["w"])
        return x + out, None, state, window, rows
    lp["experts"] = (params[name]["experts"], j)
    out, near = routed_experts(lp, y, hp, swap, live)
    return x + out, near, state, window, rows


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None,
                   fed=0, live=None):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every EXPERT layer: biased
    scores and experts [new, expert layers, 4]; the linear layers' states
    after ``fed`` positions [linear layers, heads, key dim, value dim]; their
    conv windows before position ``fed`` [linear layers, taps - 1, channels];
    the latent layers' cached rows of the first ``LATENT_ROWS`` positions
    [latent layers, rows, kv_lora_rank + rope]). ``swaps`` [S, expert layers,
    2] re-routes (``route``). ``live``: the row's positions ahead of its
    padding (all, unless given): what lies behind them is not computed.
    Layers are visited one by one in the model's order, each read out of its
    stack, so one layer's float32 copies live at a time: the dense-MLP
    layers in a Python loop, the expert layers in ONE ``lax.scan`` whose body
    switches on the layer's kind — each kind's layer is compiled once, not
    once a layer (a cold run compiles this forward inside the check's time)."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    s, dense = x.shape[0], hp["dense"]
    experts = len(hp["kinds"]) - dense
    if swaps is None:
        swaps = jnp.full((s, experts, 2), -1, jnp.int32)
    seen: dict = {}
    place = []                      # a layer's place in its stack
    for i, kind in enumerate(hp["kinds"]):
        name = _STACKS[kind, i >= dense]
        seen[name] = seen.get(name, -1) + 1
        place.append(seen[name])
    kept = []                       # (state, window, rows) a layer
    for i, kind in enumerate(hp["kinds"][:dense]):
        x, _, *left = _layer(params, _STACKS[kind, False], place[i], x, hp,
                             fed, live)
        kept.append(left)
    kinds = sorted(set(hp["kinds"][dense:]))

    def expert_layer(x, xs):
        kind, j, swap = xs
        x, (near_s, near_e), *left = jax.lax.switch(kind, [
            lambda x, k=k: _layer(params, _STACKS[k, True], j, x, hp, fed, live,
                                  swap) for k in kinds], x)
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, at, new, axis=0)  # noqa: E731
        return x, (cut(near_s), cut(near_e), *left)

    x, (near_s, near_e, *left) = jax.lax.scan(expert_layer, x, (
        jnp.asarray([kinds.index(k) for k in hp["kinds"][dense:]], jnp.int32),
        jnp.asarray(place[dense:], jnp.int32), jnp.moveaxis(swaps, 1, 0)))
    kept += [[a[e] for a in left] for e in range(experts)]
    linear = [i for i, k in enumerate(hp["kinds"]) if k == _LINEAR]
    latent = [i for i, k in enumerate(hp["kinds"]) if k == _FULL]
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    x = _rms_norm(params["norm_out"]["scale"], x, hp["eps"])
    return (x @ _f32(params["lm_head"]["w"]),
            (jnp.moveaxis(near_s, 0, 1), jnp.moveaxis(near_e, 0, 1)),
            jnp.stack([kept[i][0] for i in linear]),
            jnp.stack([kept[i][1] for i in linear]),
            jnp.stack([kept[i][2] for i in latent]))


def _row_forward(hp: dict, new: int):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions."""
    import jax
    import jax.numpy as jnp

    def fn(params, row, at, served, swaps, fed):
        logits, (near_s, near_e), states, windows, latents = decoder_logits(
            params, row, at, new=new, hp=hp, swaps=swaps, fed=fed, live=fed + 1)
        top2 = jax.lax.top_k(logits, 2)[0]
        return {"best": top2[:, 0], "second": top2[:, 1],
                "served": jnp.take_along_axis(logits, served[:, None], 1)[:, 0],
                "absmax": jnp.abs(logits).max(), "near_scores": near_s,
                "near_experts": near_e, "states": states, "windows": windows,
                "latents": latents}

    return jax.jit(fn)


def state_verdict(states, windows, want_states, want_windows, ahead: int) -> dict:
    """Rule (d) / (e) over one row: the served states and windows against
    the forward's — ``ahead``: the largest distance over the heads of the
    first ``ahead`` linear layers (and their windows'); ``behind``: each
    later linear layer's distance over all its heads (its window's where
    larger); ``behind_head``: the largest over those layers' heads."""
    heads = head_rel_err(states, want_states)
    rows = layer_rel_err(windows, want_windows)
    whole = layer_rel_err(np.asarray(states).reshape(len(heads), 1, -1),
                          np.asarray(want_states).reshape(len(heads), 1, -1))
    return {"ahead": float(max(heads[:ahead].max(), rows[:ahead].max())),
            "behind": np.maximum(whole, rows)[ahead:].tolist(),
            "behind_head": float(heads[ahead:].max()) if len(heads) > ahead else 0.0,
            "bf16_share": bf16_values_share(states)}


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               states=None, windows=None, delta: float = ROUTER_DELTA,
               shares: float = 1.0, token_rows=None, probe=None) -> dict:
    """Rules (a), (b), (d) and (f) over the given rows: each row is one plain
    forward over prompt + served tokens, right-padded (causal layers never
    look at the padding, and a token's routing depends on no other token);
    teacher forcing feeds the SERVED tokens; a row with refused tokens is
    run again, each of them re-routed by its next candidate, accepted
    re-routings staying in place. ``states`` / ``windows``: what each row
    left in the pool, held to the forward's after the positions the row
    fed. ``token_rows``: the rows rules (a) and (b) cover (all unless
    given; rule (d) holds every row). ``probe``: ``latent_probe``'s request,
    one more row through the same forward, whose cached latent rows rule
    (f) holds. ``shares`` scales the limits (a rehearsal's, see ``judge``;
    the states' ahead of the routers by 2 at most: heads of 16 round coarser
    than heads of 128)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = len(hp["kinds"]) - hp["dense"]
    fn = _row_forward(hp, new)
    # ONE padded width for the rows judged (the longest's): a second program
    # costs a cold run its compile
    every = list(zip(prompt_ids, tokens)) + (
        [(probe["prompt"], probe["tokens"])] if probe else [])
    width = row_width(max(len(p) + len(t) for p, t in every), longest)
    if width > ROWS:    # whole blocks of ROWS: a padded row's are skipped
        width = -(-width // ROWS) * ROWS

    def run(r, swaps):
        pids, toks = every[r]
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
                f"the selection boundary by expert layer "
                f"{np.round(near[i][:, 1] - near[i][:, 2], 5).tolist()}")
    n = max(checked, 1)
    lead = linear_layers_ahead(hp)
    held = [state_verdict(st, win, out["states"], out["windows"], lead)
            for st, win, out in zip(states or (), windows or (), first)]
    ahead = max((h["ahead"] for h in held), default=0.0)
    behind = _median([e for h in held for e in h["behind"]])
    bf16 = max((h["bf16_share"] for h in held), default=0.0)
    cached = latent_verdict(probe["latent"], run(len(tokens), none)["latents"],
                            len(probe["tokens"]) - 1) if probe else {}
    limit = shares * UNEXPLAINED_SHARE
    return {"ok": bool(decided > 0 and unexplained <= limit * n
                       and all(left <= limit * of for left, _, of, _ in by_row
                               if of >= ROW_POSITIONS)
                       and ahead <= min(shares, 2.0) * STATE_REL_ERR
                       and behind <= shares * STATE_REL_ERR_BEHIND
                       and bf16 <= STATE_BF16_SHARE
                       and all(cached[k] <= shares * LATENT_REL_ERR
                               for k in ("latent_rel_err",
                                         "latent_rel_err_decode_rows") if probe)
                       and cached.get("latent_behind_key_abs_max", 0.0) == 0.0),
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
            "first_unexplained": first_unexplained, "logit_tol": tol, **cached}


def probe_candidates(params, hp: dict, tokens) -> dict:
    """This forward over each of ``tokens`` as a one-token sequence (a second
    position of padding behind it), reduced to what rule (e) reads: ``near``
    scores and experts [tokens, expert layers, 4], the linear layers' states
    after the token and their windows."""
    import jax
    import jax.numpy as jnp

    def one(params, token):
        row = jnp.stack([token, jnp.zeros_like(token)])
        _, (near_s, near_e), states, windows, _ = decoder_logits(
            params, row, 0, new=1, hp=hp, fed=1)
        return {"near_scores": near_s[0], "near_experts": near_e[0],
                "states": states, "windows": windows}

    # the weights are an ARGUMENT: closed over, 4 GB of them would be
    # constants of the program
    with jax.default_matmul_precision("highest"):
        out = jax.jit(jax.vmap(one, in_axes=(None, 0)))(
            params, jnp.asarray(tokens, jnp.int32))
    return {k: np.asarray(v) for k, v in out.items()}


def reuse_probe(server, params, hp: dict, seed: int, vocab: int,
                shares: float = 1.0) -> dict:
    """Rule (e): a one-token prompt asking for one token through the served
    program, after the drain, then what its chunk left in its slot: the
    windows' rows before the sequence ZERO, their last row the token's own
    projected input, and the state the one token's ``k (x) beta v`` from a
    ZERO state. A one-token sequence has no context, so a linear layer's
    state depends on the token's OWN routing at the expert layers ahead of
    it alone: the token is the one of ``PROBE_CANDIDATES`` seeded ones whose
    leading expert layers this forward routes FURTHEST from a tie (no choice
    within ``PROBE_DELTA`` that involves a held expert), and behind routers
    the linear layers behind those layers only are held (their median, to
    ``PROBE_REL_ERR_BEHIND``); ``shares`` scales that limit (a rehearsal's,
    see ``judge``)."""
    import asyncio

    rng = np.random.default_rng([int(seed), 0x50524F42])
    tokens = rng.choice(np.arange(1, vocab), min(PROBE_CANDIDATES, vocab - 1),
                        replace=False)
    cand = probe_candidates(params, hp, tokens)
    far = routed_far_from_a_tie(cand["near_scores"], cand["near_experts"],
                                hp["held"])
    lead = np.cumprod(far, axis=1).sum(axis=1)   # leading expert layers far from a tie
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
    ahead = linear_layers_ahead(hp)
    got = state_verdict(found[0]["state"], window, cand["states"][best],
                        cand["windows"][best], ahead)
    # expert layer e is model layer dense + e; a linear layer at place i of
    # the model lies behind expert layers 0 .. i - dense - 1
    places = [i for i, kind in enumerate(hp["kinds"]) if kind == _LINEAR][ahead:]
    held = [e for i, e in zip(places, got["behind"])
            if i - hp["dense"] <= lead[best]]
    behind = _median(held)
    return {"ok": bool(before == 0.0
                       and got["ahead"] <= min(shares, 2.0) * STATE_REL_ERR
                       and behind <= shares * PROBE_REL_ERR_BEHIND
                       and found[0]["tenancy"] >= 2),
            "token": token, "tenancy": int(found[0]["tenancy"]),
            "before_abs_max": before, "state_rel_err": got["ahead"],
            "state_rel_err_behind_routers": behind,
            "state_rel_err_behind_routers_largest_head": got["behind_head"],
            "expert_layers_routed_far_from_a_tie": int(lead[best]),
            "linear_layers_held_behind_routers": len(held)}


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 — the router, its selection bias, ``A_log``, ``dt_bias``
    and every norm scale — whose placed value is not the float32 master, bit
    for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if not any("router" in k or "norm" in k or k in (
                "kda_A_log", "kda_dt_bias") for k in keys):
            continue
        master = masters
        for k in path:
            master = master[k.key]
        a, b = np.asarray(leaf), np.asarray(master, np.float32)
        differ += int(a.size if a.dtype != np.float32
                      else (a.view(np.uint32) != b.view(np.uint32)).sum())
    return differ


def latent_probe(server, seed: int, vocab: int, max_input: int) -> dict:
    """Rule (f)'s request, after the drain: a seeded prompt of
    ``LATENT_PROBE_TOKENS`` ids (more than one of the cell's chunks, the last
    one padded) asking ``LATENT_PROBE_NEW`` tokens through the served
    program, then the rows its pages hold — the prompt's written by the
    compiled chunk program, the last ``LATENT_PROBE_NEW - 1`` by the decode
    program. Nothing else is in flight, so the pages it gave back when it
    finished are still its own."""
    import asyncio

    rng = np.random.default_rng([int(seed), 0x4C4154])
    prompt = rng.integers(1, vocab, min(LATENT_PROBE_TOKENS, max_input)).tolist()
    asyncio.run(server.generate(prompt, max_new_tokens=LATENT_PROBE_NEW))
    slot = next((i for i in range(server.slots)
                 if list(server.slot_state(i)["prompt"] or ()) == prompt), None)
    if slot is None:
        return {"why": "no slot holds the latent probe"}
    st = server.slot_state(slot, latent=True)
    return {"prompt": prompt, "tokens": list(st["tokens"]), "latent": st["latent"]}


def latent_verdict(got, want, decode_rows: int) -> dict:
    """Rule (f): the rows a request left in its latent pages — ``got``:
    (latent rows [latent layers, fed, kv_lora_rank], shared keys [latent
    layers, fed, key lanes]) — against this forward's ``want`` [latent
    layers, >= fed, kv_lora_rank + rope]. A position's distance is the larger
    of its latent row's and its shared key's, |got - want| / |want|; a
    layer's is the MEDIAN over positions (every latent layer lies behind
    routers: a near-tie the served path rightly chose the other way moves
    that position's row, and dilutedly the rows behind it), over the rows the
    chunk program wrote and over the last ``decode_rows``, which the decode
    program wrote; the lanes behind a shared key are zeros."""
    rows, keys = (np.asarray(a, np.float32) for a in got)
    fed, lat = rows.shape[1], rows.shape[2]
    want = np.asarray(want, np.float32)[:, :fed]
    rope = want.shape[2] - lat

    def rel(a, b):
        return np.sqrt(np.square(a - b).sum(-1)) / np.maximum(
            np.sqrt(np.square(b).sum(-1)), 1e-30)

    per = np.maximum(rel(rows, want[..., :lat]),
                     rel(keys[..., :rope], want[..., lat:]))     # [layers, fed]
    cut = fed - decode_rows
    return {"latent_rel_err": float(np.median(per[:, :cut], axis=1).max()),
            "latent_rel_err_decode_rows": float(
                np.median(per[:, cut:], axis=1).max()),
            "latent_rel_err_largest": float(per.max()),
            "latent_behind_key_abs_max": float(np.abs(keys[..., rope:]).max())
            if keys.shape[2] > rope else 0.0,
            "latent_rows_held": int(fed)}


def judge(ctx) -> dict:
    """Sample slots, probe a slot's reuse, send the latent probe and fetch
    the rows its pages hold (rule (f)), hand the latent pages back (the
    forward of a 17,152-token row wants their room; the sampled rows' states
    were fetched first), then teacher-force the rows of shortest and longest
    prompt among those that held the sampled slots last and hold their served
    tokens, and every sampled row's state and window, to the plain forward;
    every written row must carry exactly ``max_new_tokens`` tokens
    (``eos_id`` -1: no early exit). A rehearsal (hidden 64, 4 of 16 experts:
    nearly every position has a choice within a rounding of its boundary)
    holds the control flow, the counts, the states ahead of the routers and
    the stated leaves, and the shares — the states' behind routers too — to
    25 times their limits (the states' ahead of them to twice theirs)."""
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
    rng = np.random.default_rng([int(ctx.seed), 0x4B4441])
    slots = rng.choice(server.slots, min(SAMPLE_SLOTS, server.slots), replace=False)
    rows, why = last_tenants(server, slots, written, want)
    if why:
        return {"ok": False, "why": why}
    rows.sort(key=lambda r: len(r["prompt"]))
    ends = sorted({0, len(rows) - 1})   # the token rules' rows: shortest, longest
    hp = hyper(proc.cfg)
    shares = 25.0 if getattr(ctx, "rehearse", False) else 1.0
    probe = reuse_probe(server, proc.params, hp, ctx.seed, proc.cfg.vocab_size,
                        shares)
    cached = latent_probe(server, ctx.seed, proc.cfg.vocab_size, max_input)
    if "why" in cached:
        return {"ok": False, **cached}
    server.k_pages = server.v_pages = None   # the run is over: 7 GB of pages
    verdict = judge_rows(
        proc.params, hp, [r["prompt"] for r in rows], [r["tokens"] for r in rows],
        max_input + want, [r["state"] for r in rows], [r["window"] for r in rows],
        token_rows=ends, shares=shares, probe=cached)
    verdict["rows_sampled"] = len(ends)
    verdict["prompt_tokens_judged"] = [len(rows[r]["prompt"]) for r in ends]
    verdict["least_tenancy"] = min(r["tenancy"] for r in rows)
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["reuse_probe"] = probe
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["least_tenancy"] >= 2
                         and probe["ok"]
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
