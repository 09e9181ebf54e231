"""Plain reference for ``decoder_lm`` with blocks of ONE mixer each — a Mamba-2 layer, a routed-expert layer of two-matrix relu-squared experts of which a share is held, or a position-free grouped-query attention layer (NVIDIA-Nemotron-3-Nano-30B-A3B, NVIDIA 2025, ``model_type: nemotron_h``; Mamba-2: Dao & Gu 2024) — and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision, written from the layer equations of the
configuration file (``benchmark/configs/nemotron3-nano-30b-a3b-l13-ep2.json``:
the model's ``config.json`` for every size, its ``assumed`` for what the keys
do not state) and independent of ``arkflow_tpu/models``: no kernel, no cache,
no batching, no chunked form. THE RECURRENCE IS WRITTEN AS THE RECURRENCE — a
``lax.scan`` over tokens from a zero state — so the served chunk scan, the
served decode update and the seam between them are held to one sequence of
states. One block on ``x`` [S, hidden]; ``n`` is RMSNorm at eps 1e-5 with a
plain scale; no projection has a bias (the conv has one)::

    block i of kind k:   x = x + mixer_k(n_i(x))      one norm, one mixer,
                                                      nothing after it
    M (Mamba-2, 64 heads of 64, state 128, 8 groups, conv 4):
        [z | xBC | dt] = y W_in          4,096 | 4,096 + 2 x 8 x 128 | 64
        xBC = silu(conv1d(xBC) + b)      depthwise, causal, zeros before the
                                         sequence
        dt = softplus(dt + dt_bias);  A = -exp(A_log)       one each a head
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t[g]     h: [64, 128] a
        o_t = h_t C_t[g] + D x_t                            head, g = head // 8
        o = RMSNorm_g(o * silu(z))       the gate FIRST, then the norm over
                                         each of the 8 groups of 512 channels
        mixer = o W_out
    E (routed experts):
        s = sigmoid(y W_r)  float32 over 128;  the 6 largest of s + bias
        w = s[chosen] / (sum of s over the chosen + 1e-20) * 2.5
        e(y) = relu(y W_up)^2 W_down     two matrices, no gate, width 1,856
        mixer = sum over the chosen experts HELD here of w_e e(y)
                + shared(y)              the same form at width 3,712, ungated
    * (attention, 32 query heads, 2 K/V heads of 128, NO positional encoding):
        q, k, v = y Wq, y Wk, y Wv;  softmax(q k^T 128^-0.5 over j <= t) v;
        head h reads K/V head h // 16;  mixer = a Wo

After the last block a final ``n`` and the untied head; embedding rows
unscaled. ``experts_held`` is the chip's share of a 2-way expert-parallel
deployment: the router keeps its 128 outputs and 6 choices, weights are
normalised over ALL the chosen, and what absent experts would add is left out
— here as in the program (``tests/test_nemotron_h.py`` adds the two shares,
the shared expert counted once, up to the uncut layer).

It reads only the program's float32 MASTERS (``proc.host_params``), in the
values the configuration states: every projection, the embedding, the head,
the experts and the conv rounded to bfloat16 on the way in; the router, its
selection bias, the norm scales and ``A_log``, ``D``, ``dt_bias`` float32 as
they are. NOTHING the program placed is read: an expert layer's 66 experts
go from their masters to the device ``EXPERT_BLOCK`` at a time, each block
once for all the sampled rows, at the PUBLISHED width (the program holds an
expert's 1,856 columns as 1,920: what stands behind the width is cut off
here, not trusted to be zeros). Layout only: blocks stack on a leading axis by kind
(``mamba_layers``, ``moe_layers``, ``dense_layers`` — the attention blocks),
weights are [in, out], the conv's weight [channels, taps], ``experts`` holds
the held routed experts first and the shared expert after them AS TWO of
width 1,856 (a relu-squared expert of width 2 f is two of width f side by
side: the reference joins them back into one of 3,712).

``judge(ctx)``: a seeded sample of SLOTS, and of each the row that held it
last in the run, is teacher-forced: one forward over the prompt and ALL the
served tokens.

(a) tokens by margin. Wherever the reference's top-2 margin exceeds twice the
    bfloat16 logit tolerance the served token is the reference's argmax — but
    for the positions a ROUTER NEAR TIE explains: the served router's input
    went through bfloat16 products, so of two scores on either side of the
    6th / 7th boundary closer than that rounding the served path may choose
    the other, rightly; the position's experts then differ and its logits
    move by more than any rounding. Those positions are not told apart here:
    their SHARE is limited (``WRONG_SHARE``), between the served program's
    reading and the readings of a forward that is wrong (PERF.md section 6, PR 62).
(b) near ties of the LOGITS: the share of judged positions whose served
    token is not the reference's argmax at all (``DIVERGED_SHARE``).
(c) the leaves the configuration states float32 are the masters, bit for bit.
(d) THE RECURRENT STATE ITSELF, as the timed run left it in the slot's row
    of the pool when the run drained (``GenerationServer.slot_state``): the
    first block is a Mamba-2 layer ahead of every router, so its state is the
    recurrence's after the prompt and all but the last served token to
    float32-accumulator accuracy (``STATE_REL_ERR`` a head,
    ``STATE_REL_ERR_LAYER`` over the layer: a state held in bfloat16 reads
    ten times the served program's there, a stale, leaked or unreset one ~1);
    behind routers a near tie moves a position's input and a state is its
    last few hundred positions', so the layers behind are held by their
    median (``STATE_REL_ERR_BEHIND``) AND by their worst
    (``STATE_REL_ERR_BEHIND_WORST``: one wrong state in one layer fails it).
Every written row carries exactly ``max_new_tokens`` tokens and every judged
row INHERITED its slot (a second or later tenant: the reset is judged too).
"""

from __future__ import annotations

import numpy as np

_BF16_EPS = 2.0 ** -8
#: slots sampled for the comparison: 3,072 positions a run (over four slots,
#: 1,536 positions, rule (b)'s share swung 0.060-0.090 by the draw alone)
SAMPLE_ROWS = 8
#: columns of the output head multiplied at a time
VOCAB_BLOCK = 16_384
#: experts multiplied at a time (float32 [block, hidden, width] twice)
EXPERT_BLOCK = 8
#: queries a block of the attention's masked softmax
QUERY_BLOCK = 512
#: rule (a): largest share of judged positions whose served token lies under
#: the reference's argmax at a decided margin (router near ties the served
#: path chose the other way). On the chip (PERF.md section 6, PR 62): the
#: served program reads 0.0024-0.0065 over twenty-two runs of four slots
#: (1,536 or 2,048 positions) and 0.0026-0.0078 over fourteen of eight
#: (3,072); a state pool held in bfloat16 0.0065, the mixer without ``D x_t``
#: 0.448 / 0.422, the reference computed in bfloat16 0.0130 / 0.0117 (refused
#: by rules (b) and (d), not by this one). The limit stands 2.6 x over the
#: largest sound reading and 21 x under the wrong forward's
WRONG_SHARE = 0.02
#: rule (b): largest share of judged positions whose served token is not the
#: reference's argmax (logit near ties, and rule (a)'s positions). Between two
#: readings: the served program 0.0625-0.0791 over fourteen runs of eight
#: slots (0.060-0.090 over twenty-two of four: a share of ~0.075 over 1,536
#: positions swings by +-0.007 by the draw of the slots alone), and the mixer
#: without ``D x_t`` 0.9985 / 0.9987: 1.5 x over the one, 8 x under the other.
#: The reference computed in bfloat16, the nearest precision below, reads
#: 0.156 / 0.1315 here; what refuses it is rule (d) (its state 0.087-0.114 a
#: head, 0.064-0.096 the layer), not this rule's margin; a bfloat16 state pool
#: reads 0.087 (rule (d)'s to refuse too)
DIVERGED_SHARE = 0.12
#: ... or this many positions, whichever is more (a rehearsal judges 32)
FEW = 4
#: rule (d): the first block's state. ``STATE_REL_ERR``: the largest relative
#: (Frobenius) distance of a HEAD's state over heads and sampled slots — the
#: served program 0.0033-0.0070 (twenty-two runs, four slots each: a head that
#: forgets in a few tokens is its last few bfloat16 products' rounding) and
#: 0.0036-0.0078 (fourteen runs of eight), a state pool held in bfloat16
#: 0.0195 and 0.0605, the reference computed in bfloat16 0.0873 / 0.1137.
#: ``STATE_REL_ERR_LAYER``: the same distance over ALL the
#: layer's heads at once, which the heads of long memory lead — where a
#: bfloat16 state's roundings pile up and a float32 state's inputs average
#: out: the served program 0.0015-0.0027 (twenty-eight runs), the bfloat16
#: pool 0.0253, the bfloat16 reference 0.0642 / 0.0965: the limit 3 x over
#: the largest sound reading and 3.2 x under the smallest control's.
#: ``STATE_REL_ERR_BEHIND``: the median over (slot, mamba layer behind a
#: router) of a layer's distance over all its heads (its window's where
#: larger): the served program 0.0067-0.060 at four slots, 0.0078-0.0174 at
#: eight (a near tie the served router chose the other way moves a position's
#: input by tenths), no ``D x_t`` 1.07 / 1.05, another slot's state 1.4.
#: ``STATE_REL_ERR_BEHIND_WORST``: the LARGEST of the same (a median passes
#: one wrong state in one of the five layers): the served program 0.022-0.165
#: at four slots and 0.040-0.175 at eight (0.174 on a bfloat16 pool, 0.216 /
#: 0.269 against the bfloat16 reference), no ``D x_t`` 1.16, another slot's
#: state 1.4: the limit 2.9 x over the largest sound reading, 2.3 x under
#: the control
STATE_REL_ERR = 0.015
STATE_REL_ERR_LAYER = 0.008
STATE_REL_ERR_BEHIND = 0.2
STATE_REL_ERR_BEHIND_WORST = 0.5
#: ... the first block's limits as above for a state of this many updates or
#: more; the bfloat16 products' rounding averages out over the updates a state
#: has seen, so a shorter one (a rehearsal's: ten) is held to the limit times
#: sqrt(this / its updates)
STATE_UPDATES = 256
#: rule (d): the first block's conv window (its last three inputs as
#: projected: bfloat16 in the pool, so a bfloat16 rounding of each value and
#: of the product before it), largest relative distance over sampled slots:
#: the served program 0.0023-0.0028; a window that is another row's or a
#: position late reads ~1.4
WINDOW_REL_ERR = 0.03

_MAMBA, _MOE, _FULL = "mamba", "moe", "full_attention"
_STACKS = {_MAMBA: "mamba_layers", _MOE: "moe_layers", _FULL: "dense_layers"}
_F32_LEAVES = ("attn_norm", "norm_out", "ssm_norm", "ssm_A_log", "ssm_D",
               "ssm_dt_bias", "router", "router_bias")


def logit_tolerance(absmax: float) -> float:
    """How far a served logit may sit from the float32 reference: 4 bfloat16
    ulps of the largest reference logit (the head's product is rounded to
    bfloat16; no floor: a seeded model's logits are of order one)."""
    return 4 * _BF16_EPS * float(absmax)


def hyper(cfg) -> dict:
    """The numbers the forward reads, from the program's configuration."""
    return {
        "kinds": tuple(cfg.layer_types[:cfg.layers]),
        "heads": cfg.heads, "kv_heads": cfg.kv_heads,
        "head_dim": cfg.head_dim or cfg.dim // cfg.heads,
        "norm_eps": cfg.norm_eps,
        "mamba_n_heads": cfg.mamba_n_heads, "mamba_d_head": cfg.mamba_d_head,
        "mamba_d_state": cfg.mamba_d_state, "mamba_n_groups": cfg.mamba_n_groups,
        "mamba_d_conv": cfg.mamba_d_conv,
        "n_routed_experts": cfg.n_routed_experts,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "routed_scaling_factor": cfg.routed_scaling_factor,
        "held": tuple(cfg.experts_held or (0, cfg.n_routed_experts)),
        "expert_width": cfg.moe_intermediate_size,
    }


def _stated(tree):
    """A tree of float32 masters on the device in the values the
    configuration states: rounded to bfloat16 (and held so) unless the leaf
    is one the configuration keeps float32."""
    import jax
    import jax.numpy as jnp

    def one(path, leaf):
        keys = [str(getattr(k, "key", k)) for k in path]
        if any(k in _F32_LEAVES for k in keys):
            return jnp.asarray(np.asarray(leaf), jnp.float32)
        return jax.block_until_ready(
            jnp.asarray(np.asarray(leaf), jnp.float32).astype(jnp.bfloat16))

    return jax.tree_util.tree_map_with_path(one, tree)


def _f32(a):
    import jax.numpy as jnp

    return a.astype(jnp.float32)


def _rms_norm(scale, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * scale


def mamba2(lp, y, hp, last, skip: bool = True):
    """The Mamba-2 mixer over one sequence ``y`` [S, hidden] from a zero
    state. Returns (its output, the state after position ``last`` [H, P, N],
    the conv's inputs at positions ``last - (taps - 2) .. last`` [taps - 1,
    channels], zeros before the sequence). ``skip`` false leaves ``D x`` out
    (the tests' control)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, p, n = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"]
    g, k = hp["mamba_n_groups"], hp["mamba_d_conv"]
    d = h * p
    u = y @ _f32(lp["ssm_in"]["w"])
    z, xbc, dt = jnp.split(u, [d, 2 * d + 2 * g * n], -1)
    # what the program's window holds: the inputs AS PROJECTED, in bfloat16
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    window = jax.lax.dynamic_slice_in_dim(padded, last + 1, k - 1, axis=0)
    w = _f32(lp["ssm_conv"]["w"])                                 # [channels, k]
    xbc = jax.nn.silu(_f32(lp["ssm_conv"]["b"]) + sum(
        padded[j:j + s] * w[:, j] for j in range(k)))
    x = xbc[:, :d].reshape(s, h, p)
    bm = jnp.repeat(xbc[:, d:d + g * n].reshape(s, g, n), h // g, 1)   # [S, H, N]
    cm = jnp.repeat(xbc[:, d + g * n:].reshape(s, g, n), h // g, 1)
    dt = jax.nn.softplus(dt + lp["ssm_dt_bias"])                  # [S, H]
    a = -jnp.exp(lp["ssm_A_log"])                                 # [H]

    def token(carry, xs):
        state, kept = carry
        x_t, b_t, c_t, dt_t, t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
                 ).astype(state.dtype)
        return ((state, jnp.where(t == last, state, kept)),
                jnp.einsum("hpn,hn->hp", state, c_t))

    zero = _f32(jnp.zeros((h, p, n)))       # the state: float32, an accumulator
    (_, kept), o = jax.lax.scan(token, (zero, zero), (x, bm, cm, dt, jnp.arange(s)))
    if skip:
        o = o + lp["ssm_D"][:, None] * x
    gated = (o.reshape(s, d) * jax.nn.silu(z)).reshape(s, g, d // g)
    normed = gated / jnp.sqrt(jnp.square(gated).mean(-1, keepdims=True)
                              + hp["norm_eps"])
    normed = normed.reshape(s, d) * lp["ssm_norm"]["scale"]
    return normed @ _f32(lp["ssm_out"]["w"]), kept, window


def attention(lp, y, hp):
    """Position-free causal GQA over one sequence, a block of queries at a
    time (a 4,608-token row's scores would be 2.7 GB at once)."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    heads, kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    q = (y @ _f32(lp["wq"]["w"])).reshape(s, heads, d)
    k = jnp.repeat((y @ _f32(lp["wk"]["w"])).reshape(s, kv, d), heads // kv, 1)
    v = jnp.repeat((y @ _f32(lp["wv"]["w"])).reshape(s, kv, d), heads // kv, 1)
    block = min(QUERY_BLOCK, s)
    pad = -s % block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block, heads, d)
    at = jnp.arange(s + pad).reshape(-1, block)

    def some(args):
        qs, pos = args
        scores = jnp.einsum("qhd,khd->hqk", qs, k) * d ** -0.5
        scores = jnp.where(jnp.arange(s)[None, None, :] <= pos[None, :, None],
                           scores, -1e30)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(some, (qb, at)).reshape(s + pad, heads * d)[:s]
    return out @ _f32(lp["wo"]["w"])


def route(lp, y, hp):
    """The combine weights [S, E] float32 over ALL the experts (0 where not
    chosen), and the scores beside the selection boundary."""
    import jax
    import jax.numpy as jnp

    e, k = hp["n_routed_experts"], hp["num_experts_per_tok"]
    scores = jax.nn.sigmoid(jnp.dot(y, lp["router"]["w"],
                                    precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + lp["router_bias"], k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * hp["routed_scaling_factor"]
    return jnp.einsum("sk,ske->se", w, jax.nn.one_hot(idx, e, dtype=jnp.float32))


def _relu2(y, w_up, w_down):
    import jax
    import jax.numpy as jnp

    return jnp.square(jax.nn.relu(y @ w_up)) @ w_down


def _some_experts(y, w_up, w_down, cw):
    """``sum_e cw[:, e] e(y)`` over a block of experts: w_up [block, hidden,
    width], w_down [block, width, hidden], cw [S, block]."""
    import jax.numpy as jnp

    act = jnp.square(jnp.maximum(jnp.einsum("sd,edf->esf", y, _f32(w_up)), 0.0))
    return jnp.einsum("esf,efd,se->sd", act, _f32(w_down), cw)


def _programs(hp: dict) -> dict:
    """The forward's pieces, each compiled once a forward (``hidden_states``
    makes them and every block and row shares them): the block of a kind but
    ``moe``, and an
    expert layer's norm, combine weights over the held experts, a block of
    experts added to a row's sum, the shared expert added to it. Experts
    arrive as held (``_stated`` masters) and are cut to the published width
    HERE: what the program keeps behind it is not read."""
    import jax
    import jax.numpy as jnp

    first, count = hp["held"]
    f = hp["expert_width"]
    progs = {kind: jax.jit(lambda lp, x, last, kind=kind:
                           block_forward(kind, lp, x, hp, last))
             for kind in set(hp["kinds"]) - {_MOE}}
    progs["norm"] = jax.jit(lambda lp, x: _rms_norm(
        lp["attn_norm"]["scale"], x, hp["norm_eps"]))
    progs["weights"] = jax.jit(
        lambda lp, y: route(lp, y, hp)[:, first:first + count])
    progs["some"] = jax.jit(lambda out, y, wu, wd, cw: out + _some_experts(
        y, wu[:, :, :f], wd[:, :f, :], cw))
    progs["shared"] = jax.jit(lambda out, y, wu, wd: out + _relu2(
        y, jnp.concatenate(list(_f32(wu[:, :, :f])), axis=-1),
        jnp.concatenate(list(_f32(wd[:, :f, :])), axis=0)))
    return progs


def experts_over_rows(lp, experts, ys: list, hp, shared: bool = True,
                      progs=None) -> list:
    """The expert layer's mixer for each row of ``ys`` ([S, hidden] each, the
    block's norm applied): the experts HELD here over every token, weighed by
    ``route`` (0 where not chosen), plus the shared expert — the stack's last
    two joined into one of twice the width — ungated. ``experts`` is the
    layer's stack of float32 masters ({w_up [E + 2, hidden, width as held],
    w_down [E + 2, width as held, hidden]}, on the host): ``EXPERT_BLOCK`` of
    them at a time go to the device, rounded there (``_stated``), and serve
    every row before the next block comes — 13.6 GB of masters pass through
    ~0.4 GB of the device."""
    import jax
    import jax.numpy as jnp

    count = hp["held"][1]
    progs = progs or _programs(hp)
    up, down = (np.asarray(experts[k]) for k in ("w_up", "w_down"))

    def stated(lo, hi):
        return _stated({"w": up[lo:hi]})["w"], _stated({"w": down[lo:hi]})["w"]

    cws = [progs["weights"](lp, y) for y in ys]
    outs = [jnp.zeros_like(y) for y in ys]
    block = min(EXPERT_BLOCK, count)
    for lo in range(0, count, block):
        wu, wd = stated(lo, min(lo + block, count))
        outs = jax.block_until_ready([
            progs["some"](out, y, wu, wd, cw[:, lo:lo + block])
            for out, y, cw in zip(outs, ys, cws)])
    if shared and up.shape[0] > count:
        wu, wd = stated(count, up.shape[0])
        outs = [progs["shared"](out, y, wu, wd) for out, y in zip(outs, ys)]
    return outs


def routed_experts(lp, y, hp, shared: bool = True):
    """``experts_over_rows`` of one row, the experts among ``lp``'s leaves."""
    return experts_over_rows(lp, lp["experts"], [y], hp, shared)[0]


def block_forward(kind: str, lp, x, hp, last):
    """One block of a kind but ``moe`` over one sequence: ``lp`` the block's
    leaves as stated (``_stated``), x [S, hidden]. Returns (x, the mixer's
    state after position ``last`` and its conv window there — None but for a
    mamba block)."""
    y = _rms_norm(lp["attn_norm"]["scale"], x, hp["norm_eps"])
    if kind == _MAMBA:
        out, state, window = mamba2(lp, y, hp, last)
        return x + out, (state, window)
    return x + attention(lp, y, hp), None


def _blocks(masters, hp):
    """(kind, the block's leaves as stated, its experts' masters or None) in
    the model's order: out of its kind's stack of masters, rounded on the way
    in (``_stated``) — but an expert layer's ``experts``, which stay float32
    masters on the host for ``experts_over_rows`` to bring in by blocks (a
    layer's 66 are 2.7 GB at once)."""
    import jax

    seen: dict = {}
    for kind in hp["kinds"]:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        stack = masters[_STACKS[kind]]
        lp = _stated(jax.tree_util.tree_map(
            lambda a: np.asarray(a)[i],
            {k: v for k, v in stack.items() if k != "experts"}))
        yield kind, lp, (jax.tree_util.tree_map(lambda a: np.asarray(a)[i],
                                                stack["experts"])
                         if kind == _MOE else None)


def hidden_states(masters, rows: np.ndarray, hp: dict, lasts=None) -> tuple:
    """The residual stream after the last block, [S, hidden] float32 a row of
    ``rows`` [R, S] int32 — a block at a time (its masters go to the device
    once and every row passes through) — and each row's recurrent states
    [mamba layers, H, P, N] and conv windows [mamba layers, taps - 1,
    channels] after its position ``lasts[r]`` (default: the row's last)."""
    import jax
    import jax.numpy as jnp

    table = np.asarray(masters["embed"]["table"])
    xs = [_f32(_stated({"table": table[row]})["table"]) for row in rows]
    progs = _programs(hp)
    lasts = [rows.shape[1] - 1] * len(rows) if lasts is None else lasts
    states = [[] for _ in rows]
    with jax.default_matmul_precision("highest"):
        for kind, lp, experts in _blocks(masters, hp):
            if kind == _MOE:
                outs = experts_over_rows(
                    lp, experts, [progs["norm"](lp, x) for x in xs], hp,
                    progs=progs)
                xs = [x + out for x, out in zip(xs, outs)]
                continue
            for r, last in enumerate(lasts):
                xs[r], kept = progs[kind](lp, xs[r], np.int32(last))
                if kept is not None:
                    states[r].append(kept)
            jax.block_until_ready(xs)
            del lp
    return xs, [(jnp.stack([s for s, _ in per]), jnp.stack([w for _, w in per]))
                for per in states]


def decoder_logits(masters, ids: np.ndarray, hp: dict):
    """[R, S] ids -> float32 logits [R, S, vocab], whole (the tests' sizes)."""
    import jax
    import jax.numpy as jnp

    hidden, _ = hidden_states(masters, np.asarray(ids), hp)
    scale = jnp.asarray(np.asarray(masters["norm_out"]["scale"]), jnp.float32)
    head = _f32(_stated({"w": masters["lm_head"]["w"]})["w"])
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_rms_norm(scale, h, hp["norm_eps"]) @ head
                          for h in hidden])


def head_stats(masters, hidden, served, hp: dict, block: int = VOCAB_BLOCK) -> dict:
    """The final norm and the output head over ``hidden`` [T, hidden], a
    block of vocabulary columns at a time, reduced to what the rules read at
    each position: the largest logit, the runner-up, the served token's
    (``served`` [T]), and the largest magnitude over all."""
    import jax
    import jax.numpy as jnp

    w = np.asarray(masters["lm_head"]["w"])
    vocab = w.shape[1]
    scale = jnp.asarray(np.asarray(masters["norm_out"]["scale"]), jnp.float32)

    @jax.jit
    def over(h, cols, first, served):
        logits = _rms_norm(scale, h, hp["norm_eps"]) @ cols.astype(jnp.float32)
        top2 = jax.lax.top_k(logits, min(2, logits.shape[-1]))[0]
        at = jnp.clip(served - first, 0, logits.shape[-1] - 1)
        mine = jnp.take_along_axis(logits, at[:, None], 1)[:, 0]
        inside = (served >= first) & (served < first + logits.shape[-1])
        return top2, jnp.where(inside, mine, -jnp.inf), jnp.abs(logits).max()

    tops, mine, absmax = [], [], 0.0
    served = jnp.asarray(served, jnp.int32)
    with jax.default_matmul_precision("highest"):
        for first in range(0, vocab, block):
            cols = _stated({"w": w[:, first:first + block]})["w"]
            t2, m, am = jax.device_get(over(hidden, cols, np.int32(first), served))
            tops.append(np.asarray(t2))
            mine.append(np.asarray(m))
            absmax = max(absmax, float(am))
    top2 = np.sort(np.concatenate(tops, axis=1), axis=1)[:, -2:]
    return {"best": top2[:, 1], "second": top2[:, 0],
            "served": np.max(np.stack(mine), axis=0), "absmax": absmax}


def head_rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """The relative Frobenius distance of each head's state: [.., H, P, N]
    each -> [.., H]."""
    num = np.sqrt(np.square(got - want).sum(axis=(-1, -2)))
    return num / np.maximum(np.sqrt(np.square(want).sum(axis=(-1, -2))), 1e-30)


def layer_rel_err(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """The relative distance of each layer's state over all its heads (or of
    its conv window): [layers, ...] each -> [layers]."""
    axes = tuple(range(1, got.ndim))
    num = np.sqrt(np.square(got - want).sum(axis=axes))
    return num / np.maximum(np.sqrt(np.square(want).sum(axis=axes)), 1e-30)


def state_verdict(held: list, windows: list, want: list) -> dict:
    """Rule (d) over the sampled rows: ``held[r]`` [mamba layers, H, N, P] the
    state the served run left behind row r (the pool's layout: the
    reference's transposed), ``windows[r]`` its conv windows, ``want[r]``
    this forward's (states, windows)."""
    first, first_layer, first_window, behind = [], [], [], []
    for got, win, (states, wins) in zip(held, windows, want):
        got = np.swapaxes(np.asarray(got, np.float32), -1, -2)
        states = np.asarray(states)
        first.append(float(head_rel_err(got[0], states[0]).max()))
        first_layer.append(float(layer_rel_err(got[:1], states[:1])[0]))
        by_window = layer_rel_err(np.asarray(win, np.float32), np.asarray(wins))
        first_window.append(float(by_window[0]))
        behind.extend(np.maximum(layer_rel_err(got, states), by_window)[1:].tolist())
    return {"state_rel_err": max(first), "state_rel_err_least": min(first),
            "state_rel_err_limit": STATE_REL_ERR,
            "state_rel_err_layer": max(first_layer),
            "state_rel_err_layer_limit": STATE_REL_ERR_LAYER,
            "state_rel_err_layer_least": min(first_layer),
            "window_rel_err": max(first_window),
            "window_rel_err_limit": WINDOW_REL_ERR,
            "state_rel_err_behind": float(np.median(behind)) if behind else 0.0,
            "state_rel_err_behind_worst": max(behind, default=0.0),
            "state_rel_err_behind_limit": STATE_REL_ERR_BEHIND,
            "state_rel_err_behind_worst_limit": STATE_REL_ERR_BEHIND_WORST}


def judge_rows(masters, hp: dict, prompt_ids: list, tokens: list, width: int,
               held: list, windows: list) -> dict:
    """Rules (a), (b) and (d) over the sampled rows. Each row is one plain
    forward over prompt + served tokens, right-padded to ``width`` (one shape
    compiles; causal attention, a causal conv and a recurrence never look
    ahead at the padding; routing is a token's own). Teacher forcing feeds
    the SERVED tokens, so a near tie the served run resolved the other way
    does not end the walk."""
    import jax.numpy as jnp

    rows = np.zeros((len(tokens), width), np.int32)
    for r, (pids, toks) in enumerate(zip(prompt_ids, tokens)):
        rows[r, :len(pids)] = pids
        rows[r, len(pids):len(pids) + len(toks)] = toks
    hidden, states = hidden_states(
        masters, rows, hp,
        lasts=[len(p) + len(t) - 2 for p, t in zip(prompt_ids, tokens)])
    verdict = state_verdict(held, windows, states)
    del states
    updates = min(len(p) + len(t) - 1 for p, t in zip(prompt_ids, tokens))
    scale = max(1.0, (STATE_UPDATES / updates) ** 0.5)
    state_limit = STATE_REL_ERR * scale
    verdict["state_rel_err_limit"] = state_limit
    # the position that predicts served token i of row r: len(prompt) - 1 + i
    picked = jnp.concatenate([
        h[len(pids) - 1:len(pids) - 1 + len(toks)]
        for h, pids, toks in zip(hidden, prompt_ids, tokens)])
    served = np.concatenate([np.asarray(t, np.int64) for t in tokens])
    out = head_stats(masters, picked, served, hp)
    tol = logit_tolerance(out["absmax"])
    margin = out["best"] - out["second"]
    gap = out["best"] - out["served"]
    decided = margin > 2 * tol
    wrong = decided & (gap > 0)
    diverged = gap > 0
    n = max(len(served), 1)
    verdict.update({
        "ok": bool(decided.any()
                   and wrong.sum() <= max(WRONG_SHARE * n, FEW)
                   and diverged.sum() <= max(DIVERGED_SHARE * n, FEW)
                   and verdict["state_rel_err"] <= state_limit
                   and verdict["state_rel_err_layer"] <= STATE_REL_ERR_LAYER * scale
                   and verdict["window_rel_err"] <= WINDOW_REL_ERR
                   and verdict["state_rel_err_behind"] <= STATE_REL_ERR_BEHIND
                   and verdict["state_rel_err_behind_worst"]
                   <= STATE_REL_ERR_BEHIND_WORST),
        "state_updates_least": updates,
        "positions_checked": int(len(served)),
        "positions_decided": int(decided.sum()),
        "wrong_on_decided": int(wrong.sum()),
        "wrong_share": float(wrong.sum()) / n, "wrong_share_limit": WRONG_SHARE,
        "diverged": int(diverged.sum()),
        "diverged_share": float(diverged.sum()) / n,
        "diverged_share_limit": DIVERGED_SHARE,
        "widest_diverged_gap": float(gap[diverged].max()) if diverged.any() else 0.0,
        "logit_tol": tol, "logit_absmax": out["absmax"]})
    return verdict


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 (norm scales, ``A_log``, ``D``, ``dt_bias``, the router and
    its bias) whose placed value is not the float32 master, bit for bit."""
    import jax

    differ = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(placed)[0]:
        keys = [str(getattr(k, "key", k)) for k in path]
        if not any(k in _F32_LEAVES for k in keys):
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
    tokens, which tenant of the slot it was — and the state and conv window
    it left in the slot's row of the pool. Returns (rows, why not): a slot
    that no finished row holds, or whose row was not written with these
    tokens, is a fault."""
    import jax

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
                     "window": np.asarray(jax.device_get(
                         server.v_pages["ssm"][:, slot + 1]), np.float32)})
    return rows, None


def judge(ctx) -> dict:
    """Sample slots, teacher-force the row each held last and hold its served
    tokens and the state it left to the plain forward; every written row
    must carry exactly ``max_new_tokens`` tokens (``eos_id`` -1: no early
    exit), and every judged row must have INHERITED its slot."""
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
    rng = np.random.default_rng([int(ctx.seed), 0x4E48])
    slots = rng.choice(server.slots, min(SAMPLE_ROWS, server.slots), replace=False)
    rows, why = last_tenants(server, slots, written, want)
    if why:
        return {"ok": False, "why": why}
    # one width whatever was sampled: one set of programs in the compile
    # cache for every seed (a width of its own is ~45 s of compiling)
    verdict = judge_rows(
        proc.host_params, hyper(proc.cfg), [r["prompt"] for r in rows],
        [r["tokens"] for r in rows], max_input + want,
        [r["state"] for r in rows], [r["window"] for r in rows])
    verdict["rows_sampled"] = len(rows)
    verdict["least_tenancy"] = min(r["tenancy"] for r in rows)
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["least_tenancy"] >= 2
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
