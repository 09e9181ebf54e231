"""Plain reference for ``decoder_lm`` with the hybrid block — GQA attention and a Mamba-2 mixer side by side in every layer (Falcon-H1: tiiuae 2025; Mamba-2: Dao & Gu 2024) — and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision, written from the published description and
independent of ``arkflow_tpu/models``: no kernel, no cache, no chunked form.
THE RECURRENCE IS WRITTEN AS THE RECURRENCE — a ``lax.scan`` over tokens
from a zero state — so the served chunk scan, the served decode update and
the seam between them are all held to the same sequence of states.

    h0 = embed[ids] * embedding_multiplier
    per layer:  y = RMSNorm_in(h)
                a = Attn(y * attention_in_multiplier) * attention_out_multiplier
                m = Mamba2(y * ssm_in_multiplier)     * ssm_out_multiplier
                h = h + a + m
                u = RMSNorm_ff(h)
                h = h + W_down(W_up u * silu(W_gate u * mlp_multipliers[0])) * mlp_multipliers[1]
    logits = lm_head(RMSNorm_out(h)) * lm_head_multiplier

    Attn:   GQA, no bias; k = (y' W_k) * key_multiplier; RoPE on q and k over
            the pairs (i, i + d/2) (the HF layout); scale head_dim^-0.5
    Mamba2: [z | x | B | C | dt] = (y' W_in) * ssm_multipliers[0..4] by segment
            [x | B | C] = silu(causal_conv1d([x | B | C], w, bias))  (depthwise,
                          d_conv - 1 earlier inputs a channel, zeros before the start)
            dt = softplus(dt + dt_bias);  A = -exp(A_log)        one each a head
            S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t (x) B_t[g(h)]  S: [d_head, d_state] a head
            o_t = S_t C_t[g(h)] + D_h x_t                        g(h) = h // (heads / groups)
            o = GroupRMSNorm(o * silu(z))   the gate FIRST, then the norm over each group
            m = o W_out

It reads only the program's float32 MASTERS (``proc.host_params``), in the
values the configuration states: every projection, the embedding, the head
and the conv rounded to bfloat16 (here, on the way in), the norm scales and
``A_log``, ``D``, ``dt_bias`` float32 as they are. One layer's leaves and one
block of the head's columns are on the device at a time, so the reference
fits beside the server's pools.

Departures from the publication, all of layout: layers are stacked on a
leading axis of the tree; weights are [in, out]; the conv's weight is
[channels, d_conv].

``judge(ctx)``: a seeded sample of SLOTS, and of each the row that held it
last in the run — by then a slot has been handed on several times, with all
its lanes' neighbours live — is teacher-forced: one forward over the prompt
and ALL the served tokens. (a), (b) the served tokens are held to its logits
(``judge_rows``); (c) the leaves the configuration states float32 are the
masters, bit for bit; (d) THE RECURRENT STATE ITSELF, as the timed run left
it: a finished row's state stays in its slot's row of the pool until the
next tenant's first chunk, so after the drain the sampled slots' rows
(``GenerationServer.slot_state``) are held to the recurrence after the
prompt and all but the last served token — every update a chunk, a padded
tail, the chunk / decode seam and ~511 decode steps among 127 other lanes
made, from the reset that followed the slot's earlier tenant. Random weights
make the state a small part of the logits (the mixer's skip ``D x`` carries
most of its output at Mamba-2's init), so tokens alone see neither the
state's precision nor a stale or leaked state: (d) does. Each limit is
written with its reason.
"""

from __future__ import annotations

import numpy as np

_BF16_EPS = 2.0 ** -8
#: rows sampled for the comparison
SAMPLE_ROWS = 6
#: columns of the output head multiplied at a time (float32 [hidden, block]
#: on the device: 334 MB at hidden 5,120)
VOCAB_BLOCK = 16_320
#: rule (b): the share of judged positions at which the served token may
#: differ from the reference's argmax (all of them near ties: rule (a) has
#: passed). A near tie flips where the served path's rounding exceeds the
#: margin, so the share measures that rounding: bfloat16 products into a
#: float32 state. Between its two readings (PERF.md section 6, PR 33):
#: 0.011-0.016 over the served program's seeds, 0.125 with the weights cut
#: to e4m3's mantissa (a state held in bfloat16 does not move it: rule (d)).
DIVERGED_SHARE = 0.05
#: ... or this many positions, whichever is more: a rehearsal judges 32
#: positions, of which two near ties are 6 % (the cell judges 3,072: 153)
DIVERGED_FEW = 4

#: rule (d): how far the recurrent state a sampled slot's last tenant left
#: in the pool may sit from the reference's, as the largest relative
#: (Frobenius) distance over heads, layers and sampled slots. The served
#: state is float32 fed by bfloat16 products, so it differs by those
#: products' rounding, averaged over the sequence; a state HELD in bfloat16
#: is rounded at every token (an increment under half an ulp of a slowly
#: decaying state is lost whole) and reads a thousand times that; a state
#: that is another row's, stale, or not reset reads ~1. Between its two
#: readings (PERF.md section 6, PR 33): 0.0003-0.0004 over the served program's
#: seeds, 0.31-0.37 over the six slots with the state pool in bfloat16.
STATE_REL_ERR = 0.004

_F32_LEAVES = ("attn_norm", "mlp_norm", "norm_out", "ssm_norm", "ssm_A_log",
               "ssm_D", "ssm_dt_bias")


def logit_tolerance(absmax: float) -> float:
    """How far a served logit may sit from the float32 reference: 4 bfloat16
    ulps of the largest reference logit. The output head's product is
    rounded to bfloat16 and THEN scaled by ``lm_head_multiplier`` (2^-7 as
    published: exact), so the tolerance scales with the logits and has no
    floor (``dense_decoder.py``'s floor of 1 would exceed every logit here)."""
    return 4 * _BF16_EPS * float(absmax)


def hyper(cfg) -> dict:
    """The numbers the forward reads, from the program's configuration."""
    names = ("heads", "kv_heads", "rope_theta", "norm_eps", "mamba_n_heads",
             "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
             "mamba_d_ssm", "embedding_multiplier", "attention_in_multiplier",
             "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
             "ssm_out_multiplier", "ssm_multipliers", "mlp_multipliers",
             "lm_head_multiplier")
    hp = {n: getattr(cfg, n) for n in names}
    hp["head_dim"] = cfg.head_dim or cfg.dim // cfg.heads
    return hp


def _stated(tree):
    """A tree of float32 masters on the device in the values the
    configuration states: rounded to bfloat16 (and held so: half the bytes)
    unless the leaf is one the configuration keeps float32. Leaf by leaf,
    so one leaf's float32 copy is on the device at a time."""
    import jax
    import jax.numpy as jnp

    def one(path, leaf):
        keys = [str(getattr(k, "key", k)) for k in path]
        leaf = jnp.asarray(np.asarray(leaf), jnp.float32)
        if any(k in _F32_LEAVES for k in keys):
            return leaf
        return jax.block_until_ready(leaf.astype(jnp.bfloat16))

    return jax.tree_util.tree_map_with_path(one, tree)


def _f32(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms_norm(scale, x, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.square(x).mean(-1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x [S, heads, d] at positions 0..S-1, pairs (i, i + d/2)."""
    import jax.numpy as jnp

    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def attention(lp, y, hp):
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    heads, kv, d = hp["heads"], hp["kv_heads"], hp["head_dim"]
    y = y * hp["attention_in_multiplier"]
    q = _rope((y @ lp["wq"]["w"]).reshape(s, heads, d), hp["rope_theta"])
    k = _rope(((y @ lp["wk"]["w"]) * hp["key_multiplier"]).reshape(s, kv, d),
              hp["rope_theta"])
    v = (y @ lp["wv"]["w"]).reshape(s, kv, d)
    k, v = jnp.repeat(k, heads // kv, 1), jnp.repeat(v, heads // kv, 1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool))[None], scores, -1e30)
    out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return (out.reshape(s, heads * d) @ lp["wo"]["w"]) * hp["attention_out_multiplier"]


def mamba2(lp, y, hp, last):
    """The mixer over one sequence ``y`` [S, hidden] from a zero state.
    Returns (its output, the state after position ``last`` [H, P, N])."""
    import jax
    import jax.numpy as jnp

    s = y.shape[0]
    h, p, n = hp["mamba_n_heads"], hp["mamba_d_head"], hp["mamba_d_state"]
    g, k, d = hp["mamba_n_groups"], hp["mamba_d_conv"], hp["mamba_d_ssm"]
    mz, mx, mb, mc, mdt = hp["ssm_multipliers"]
    u = (y * hp["ssm_in_multiplier"]) @ lp["ssm_in"]["w"]
    z, x, bm, cm, dt = jnp.split(u, [d, 2 * d, 2 * d + g * n, 2 * d + 2 * g * n], -1)
    xbc = jnp.concatenate([x * mx, bm * mb, cm * mc], -1)
    # causal depthwise conv: output t reads inputs t - (k - 1) .. t
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    w = lp["ssm_conv"]["w"]                                       # [channels, k]
    xbc = jax.nn.silu(lp["ssm_conv"]["b"] + sum(
        padded[j:j + s] * w[:, j] for j in range(k)))
    x = xbc[:, :d].reshape(s, h, p)
    bm = jnp.repeat(xbc[:, d:d + g * n].reshape(s, g, n), h // g, 1)   # [S, H, N]
    cm = jnp.repeat(xbc[:, d + g * n:].reshape(s, g, n), h // g, 1)
    dt = jax.nn.softplus(dt * mdt + lp["ssm_dt_bias"])            # [S, H]
    a = -jnp.exp(lp["ssm_A_log"])                                 # [H]

    def token(carry, xs):
        state, kept = carry
        x_t, b_t, c_t, dt_t, t = xs
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return ((state, jnp.where(t == last, state, kept)),
                jnp.einsum("hpn,hn->hp", state, c_t))

    zero = jnp.zeros((h, p, n), jnp.float32)
    (_, kept), o = jax.lax.scan(token, (zero, zero), (x, bm, cm, dt, jnp.arange(s)))
    o = o + lp["ssm_D"][:, None] * x
    gated = (o.reshape(s, d) * jax.nn.silu(z * mz)).reshape(s, g, d // g)
    normed = gated / jnp.sqrt(jnp.square(gated).mean(-1, keepdims=True)
                              + hp["norm_eps"])
    normed = normed.reshape(s, d) * lp["ssm_norm"]["scale"]
    return (normed @ lp["ssm_out"]["w"]) * hp["ssm_out_multiplier"], kept


def layer_forward(lp, x, hp, last):
    """One layer over one sequence: ``lp`` the layer's leaves as stated
    (``_stated``), x [S, hidden]. Returns (x, the mixer's state after
    position ``last``)."""
    import jax

    lp = _f32(lp)
    y = _rms_norm(lp["attn_norm"]["scale"], x, hp["norm_eps"])
    mixed, state = mamba2(lp, y, hp, last)
    x = x + attention(lp, y, hp) + mixed
    u = _rms_norm(lp["mlp_norm"]["scale"], x, hp["norm_eps"])
    m_gate, m_out = hp["mlp_multipliers"]
    act = jax.nn.silu((u @ lp["w_gate"]["w"]) * m_gate) * (u @ lp["w_up"]["w"])
    return x + (act @ lp["w_down"]["w"]) * m_out, state


def hidden_states(masters, rows: np.ndarray, hp: dict, lasts=None) -> tuple:
    """The residual stream after the last layer, [S, hidden] float32 a row
    of ``rows`` [R, S] int32 — a layer at a time (its masters go to the
    device once and every row passes through), a row at a time — and each
    row's recurrent states after its position ``lasts[r]`` (default: the
    row's last), [layers, H, P, N]."""
    import jax
    import jax.numpy as jnp

    table = np.asarray(masters["embed"]["table"])
    xs = [_stated({"table": table[row]})["table"].astype(jnp.float32)
          * hp["embedding_multiplier"] for row in rows]
    step = jax.jit(lambda lp, x, last: layer_forward(lp, x, hp, last))
    stack = masters["layers"]
    layers = int(np.shape(stack["attn_norm"]["scale"])[0])
    lasts = [rows.shape[1] - 1] * len(rows) if lasts is None else lasts
    states = [[] for _ in rows]
    with jax.default_matmul_precision("highest"):
        for i in range(layers):
            lp = _stated(jax.tree_util.tree_map(lambda a: np.asarray(a)[i], stack))
            for r, last in enumerate(lasts):
                xs[r], state = step(lp, xs[r], np.int32(last))
                states[r].append(state)
            jax.block_until_ready(xs)
            del lp
    return xs, [jnp.stack(per_row) for per_row in states]


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
        logits = (_rms_norm(scale, h, hp["norm_eps"])
                  @ cols.astype(jnp.float32)) * hp["lm_head_multiplier"]
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


def judge_rows(masters, hp: dict, prompt_ids: list, tokens: list, width: int,
               held: list) -> dict:
    """Rules (a), (b) and (d) over the sampled rows. Each row is one plain
    forward over prompt + served tokens, right-padded to ``width`` (one
    shape compiles; causal attention, a causal conv and a recurrence never
    look ahead at the padding). Teacher forcing feeds the SERVED tokens, so
    a near tie the served run resolved the other way does not end the walk.

    (a) the near-tie rule of ``dense_decoder.py``: wherever the reference's
        top-2 margin exceeds twice the logit tolerance the served token is
        the reference's argmax; no exception.
    (b) elsewhere it may differ, at no more than ``DIVERGED_SHARE`` of the
        judged positions (or ``DIVERGED_FEW`` of them, where that is more).
    (d) ``held[r]`` [layers, H, N, P], the state the served run left behind
        row r (the pool's layout: the reference's transposed), sits within
        ``STATE_REL_ERR`` of the recurrence's after the prompt and all but
        the last served token — the last is sampled and never fed."""
    import jax.numpy as jnp

    rows = np.zeros((len(tokens), width), np.int32)
    for r, (pids, toks) in enumerate(zip(prompt_ids, tokens)):
        rows[r, :len(pids)] = pids
        rows[r, len(pids):len(pids) + len(toks)] = toks
    hidden, states = hidden_states(
        masters, rows, hp,
        lasts=[len(p) + len(t) - 2 for p, t in zip(prompt_ids, tokens)])
    dists = [state_distance(np.swapaxes(np.asarray(got, np.float32), -1, -2),
                            np.asarray(want))
             for got, want in zip(held, states)]
    del states
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
    first_wrong = None
    if wrong.any():
        i = int(np.flatnonzero(wrong)[0])
        first_wrong = (f"position {i} of the sample: token {int(served[i])} "
                       f"lies {gap[i]:.6f} under the reference's largest "
                       f"logit at margin {margin[i]:.6f} (admitted: "
                       f"{2 * tol:.6f})")
    n = max(len(served), 1)
    share = float(diverged.sum()) / n
    return {"ok": bool(decided.any() and not wrong.any()
                       and diverged.sum() <= max(DIVERGED_SHARE * n, DIVERGED_FEW)
                       and max(dists) <= STATE_REL_ERR),
            "state_rel_err": max(dists), "state_rel_err_limit": STATE_REL_ERR,
            "state_rel_err_least": min(dists),
            "state_updates_least": min(
                len(p) + len(t) - 1 for p, t in zip(prompt_ids, tokens)),
            "positions_checked": int(len(served)),
            "positions_decided": int(decided.sum()),
            "wrong_on_decided": int(wrong.sum()),
            "diverged": int(diverged.sum()), "diverged_share": share,
            "diverged_share_limit": DIVERGED_SHARE,
            "widest_diverged_gap": float(gap[diverged].max()) if diverged.any() else 0.0,
            "first_wrong": first_wrong, "logit_tol": tol,
            "logit_absmax": out["absmax"]}


def state_distance(served: np.ndarray, want: np.ndarray) -> float:
    """The largest relative Frobenius distance of a head's state, over
    heads and layers: [layers, H, P, N] each."""
    num = np.sqrt(np.square(served - want).sum(axis=(-1, -2)))
    den = np.sqrt(np.square(want).sum(axis=(-1, -2)))
    return float((num / np.maximum(den, 1e-30)).max())


def stated_float32_leaves_differ(placed, masters) -> int:
    """Rule (c): the number of values among the leaves the configuration
    states float32 (norm scales, ``A_log``, ``D``, ``dt_bias``) whose placed
    value is not the float32 master, bit for bit."""
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
    tokens, which tenant of the slot it was — and the state it left in the
    slot's row of the pool. Returns (rows, why not): a slot that no finished
    row holds, or whose row was not written with these tokens, is a fault."""
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
                     "prompt": prompt, "tokens": tokens, "state": st["state"]})
    return rows, None


def judge(ctx) -> dict:
    """Sample slots, teacher-force the row each held last and hold its served
    tokens and the state it left to the plain forward; every written row
    must carry exactly ``max_new_tokens`` tokens (``eos_id`` -1: no early
    exit), and every judged row must have INHERITED its slot (a second or
    later tenant: the reset is then part of what is judged)."""
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
    rng = np.random.default_rng([int(ctx.seed), 0x70C5])
    slots = rng.choice(server.slots, min(SAMPLE_ROWS, server.slots), replace=False)
    rows, why = last_tenants(server, slots, written, want)
    if why:
        return {"ok": False, "why": why}
    verdict = judge_rows(
        proc.host_params, hyper(proc.cfg), [r["prompt"] for r in rows],
        [r["tokens"] for r in rows], max_input + want, [r["state"] for r in rows])
    verdict["rows_sampled"] = len(rows)
    verdict["least_tenancy"] = min(r["tenancy"] for r in rows)
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["least_tenancy"] >= 2
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
