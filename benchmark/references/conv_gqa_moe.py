"""Plain reference for ``decoder_lm`` with gated short-convolution layers among grouped-query attention layers of 64-wide heads, two leading dense SwiGLUs and then sigmoid-routed experts held whole (LFM2-8B-A1B, LiquidAI 2025, ``model_type: lfm2_moe``), and the comparison that decides ``correct``.

The architecture's forward pass in straightforward ``jax.numpy`` and float32
at ``highest`` matmul precision: no kernel, no cache, no batching, experts
as a loop with masks — written from the layer equations of the configuration
file (``benchmark/configs/lfm2-8b-a1b-l12.json``: the model's ``config.json``
for every size, its ``assumed`` for what the keys do not state) and
independent of ``arkflow_tpu/models``. It reads only the program's parameter
tree, in the values the configuration states: bfloat16-rounded weights;
float32 router, selection bias and norm scales. Attention and the dense
SwiGLU are computed a block of queries at a time, so a 4,608-token row fits
beside the server's pools.

One layer on ``x`` [S, hidden], ``n`` RMSNorm (eps 1e-5), no bias anywhere::

    y = n(x)
    conv layer (L = conv_L_cache = 3):
        [B | C | u] = y W_in                      (hidden -> 3 x hidden)
        v_t = B_t * u_t                           (the gated input: what a
                                                   sequence caches)
        c_t = sum_{j=0..2} w[:, j] * v_{t-2+j}    (depthwise, causal, v = 0
                                                   before the sequence, no
                                                   activation)
        x = x + (C * c) W_out
    attention layer:
        q = y Wq -> [32, 64];  k = y Wk -> [8, 64];  v = y Wv -> [8, 64]
        every q and k head RMS-normed over its 64 values (one scale each,
            shared by the heads), then rotated in split halves (i, i + 32)
            at base 1e6
        s = q k^T / sqrt(64) over j <= t; query head h reads K/V head h // 4
        x = x + softmax(s) v Wo
    y = n(x)
    layers 0, 1:   x = x + W2(silu(y W1) * (y W3))            (width 7,168)
    later layers:  z = sigmoid(y Wr) float32 over 32; the 4 largest of
                   z + b chosen; w = z / (sum(z over the chosen) + 1e-6)
                   x = x + sum over the chosen of w_e E_e(y)  (width 1,792)

After the last layer a final RMSNorm and the head; embedding rows unscaled.
Departures from the publication are the configuration file's ``assumed``
(head size, the head an array of its own, pre-norm, the per-head QK norm,
the taps' order, the bias's seeding); layout only: layers stack on a leading
axis by (dense | routed) x (conv | full) (``conv_dense_layers``,
``conv_layers``, ``dense_layers``, ``layers``), weights are [in, out].

``judge(ctx)`` holds what the TIMED path wrote to this forward:

(a), (b), (c): the rules of ``window_gqa_moe.py`` — the bf16 logit tolerance,
    the router's near-tie re-routing (counted; what no admitted re-routing
    explains is limited), the stated float32 leaves served as stated — over
    the rows that a seeded sample of SLOTS held last, the one of shortest
    and the one of longest prompt (rule (d) holds the windows of EVERY row
    of the sample); each must have INHERITED its slot (a
    second or later tenant). WHAT THESE RULES CAN SEE HERE IS COARSE, and
    the limit says so: ten expert layers hold ALL 32 experts, so every
    near-tie the served path resolved the other way swaps a real expert (a
    quarter of a layer's output; in the cells that hold a share most swaps
    move absent experts); nine positions in ten have a near-tie at one of
    the ten routers, and a conv layer hands a position's gated inputs to
    its two successors: a position is moved by ANOTHER position's admitted
    choice, which re-routing its own does not explain. The served program
    leaves 4-8 % of its positions unexplained (12 % after one round of
    re-routing, where the witness reads 12 % too). The cause is
    SHOWN: ``_witness`` holds this forward to itself under choices the
    rules admit, in every run (the same share, no program in the loop), and
    the program cut to NO router serves the same prompts through the same
    walk and windows at 0 unexplained (the builder's run on the chip,
    PERF.md §6, PR 46); the verdict says where the unexplained lie (by row:
    the short prompt against the long; by quarter of a row's tokens). The
    rules refuse what moves the logits grossly (a conv a row late, no QK
    norm, products at 3 mantissa bits); what moves them by a tenth is rule
    (d)'s and (e)'s to see;
(d) the conv windows those rows left in their slots' rows of the conv pool
    when the run drained (``GenerationServer.slot_state``) are this
    forward's gated inputs of the last two positions the row FED (its prompt
    and all but the last of its tokens), to ``STATE_REL_ERR``: a padded
    position or an idle lane that advanced a window, or a window a position
    late, shows here. Held on the conv layers AHEAD of the first routed
    layer (the two leading dense ones) and, where this forward routes both
    of the window's positions outside the margin rule (b) admits at the
    FIRST router, on the conv layer behind that one router: in this model the first layer behind
    an attention layer, whose keys no router has touched, so the window
    there is continuous in what the narrow-head walk returned over the row's
    whole context through its pages (a head read from another's lanes, a
    wrong scale, moves it by tenths; a page dropped among thousands of
    near-uniform weights does not: no rule of any cell sees that). Further
    behind, a near-tie the served path rightly chose the other way (rule
    (b)) moves a position's gated inputs by tenths;
(e) after the drain ONE more request goes through the served program, a
    one-token prompt asking for one token: its chunk starts a sequence in a
    slot that was held before, so the window it leaves must be zeros (the
    position before the sequence) on EVERY conv layer — a state that
    survives a slot's reuse shows here and nowhere else: a conv window
    forgets its first tenant after two tokens — and then its own gated
    input, held on the conv layers behind the expert layers this forward
    routes far from a tie for that token (``reuse_probe``: the token is
    chosen for it), behind at least one router: continuous values, where
    the tokens' rules are coarse.
"""

from __future__ import annotations

import numpy as np

from benchmark.references.hybrid_ssm_decoder import last_tenants
from benchmark.references.mla_moe_decoder import (logit_tolerance, reroutings,
                                                  stated_float32_leaves_differ)
from benchmark.references.window_gqa_moe import (BLOCK, REROUTE_ROUNDS, _blocks,
                                                 _f32, _rms_norm, _swiglu,
                                                 gqa_attention, row_width)

#: slots drawn for the comparison; of the rows that held them last the one
#: of shortest and the one of longest prompt are judged
SAMPLE_SLOTS = 4
#: rule (b): two biased scores on either side of the selection boundary
#: closer than this may be chosen the other way by the served path (its
#: router's INPUT went through bfloat16 products). 32 outputs: neighbouring
#: scores lie ~0.015 apart, the served score's error stays ~1e-3
ROUTER_DELTA = 6e-3
#: largest share of the positions checked that no admitted re-routing
#: explains. On the chip (PERF.md §6, PR 46, the review round's runs: every
#: one listed there) the served program reads 0.043 to 0.084 over sixteen
#: runs (mean 0.062); the controls through the timed path read: a state
#: that survives a slot's reuse 0.175 (the probe's to refuse: its row before
#: the sequence), the expert bias in the weights 0.248 (rule (e)'s line 1.01,
#: rule (d) behind one router 0.068), the QK norm left out 0.438 (rule (d)
#: there 0.182), products' left operands at 3 mantissa bits 0.555, the conv
#: a row late 1.0. The limit stands 1.43 x over the largest sound reading
#: (five of the readings' standard deviations over it) and 1.46 x under the
#: smallest control's (a walk that hands query heads
#: their neighbour's K/V lanes beyond 1,024 keys reads 0.238, 0.416 on the
#: long row, and 0.300 on rule (d) behind one router). WHY a sound run reads
#: this high is shown, not argued: ``_witness`` in every run, and the program
#: cut to no router on the chip, which reads 0. The share accepted only
#: RE-ROUTED is reported and limits nothing: no control moves it (the
#: program 0.14-0.18, the controls 0.0-0.22)
UNEXPLAINED_SHARE = 0.12
#: the same limit holds over each judged row of at least this many tokens:
#: of 32 sound rows of 512 on the chip the largest reads 0.090 (mean 0.062);
#: a walk that crosses heads at one position in ten beyond 1,024 keys reads
#: 0.150 on the long row (0.061 on the short, 0.105 over both: the limit
#: over all positions alone passes it; with this one the run is refused)
ROW_POSITIONS = 64
#: rule (d), (e): largest relative distance (norms over a conv layer's rows)
#: of a window in the pool (bfloat16 of bfloat16 products) from this
#: forward's gated inputs. On the chip the served program reads 0.0108 to
#: 0.0119 on the two layers ahead of every router, 0.0164 to 0.0186 on the
#: one behind one router and 0.0146 to 0.0237 on the five to nine rule (e)
#: holds; products at 3 mantissa bits 0.119, 0.257 and 0.179, the conv a row
#: late 1.42, 1.46 and 1.46, the QK norm left out 0.182 and the expert bias
#: in the weights 0.068 behind one router (PERF.md §6, PR 46)
STATE_REL_ERR = 0.05
#: rule (e): seeded one-token prompts this forward routes, and the gap
#: across the selection boundary over which a routing counts as far from a
#: tie (the served score's error stays ~1e-3; ``ROUTER_DELTA`` is 6e-3)
PROBE_CANDIDATES = 64
PROBE_DELTA = 0.02
#: rule (e): the served inputs' place on the line from this forward (0) to
#: the same forward with the bias in the weights (1) must be under this: the
#: served program reads -0.04 to +0.05 over sixteen runs, the control 1.01
BIAS_LINE_SHARE = 0.5

_FULL, _CONV = "full_attention", "conv"
#: the program's stack of a layer, by (kind, routed?)
_STACKS = {(_FULL, False): "dense_layers", (_FULL, True): "layers",
           (_CONV, False): "conv_dense_layers", (_CONV, True): "conv_layers"}


def short_conv(lp, y, hp):
    """A conv layer's mixer over [S, hidden] from the sequence's start.
    Returns (its output [S, hidden], the gated inputs ``v`` [S, hidden])."""
    import jax.numpy as jnp

    s, d = y.shape
    taps = hp["taps"]
    bcu = y @ _f32(lp["conv_in"]["w"])
    v = bcu[:, :d] * bcu[:, 2 * d:]
    ext = jnp.pad(v, ((taps - 1, 0), (0, 0)))
    w = _f32(lp["conv_w"])                                        # [hidden, taps]
    c = sum(ext[j:j + s] * w[:, j] for j in range(taps))
    return (bcu[:, d:2 * d] * c) @ _f32(lp["conv_out"]["w"]), v


def route(lp, y, hp, swap=None):
    """(chosen experts [S, k], their weights [S, k], ``near``: the biased
    scores [S, 4] and the experts [S, 4] of the two last chosen and the two
    first not chosen). The bias selects and never weighs; the weights are
    the scores over (their sum + ``topk_eps``). ``swap`` [S, 2] re-routes
    (``window_gqa_moe.route``)."""
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
    if hp.get("bias_weighs"):  # the alternative rule (e) holds the served path AWAY from
        w = w + _f32(lp["router_bias"])[idx]
    return idx, w / (w.sum(-1, keepdims=True) + hp["topk_eps"]) * hp["scaling"], near


def routed_experts(lp, y, hp, swap=None):
    """The weighted sum of the chosen experts' SwiGLUs, one expert at a time
    over every token with a mask. ``lp["experts"]`` is (the stack's experts,
    the layer's index): an expert's three matrices are read out of the stack
    one expert at a time."""
    import jax
    import jax.numpy as jnp

    idx, w, near = route(lp, y, hp, swap)
    ex, layer = lp["experts"]

    def one_expert(acc, i):
        weight = jnp.where(idx == i, w, 0.0).sum(-1, keepdims=True)
        return acc + weight * _swiglu(
            y, *(ex[k][layer, i] for k in ("w_gate", "w_up", "w_down"))), None

    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(y),
                          jnp.arange(ex["w_gate"].shape[1]))
    return out, near


def hyper(cfg) -> dict:
    """The sizes the forward needs, from the program's model config (read
    as a bag of keys; none of the program's code runs)."""
    # a model with no router at all (the witness's cut) stacks every layer
    # under the routed stacks' names
    moe = bool(cfg.n_routed_experts)
    dense = cfg.first_k_dense_replace if moe else cfg.layers
    return {
        "heads": cfg.heads, "kv_heads": cfg.kv_heads,
        "dh": cfg.head_dim or cfg.dim // cfg.heads, "theta": cfg.rope_theta,
        "eps": cfg.norm_eps, "qk_norm": cfg.qk_norm, "full_rope": True,
        "window": 0, "taps": cfg.conv_L_cache,
        "kinds": list(cfg.layer_types[:cfg.layers]),
        "dense": dense, "moe": moe, "top_k": cfg.num_experts_per_tok,
        "scaling": cfg.routed_scaling_factor, "topk_eps": cfg.norm_topk_eps,
        # expert layers ahead of each conv layer, in the layers' order
        "experts_ahead": tuple(
            max(i - dense, 0)
            for i, kind in enumerate(cfg.layer_types[:cfg.layers]) if kind == _CONV),
    }


def decoder_logits(params, input_ids, at, *, new: int, hp: dict, swaps=None,
                   state_at=0):
    """[S] ids -> (float32 logits [new, vocab] of the ``new`` positions from
    ``at`` on; ``near`` of those positions at every expert layer: scores and
    experts [new, expert layers, 4]; the conv layers' gated inputs of the
    ``taps - 1`` positions from ``state_at`` on [conv layers, taps - 1,
    hidden]). ``swaps`` [S, expert layers, 2] re-routes (``route``). Layers
    are visited one by one in the model's order, each read out of its
    stack, so one layer's float32 copies live at a time."""
    import jax
    import jax.numpy as jnp

    x = _f32(params["embed"]["table"][input_ids])
    near, gated, seen = [], [], {}
    for i, kind in enumerate(hp["kinds"]):
        routed = i >= hp["dense"]
        name = _STACKS[kind, routed or not hp["moe"]]
        j = seen[name] = seen.get(name, -1) + 1
        lp = jax.tree_util.tree_map(lambda a: a[j], {
            k: v for k, v in params[name].items() if k != "experts"})
        y = _rms_norm(lp["attn_norm"]["scale"], x, hp["eps"])
        if kind == _CONV:
            out, v = short_conv(lp, y, hp)
            gated.append(jax.lax.dynamic_slice_in_dim(v, state_at,
                                                      hp["taps"] - 1, axis=0))
        else:
            out = gqa_attention(lp, y, hp, kind)
        x = x + out
        y = _rms_norm(lp["mlp_norm"]["scale"], x, hp["eps"])
        if routed:
            lp["experts"] = (params[name]["experts"], j)
            out, n = routed_experts(
                lp, y, hp, None if swaps is None else swaps[:, len(near)])
            near.append(n)
        else:
            out = _blocks(
                lambda _, yb, lp=lp: _swiglu(yb, lp["w_gate"]["w"],
                                             lp["w_up"]["w"], lp["w_down"]["w"]),
                y.shape[0], y)
        x = x + out
    x = jax.lax.dynamic_slice_in_dim(x, at, new, axis=0)
    if near:
        near = tuple(jax.lax.dynamic_slice_in_dim(
            jnp.stack([n[j] for n in near], axis=1), at, new, axis=0)
            for j in (0, 1))
    else:  # no router at all: the cut the builder's witness serves (PERF.md §6)
        near = (jnp.zeros((new, 0, 4), jnp.float32), jnp.zeros((new, 0, 4), jnp.int32))
    x = _rms_norm(params["norm_out"]["scale"], x, hp["eps"])
    return x @ _f32(params["lm_head"]["w"]), near, jnp.stack(gated)


def _row_forward(hp: dict, new: int):
    """The jitted plain forward of one padded row, reduced on the device to
    what the rules read at each of the ``new`` positions."""
    import jax
    import jax.numpy as jnp

    def fn(params, row, at, served, swaps, state_at):
        logits, (near_s, near_e), gated = decoder_logits(
            params, row, at, new=new, hp=hp, swaps=swaps, state_at=state_at)
        top2 = jax.lax.top_k(logits, 2)[0]
        return {"best": top2[:, 0], "second": top2[:, 1],
                "argmax": jnp.argmax(logits, axis=-1).astype(jnp.int32),
                "served": jnp.take_along_axis(logits, served[:, None], 1)[:, 0],
                "absmax": jnp.abs(logits).max(), "near_scores": near_s,
                "near_experts": near_e, "gated": gated}

    return jax.jit(fn)


def rel_err(got, want) -> float:
    """Largest, over the conv layers, of |got - want| / |want| (norms over a
    layer's rows)."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    diff = np.sqrt(np.square(got - want).sum(axis=(1, 2)))
    return float((diff / np.maximum(np.sqrt(np.square(want).sum(axis=(1, 2))),
                                    1e-30)).max())


def judge_rows(params, hp: dict, prompt_ids: list, tokens: list, longest: int,
               states=None, delta: float = ROUTER_DELTA, shares: float = 1.0,
               token_rows=None) -> dict:
    """Rules (a), (b) and (d) over the given rows, as ``window_gqa_moe.
    judge_rows`` applies the first two (this model's forward, this cell's
    limits): each row is one plain forward over prompt + served tokens,
    right-padded; teacher forcing feeds the SERVED tokens; a row with
    refused tokens is run again, each of them re-routed by its next
    candidate, accepted re-routings staying in place. ``states``: the conv
    window each row left in the pool [conv layers, taps - 1, hidden], held
    to the forward's gated inputs of the last positions the row fed.
    ``shares`` scales the limit (a rehearsal's, see ``judge``).
    ``token_rows``: the rows rules (a) and (b) cover (all unless given; rule
    (d) holds every row's window: one forward a row). Beside the
    verdict: where the unexplained positions lie (by row, by quarter of a
    row's tokens) and the witness of their cause (``_witness``)."""
    import jax

    new = max(len(t) for t in tokens)
    layers = len(hp["kinds"]) - hp["dense"]
    keep = hp["taps"] - 1
    fn = _row_forward(hp, new)

    # ONE padded width for the rows judged (the longest's): a second
    # program costs a cold run half a minute of compile, the shorter row's
    # padding a second of products
    width = row_width(max(len(p) + len(t) for p, t in zip(prompt_ids, tokens)),
                      longest)

    def run(r, swaps, held_to=None):
        """Row ``r`` (its prompt and SERVED tokens) under ``swaps``; the
        logits are read at ``held_to`` (the served tokens unless given)."""
        pids, toks = prompt_ids[r], tokens[r]
        row = np.zeros((width,), np.int32)
        row[:len(pids)] = pids
        row[len(pids):len(pids) + len(toks)] = toks
        served = np.zeros((new,), np.int32)
        served[:len(toks)] = toks if held_to is None else held_to
        # the last decode step fed all but the last token: its window holds
        # the ``keep`` positions before the last token's
        fed = len(pids) + len(toks) - 1
        with jax.default_matmul_precision("highest"):
            out = jax.device_get(fn(params, row, np.int32(len(pids) - 1),
                                    served, swaps[:width],
                                    np.int32(max(fed - keep, 0))))
        return {k: np.asarray(v) for k, v in out.items()}

    none = np.full((longest + 4 * BLOCK, layers, 2), -1, np.int32)
    first = [run(r, none) for r in range(len(tokens))]
    token_rows = range(len(tokens)) if token_rows is None else token_rows
    tol = max(logit_tolerance(first[r]["absmax"]) for r in token_rows)

    def explain(r, out, rounds, held_to=None):
        """Rules (a) and (b) over row ``r``'s positions for the tokens
        ``out`` was read at: what is left unexplained after ``rounds``
        rounds of re-routing, and after the first."""
        n, at = len(tokens[r]), len(prompt_ids[r]) - 1
        gap = (out["best"] - out["served"])[:n]
        pending = {int(i): reroutings(out["near_scores"][i], out["near_experts"][i],
                                      delta)[:REROUTE_ROUNDS] if layers else []
                   for i in np.flatnonzero(gap > 2 * tol)}
        closest = {i: float(gap[i]) for i in pending}
        swaps, gaps, forwards, after_first = none.copy(), [], 0, len(pending)
        for k in range(rounds):
            trying = {i: c.pop(0) for i, c in pending.items() if c}
            if not trying:
                break
            trial = swaps.copy()
            for i, (_, _, moves) in trying.items():
                for layer, drop, add in moves:
                    trial[at + i, layer] = (drop, add)
            again = run(r, trial, held_to)
            forwards += 1
            for i, (_, gap_i, moves) in trying.items():
                closest[i] = min(closest[i],
                                 float(again["best"][i] - again["served"][i]))
                if closest[i] <= 2 * tol:
                    gaps.append(round(gap_i, 6))
                    for layer, drop, add in moves:
                        swaps[at + i, layer] = (drop, add)
                    del pending[i]
            if k == 0:
                after_first = len(pending)
        return {"pending": pending, "closest": closest, "gaps": gaps, "gap": gap,
                "forwards": forwards, "after_first": after_first}

    checked = decided = ties = unexplained = near_ties = forwards = after_first = 0
    gaps, worst = [], 0.0  # the widest score gap of each accepted re-routing
    first_unexplained = None
    by_row, by_quarter, witness = [], [0, 0, 0, 0], []
    for r in token_rows:
        toks, out = tokens[r], first[r]
        n = len(toks)
        margin = (out["best"] - out["second"])[:n]
        got = explain(r, out, REROUTE_ROUNDS)
        gap, pending = got["gap"], got["pending"]
        checked += n
        decided += int((margin > 2 * tol).sum())
        ties += int(((gap > 0) & (gap <= 2 * tol)).sum())
        worst = max(worst, float(gap.max()))
        near = out["near_scores"][:n]
        if layers:
            near_ties += int(((near[..., 1] - near[..., 2]).min(-1) < delta).sum())
        gaps += got["gaps"]
        forwards += got["forwards"]
        after_first += got["after_first"]
        unexplained += len(pending)
        by_row.append([len(pending), n, len(prompt_ids[r])])
        for i in pending:
            by_quarter[min(4 * i // n, 3)] += 1
        for i in sorted(pending)[:1]:
            first_unexplained = first_unexplained or (
                f"row {r} step {i}: token {toks[i]} lies {gap[i]:.4f} under "
                f"the reference's largest logit, {got['closest'][i]:.4f} under the "
                f"nearest re-routing's (admitted: {2 * tol:.4f}); gaps across "
                f"the selection boundary by expert layer "
                f"{np.round(near[i][:, 1] - near[i][:, 2], 5).tolist()}")
        if layers:
            witness.append(_witness(r, out, n, len(prompt_ids[r]) - 1, none, delta,
                                    run, explain))
            forwards += witness[-1].pop("forwards")
    n = max(checked, 1)
    held = hp["experts_ahead"].count(0)   # the conv layers ahead of every router
    state_err = behind_err = 0.0
    behind_rows = 0
    if states is not None:
        state_err = max((rel_err(st[:held], out["gated"][:held])
                         for st, out in zip(states, first)), default=0.0)
        # the conv layers behind ONE router (LFM2: the first behind an
        # attention layer, whose keys no router has touched): held where
        # this forward routes both of the window's positions outside the
        # margin rule (b) admits at that router, so that the window is
        # continuous in the walk's output over the row's whole context
        one = [j for j, ahead in enumerate(hp["experts_ahead"]) if ahead == 1]
        for toks, st, out in zip(tokens, states, first) if one else ():
            last = out["near_scores"][max(len(toks) - keep, 0):len(toks), 0]
            if len(last) and (last[:, 1] - last[:, 2]).min() >= delta:
                behind_rows += 1
                behind_err = max(behind_err, rel_err(st[one], out["gated"][one]))
    sums = {k: sum(w[k] for w in witness) for k in
            ("flipped", "moved", "unexplained_first_round")} if witness else {}
    # the limit holds over all positions AND over each row of its own (a
    # row of under ``ROW_POSITIONS`` tokens is too few to hold to a share):
    # a fault of long contexts shows on the long row alone
    limit = shares * UNEXPLAINED_SHARE
    return {"ok": bool(decided > 0 and unexplained <= limit * n
                       and all(left <= limit * of for left, of, _ in by_row
                               if of >= ROW_POSITIONS)
                       and max(state_err, behind_err) <= STATE_REL_ERR),
            "positions_checked": checked, "positions_decided": decided,
            "near_tie_divergences": ties, "unexplained": unexplained,
            "unexplained_share": unexplained / n,
            "unexplained_first_round_share": after_first / n,
            "unexplained_by_row": by_row, "unexplained_by_quarter": by_quarter,
            "rerouted": len(gaps), "rerouted_share": len(gaps) / n,
            "widest_gap_rerouted": max(gaps, default=0.0),
            "largest_distance_under_best": worst,
            "router_delta": delta, "router_near_tie_share": near_ties / n,
            "reroute_forwards": forwards, "state_rel_err": state_err,
            "state_rel_err_behind_one_router": behind_err,
            "rows_held_behind_one_router": behind_rows, "rows_with_windows_held":
            len(first) if states is not None else 0,
            "witness": {**sums, "unexplained_first_round_share":
                        sums["unexplained_first_round"] / n} if sums else None,
            "first_unexplained": first_unexplained, "logit_tol": tol}


def _witness(r, out, n, at, none, delta, run, explain) -> dict:
    """The cause of the unexplained positions, shown without the program:
    THIS forward against ITSELF. Every choice of row ``r``'s judged
    positions whose gap across the selection boundary is under a quarter of
    ``delta`` (what a served score's error reaches) is resolved the other
    way — each a re-routing the rules admit —, the forward's own greedy
    tokens under those choices are read, and they are held to the
    unflipped forward by rules (a) and (b) with one round of re-routing
    (compare ``unexplained_first_round_share``). What stays unexplained was
    moved by ANOTHER position's admitted choice (a conv layer reads its two
    predecessors' gated inputs, an attention layer every earlier key): the
    floor the token rules have on a model that holds every expert, whatever
    serves it."""
    gap = out["near_scores"][:n, :, 1] - out["near_scores"][:n, :, 2]
    ii, ll = np.nonzero(gap < delta / 4)
    flipped = none.copy()
    flipped[at + ii, ll, 0] = out["near_experts"][ii, ll, 1]
    flipped[at + ii, ll, 1] = out["near_experts"][ii, ll, 2]
    own = run(r, flipped)["argmax"][:n]
    held = explain(r, run(r, none, own), 1, own)
    return {"flipped": len(ii), "moved": int((held["gap"] > 0).sum()),
            "unexplained_first_round": held["after_first"],
            "forwards": 2 + held["forwards"]}


def probe_candidates(params, hp: dict, tokens) -> tuple:
    """This forward over each of ``tokens`` as a one-token sequence (a
    second position of padding behind it): (the gaps across the selection
    boundary [tokens, expert layers] — last chosen against first not chosen
    —, the conv layers' gated input of the token [tokens, conv layers,
    hidden], the same under the ALTERNATIVE routing rule — the expert bias
    added to the chosen experts' weights too)."""
    import jax
    import jax.numpy as jnp

    def one(params, token):
        row = jnp.stack([token, jnp.zeros_like(token)])
        _, (near, _), gated = decoder_logits(params, row, 0, new=1, hp=hp)
        alt = decoder_logits(params, row, 0, new=1,
                             hp={**hp, "bias_weighs": True})[2]
        return near[0, :, 1] - near[0, :, 2], gated[:, 0], alt[:, 0]

    # the weights are an ARGUMENT: closed over, 8 GB of them would be
    # constants of the program
    with jax.default_matmul_precision("highest"):
        out = jax.jit(jax.vmap(one, in_axes=(None, 0)))(
            params, jnp.asarray(tokens, jnp.int32))
    return tuple(np.asarray(a) for a in out)


def reuse_probe(server, params, hp: dict, seed: int, vocab: int) -> dict:
    """Rule (e): a one-token prompt asking for one token through the served
    program, after the drain, then the window its chunk left in its slot.
    The row before the sequence must be ZERO in every conv layer. The other
    row is the token's gated input: a one-token sequence has no context, so
    a conv layer's input depends on the token's OWN routing at the expert
    layers ahead of it alone — the token is the one of ``PROBE_CANDIDATES``
    seeded ones whose leading expert layers this forward routes FURTHEST
    from a tie (every gap over ``PROBE_DELTA``, three times the re-routing
    margin), and the conv layers behind those layers only are held, to
    ``STATE_REL_ERR``: continuous values from behind the routers, where
    products at 3 mantissa bits show (a tenth) and the tokens' rules cannot
    tell them from a near-tie. THE BIAS SELECTS AND NEVER WEIGHS: adding it
    to the weights moves those inputs by less than the bfloat16 products do
    (~1 %: an expert layer's output is a tenth of the stream), so no
    distance sees it — but its DIRECTION is this forward's to compute: the
    served inputs' place on the line from this forward (0) to the same
    forward with the bias in the weights (1) must be under a half
    (``bias_in_weights_share``: in 2,048 x layers dimensions the products'
    rounding has next to no component along that line)."""
    import asyncio

    rng = np.random.default_rng([int(seed), 0x50524F42])
    tokens = rng.choice(np.arange(1, vocab), PROBE_CANDIDATES, replace=False)
    gaps, gated, alt = probe_candidates(params, hp, tokens)
    robust = (np.cumprod(gaps >= PROBE_DELTA, axis=1)).sum(axis=1)
    best = int(np.argmax(robust))
    token = int(tokens[best])
    # conv layer j is held where every expert layer ahead of it is robust
    held = [j for j, ahead in enumerate(hp["experts_ahead"]) if ahead <= robust[best]]
    asyncio.run(server.generate([token], max_new_tokens=1))
    found = [st for st in map(server.slot_state, range(server.slots))
             if st["prompt"] is not None and list(st["prompt"]) == [token]
             and not st["tokens"][1:]]
    if len(found) != 1:
        return {"ok": False, "why": f"the probe holds {len(found)} slots"}
    state = np.asarray(found[0]["state"], np.float32)
    before = float(np.abs(state[:, :-1]).max())
    err = rel_err(state[held, -1:], gated[best][held, None])
    line = (alt[best] - gated[best])[held]
    share = float(((state[held, -1] - gated[best][held]) * line).sum()
                  / max(float(np.square(line).sum()), 1e-30))
    return {"ok": bool(before == 0.0 and err <= STATE_REL_ERR
                       and share < BIAS_LINE_SHARE
                       and found[0]["tenancy"] >= 2
                       # behind at least one router, where the model has one
                       and (len(held) > hp["experts_ahead"].count(0)
                            or not any(hp["experts_ahead"]))),
            "token": token, "tenancy": int(found[0]["tenancy"]),
            "before_abs_max": before, "gated_rel_err": err,
            "bias_in_weights_share": share,
            "conv_layers_held": len(held),
            "expert_layers_robust": int(robust[best])}


def judge(ctx) -> dict:
    """Sample slots, teacher-force the rows of shortest and longest prompt
    among those that held them last and hold their served tokens and the
    windows they left to the plain forward; probe a slot's reuse; every
    written row must carry exactly ``max_new_tokens`` tokens (``eos_id`` -1:
    no early exit). A rehearsal (hidden 64, 8 experts: nearly every position
    has a choice within a rounding of its boundary) holds the control flow,
    the counts, the states and the stated leaves, and the shares to twice
    their limits across a near-tie margin five times as wide."""
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
    rng = np.random.default_rng([int(ctx.seed), 0x4C46])
    slots = rng.choice(server.slots, min(SAMPLE_SLOTS, server.slots), replace=False)
    rows, why = last_tenants(server, slots, written, want)
    if why:
        return {"ok": False, "why": why}
    rows.sort(key=lambda r: len(r["prompt"]))
    ends = sorted({0, len(rows) - 1})   # the token rules' rows: shortest, longest
    hp = hyper(proc.cfg)
    longest = max_input + want
    verdict = judge_rows(
        proc.params, hp, [r["prompt"] for r in rows], [r["tokens"] for r in rows],
        longest, [r["state"] for r in rows], token_rows=ends,
        **(dict(shares=2.0, delta=5 * ROUTER_DELTA)
           if getattr(ctx, "rehearse", False) else {}))
    verdict["rows_sampled"] = len(ends)
    verdict["prompt_tokens_judged"] = [len(rows[r]["prompt"]) for r in ends]
    verdict["least_tenancy"] = min(r["tenancy"] for r in rows)
    verdict["rows_with_wrong_token_count"] = short
    verdict["float32_values_not_as_stated"] = stated_float32_leaves_differ(
        proc.params, proc.host_params)
    verdict["reuse_probe"] = reuse_probe(server, proc.params, hp, ctx.seed,
                                         proc.cfg.vocab_size)
    verdict["ok"] = bool(verdict["ok"] and short == 0
                         and verdict["least_tenancy"] >= 2
                         and verdict["reuse_probe"]["ok"]
                         and verdict["float32_values_not_as_stated"] == 0)
    return verdict
