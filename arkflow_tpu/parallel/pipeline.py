"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

Completes the parallelism family (dp/tp/sp/ep/pp): the decoder's stacked
layer parameters shard along their leading (layer) dimension over ``pp``
stages, and activations stream stage-to-stage with ``jax.lax.ppermute``
inside a ``shard_map`` — the TPU-native expression of pipeline parallelism
(a ring of ICI hops, no NCCL-style send/recv). The classic GPipe schedule
runs M microbatches over ``M + S - 1`` ticks, so all S stages are busy in
the steady state and the bubble is (S-1)/(M+S-1).

Scope: dense decoder configs (MoE routes through ep, long context through
sp/ring attention — composing those with pp is future work; the builder
rejects the combinations). dp composes: the batch shards over ``dp`` while
each dp-replica's pipeline runs over ``pp``.

Correctness bar (tested): pp loss == single-device loss to float tolerance,
and grads flow to every stage's parameters (embedding/head replicate; their
grads psum across stages via the shard_map transpose).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import common as cm
from arkflow_tpu.models.decoder import DecoderConfig, _attention_block, _mlp
from arkflow_tpu.parallel.segment import StagePlan


def pp_param_specs(cfg: DecoderConfig) -> dict:
    """Layer stacks shard over pp on the layer dim; the rest replicates."""
    layer = {
        "attn_norm": {"scale": P("pp")},
        "wq": {"w": P("pp")}, "wk": {"w": P("pp")}, "wv": {"w": P("pp")},
        "wo": {"w": P("pp")},
        "mlp_norm": {"scale": P("pp")},
        "w_gate": {"w": P("pp")}, "w_up": {"w": P("pp")}, "w_down": {"w": P("pp")},
    }
    return {
        "embed": {"table": P()},
        "norm_out": {"scale": P()},
        "lm_head": {"w": P()},
        "layers": layer,
    }


def _stage_apply(lp_stack, x, cfg: DecoderConfig, positions, causal):
    """Run this stage's local layer stack (the shared dense block math)."""

    def layer(x, lp):
        x = _attention_block(lp, x, cfg, positions, causal)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        return x + _mlp(lp, y, cfg), None

    x, _ = jax.lax.scan(layer, x, lp_stack)
    return x


def make_pp_train_step(cfg: DecoderConfig, optimizer, mesh: Mesh, *,
                       microbatches: int | None = None):
    """Pipeline-parallel training step over mesh axes (dp, pp).

    Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    loss)``; jit it under the mesh. ``batch`` carries input_ids/targets/mask
    sharded over dp. Params must be placed with ``pp_param_specs`` (layer
    stacks split across stages).
    """
    if cfg.num_experts > 1:
        raise ConfigError("pipeline parallelism + MoE (ep) is not composed yet")
    if cfg.use_ring_attention:
        raise ConfigError("pipeline parallelism + ring attention is not composed yet")
    stages = mesh.shape["pp"]
    if cfg.layers % stages != 0:
        raise ConfigError(f"layers ({cfg.layers}) must divide by pp stages ({stages})")
    n_micro = microbatches or stages
    perm = [(i, (i + 1) % stages) for i in range(stages)]

    def pp_loss(params, ids, targets, mask):
        """Runs per-device under shard_map: layer stack is the LOCAL shard."""
        stage = jax.lax.axis_index("pp")
        b, s = ids.shape
        if b % n_micro != 0:
            raise ConfigError(
                f"per-replica batch {b} must divide by microbatches {n_micro}")
        mb = b // n_micro
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (mb, s))
        causal = jnp.tril(jnp.ones((s, s), bool))[None, None]

        # every stage embeds (params replicate; trivial FLOPs) — only stage
        # 0's result enters the pipeline, but a uniform program keeps SPMD
        x = cm.embedding(params["embed"], ids)                     # [B, S, D]
        mb_x = x.reshape(n_micro, mb, s, cfg.dim)

        def tick(cur, t):
            # stage 0 ingests microbatch t (clamped; ticks >= M recirculate
            # garbage that never reaches a valid output slot)
            inject = jax.lax.dynamic_index_in_dim(
                mb_x, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, inject, cur)
            out = _stage_apply(params["layers"], inp, cfg, positions, causal)
            nxt = jax.lax.ppermute(out, "pp", perm)
            return nxt, out

        zeros = jnp.zeros((mb, s, cfg.dim), x.dtype)
        _, outs = jax.lax.scan(tick, zeros, jnp.arange(n_micro + stages - 1))
        # the LAST stage's outputs at ticks S-1 .. S-1+M-1 are the finished
        # microbatches, in order
        final = outs[stages - 1:stages - 1 + n_micro]              # [M, mb, S, D]
        h = final.reshape(b, s, cfg.dim)
        h = cm.rms_norm(params["norm_out"], h, cfg.norm_eps)
        logits = cm.dense(params["lm_head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        maskf = mask.astype(jnp.float32)
        local = -(ll * maskf).sum() / jnp.maximum(maskf.sum(), 1.0)
        # only the last stage computed real logits; broadcast its loss
        loss = jax.lax.psum(jnp.where(stage == stages - 1, local, 0.0), "pp")
        return jax.lax.pmean(loss, "dp")

    specs = pp_param_specs(cfg)
    data_spec = P("dp")
    loss_fn = jax.shard_map(
        pp_loss, mesh=mesh, in_specs=(specs, data_spec, data_spec, data_spec),
        out_specs=P(), check_vma=False)

    def train_step(params, opt_state, batch):
        import optax

        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch["input_ids"], batch["targets"], batch["mask"])
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step


# -- pipelined INFERENCE (profiled segmentation serving) ---------------------
#
# The serving twin of the train step above: stage-sharded layer stacks, the
# same ppermute ring and GPipe tick scan, but forward-only and driven by a
# StagePlan (parallel/segment.py) so stages can hold UNEVEN layer ranges when
# a measured profile says the balanced cut is uneven. Families plug in via an
# extras hook ``pp_stage_fns(cfg) -> (pre_fn, layer_fn, post_fn)``:
#
#   pre_fn(params, inputs)   -> (x, aux)   embeddings + per-batch side inputs
#   layer_fn(lp, x, aux)     -> x          ONE layer (math identical to the
#                                          family's single-device scan body)
#   post_fn(params, x, aux)  -> {outputs}  head (logits/labels/scores)
#
# Every stage runs pre_fn/post_fn on replicated params (trivial FLOPs — the
# uniform program keeps SPMD); only the last stage's head output is real, and
# a masked psum broadcasts it so the step returns replicated outputs.


def pp_layer_slot_tables(plan: StagePlan) -> tuple[np.ndarray, np.ndarray]:
    """Per-stage layer slot tables for an (possibly uneven) plan.

    Stages scan a PADDED local stack of ``Lmax = max(plan.sizes)`` slots so
    the sharded layer array stays rectangular; ``index[s, j]`` is the source
    layer for stage ``s`` slot ``j`` (filler slots point at layer 0) and
    ``active[s, j]`` marks real slots — the executor skips inactive slots
    with ``lax.cond``, so a short stage pays for ITS layers, not Lmax.
    """
    lmax = max(plan.sizes)
    index = np.zeros((plan.stages, lmax), np.int32)
    active = np.zeros((plan.stages, lmax), bool)
    for s, (start, end) in enumerate(plan.bounds):
        n = end - start
        index[s, :n] = np.arange(start, end, dtype=np.int32)
        active[s, :n] = True
    return index, active


def pp_repack_layers(params: dict, plan: StagePlan):
    """Repack a family's stacked ``params["layers"]`` (leading dim = layer)
    into the stage-padded layout ``[S * Lmax, ...]`` the pp executor shards
    over ``pp``: stage ``s`` owns slots ``s*Lmax .. (s+1)*Lmax - 1``, real
    layers first, filler slots repeating layer 0 (never executed — the slot
    table masks them). Host-side; returns a NEW params dict."""
    index, _ = pp_layer_slot_tables(plan)
    flat_idx = jnp.asarray(index.reshape(-1))

    def take(leaf):
        if plan.num_layers == 0 or leaf.shape[0] != plan.num_layers:
            raise ConfigError(
                f"pp repack: layer stack leaf has leading dim {leaf.shape[0]}, "
                f"expected {plan.num_layers} (the plan's layer count)")
        return jnp.take(leaf, flat_idx, axis=0)

    out = dict(params)
    out["layers"] = jax.tree_util.tree_map(take, params["layers"])
    return out


def pp_infer_param_specs(params: dict) -> dict:
    """PartitionSpec pytree for pp serving over REPACKED params: layer slots
    shard over ``pp`` on the leading dim, everything else replicates (embed/
    head run on every stage). Built from the actual (possibly quantized)
    tree, so int8's {w_q, w_scale} leaves need no spec rewrite."""
    return {
        k: jax.tree_util.tree_map(lambda _: P("pp") if k == "layers" else P(), v)
        for k, v in params.items()
    }


def make_pp_infer_step(family, cfg, mesh: Mesh, *, plan: StagePlan,
                       microbatch_rows: int, param_specs: Optional[dict] = None):
    """Pipeline-parallel INFERENCE step over mesh axes (dp, pp).

    Returns ``infer_fn(params, inputs) -> outputs`` to be jitted (the runner
    owns jit/donation/shardings). ``inputs`` are the family's input_spec
    arrays, batch-leading; params must be repacked (``pp_repack_layers``)
    and placed with ``pp_infer_param_specs`` — pass that same spec tree as
    ``param_specs`` (it becomes the shard_map in_specs, so the wrapped
    function's partitioning can never disagree with the placement).

    Schedule: the per-replica batch ``b`` splits into ``M = b /
    microbatch_rows`` microbatches streamed through S stages over
    ``M + S - 1`` ticks (GPipe forward). M is derived from the static batch
    shape, so every bucket keeps its own bucket-exact microbatch count and
    the analytic bubble is (S-1)/(M+S-1) per compiled shape.
    """
    extras = family.extras or {}
    if "pp_stage_fns" not in extras:
        raise ConfigError(
            f"model {family.name!r} has no pipeline-parallel serving support "
            "(family extras lack pp_stage_fns)")
    pre_fn, layer_fn, post_fn = extras["pp_stage_fns"](cfg)
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    stages = int(axis_sizes.get("pp", 1))
    if stages != plan.stages:
        raise ConfigError(
            f"pp mesh has {stages} stages but the plan cuts {plan.stages}")
    if microbatch_rows < 1:
        raise ConfigError(
            f"pp microbatch_rows must be >= 1, got {microbatch_rows}")
    if param_specs is None:
        raise ConfigError(
            "make_pp_infer_step requires param_specs "
            "(pp_infer_param_specs over the repacked tree)")
    perm = [(i, (i + 1) % stages) for i in range(stages)]
    index_tbl, active_tbl = pp_layer_slot_tables(plan)
    lmax = index_tbl.shape[1]

    def pp_infer(params, inputs):
        """Runs per-device under shard_map: ``params['layers']`` is the
        LOCAL [Lmax, ...] stage shard; inputs are the dp-local batch."""
        stage = jax.lax.axis_index("pp")
        x, aux = pre_fn(params, inputs)
        b = x.shape[0]
        mb = min(microbatch_rows, b)
        if b % mb != 0:
            raise ConfigError(
                f"pp: per-replica batch {b} must divide by microbatch rows "
                f"{mb} (align the bucket grid with pp_microbatch_rows)")
        n_micro = b // mb
        mb_x = x.reshape(n_micro, mb, *x.shape[1:])
        mb_aux = jax.tree_util.tree_map(
            lambda a: a.reshape(n_micro, mb, *a.shape[1:]), aux)
        active = jnp.asarray(active_tbl)[stage]  # [Lmax] bool, this stage's

        def stage_apply(h, aux_j):
            if plan.uniform:
                # even cut: every slot is real — plain scan, no masking
                def body(h, lp):
                    return layer_fn(lp, h, aux_j), None
                h, _ = jax.lax.scan(body, h, params["layers"])
                return h

            def body(h, slot):
                lp, act = slot
                # cond (not where): a filler slot SKIPS its layer math, so a
                # 2-layer stage next to a 4-layer stage costs 2 layers/tick
                return jax.lax.cond(
                    act, lambda t: layer_fn(lp, t, aux_j), lambda t: t, h), None

            h, _ = jax.lax.scan(body, h, (params["layers"], active))
            return h

        def tick(cur, t):
            # stage 0 ingests microbatch t (clamped: ticks >= M recirculate
            # garbage that never reaches a valid output slot); stage s is
            # processing microbatch t - s, so its side inputs index there
            inject = jax.lax.dynamic_index_in_dim(
                mb_x, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            j = jnp.clip(t - stage, 0, n_micro - 1)
            aux_j = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, j, 0, keepdims=False),
                mb_aux)
            inp = jnp.where(stage == 0, inject, cur)
            out = stage_apply(inp, aux_j)
            nxt = jax.lax.ppermute(out, "pp", perm)
            return nxt, out

        zeros = jnp.zeros((mb, *x.shape[1:]), x.dtype)
        _, outs = jax.lax.scan(tick, zeros, jnp.arange(n_micro + stages - 1))
        # the LAST stage's outputs at ticks S-1 .. S-1+M-1 are the finished
        # microbatches, in order (garbage on every other stage)
        final = outs[stages - 1:stages - 1 + n_micro]
        h = final.reshape(b, *x.shape[1:])
        out = post_fn(params, h, aux)

        def bcast(leaf):
            # only the last stage computed real outputs; mask-then-psum
            # broadcasts them (adding exact zeros — argmax/bitwise safe for
            # every representable value except -0.0 -> +0.0)
            masked = jnp.where(stage == stages - 1, leaf,
                               jnp.zeros_like(leaf))
            return jax.lax.psum(masked, "pp")

        return jax.tree_util.tree_map(bcast, out)

    data_spec = P("dp")
    return jax.shard_map(pp_infer, mesh=mesh, in_specs=(param_specs, data_spec),
                         out_specs=data_spec, check_vma=False)
