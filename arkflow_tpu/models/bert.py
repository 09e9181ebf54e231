"""BERT-base sequence classifier — the flagship streaming-inference model.

Target workload: Kafka text -> BERT-base classify -> Kafka (BASELINE.json
config 2, >=100k rows/sec/chip at p99 < 50ms on v5e). Architecture follows the
standard BERT-base shape (12 layers, hidden 768, 12 heads, FFN 3072,
vocab 30522) as a pure-JAX functional model: bfloat16 matmuls on the MXU,
float32 LN, softmax in float32 by default (``softmax_dtype: bfloat16``
halves scores bandwidth — the serving/bench opt-in), static shapes
bucketed by the runner.

Weights can be imported from a HuggingFace ``bert-base-uncased`` checkpoint
when one is available locally (``from_hf_state_dict``); benches run fine on
random init since throughput is weight-independent.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from arkflow_tpu.models import common as cm
from arkflow_tpu.models.registry import ModelFamily, register_model


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    ffn: int = 3072
    max_positions: int = 512
    type_vocab: int = 2
    num_labels: int = 2
    ln_eps: float = 1e-12
    #: attention via the ragged Pallas kernel. REQUIRES right-padding:
    #: attention_mask must be a contiguous prefix of ones (row sums become
    #: per-row lengths; ModelRunner enforces this outside jit). Fully-padded
    #: K tiles are skipped on the MXU; pad positions output zeros instead of
    #: attending (identical [CLS] logits — pad keys are masked either way).
    #: None = auto: ModelRunner resolves to True on TPU backends (where the
    #: kernel wins on partially-filled buckets), False elsewhere; direct
    #: ``apply`` callers get the XLA path unless they opt in explicitly.
    use_flash_attention: "bool | None" = None
    #: trace-time floor: buckets with seq below this use XLA attention even
    #: when flash is on. At short seq the kernel's tiles degenerate (tile =
    #: seq < MXU 128x128) and the grid overhead dominates — measured on a
    #: v5e at seq 32 the Pallas path cost 47% of end-to-end throughput.
    #: None = unset: no floor for direct/explicit users; ModelRunner's
    #: auto-resolution fills in the measured crossover (128) only then, so
    #: an operator-tuned value is never clobbered.
    flash_min_seq: "int | None" = None
    flash_interpret: bool = False  # CPU-interpret mode (tests)
    #: packed execution only: route the block-diagonal attention through the
    #: segment flash kernel (ops/segment_attention.py) instead of an XLA
    #: pair mask. Resolved by ModelRunner from ARKFLOW_PACKED_FLASH=1 (TPU
    #: backends, kill-switchable via ARKFLOW_FLASH=0) — direct callers opt
    #: in explicitly; stays off until the kernel has chip A/B numbers.
    packed_flash: bool = False
    #: softmax accumulation dtype for XLA attention. float32 is the safe
    #: default; "bfloat16" halves the scores-tensor bandwidth, worth ~11%
    #: of the whole serving step at b1024/seq32 on a v5e (60.8 -> 54.2ms
    #: measured) with argmax-identical labels on the tested checkpoints.
    #: An explicit reduced-precision opt-in like serving_dtype.
    softmax_dtype: str = "float32"

    def __post_init__(self):
        if self.softmax_dtype not in ("float32", "bfloat16"):
            from arkflow_tpu.errors import ConfigError

            raise ConfigError(
                f"softmax_dtype {self.softmax_dtype!r} invalid "
                "(float32/bfloat16)")


def init(rng, cfg: BertConfig) -> dict:
    keys = iter(jax.random.split(rng, 16 + 8 * cfg.layers))
    params = {
        "embed": {
            "word": cm.embedding_init(next(keys), cfg.vocab_size, cfg.hidden),
            "position": cm.embedding_init(next(keys), cfg.max_positions, cfg.hidden),
            "token_type": cm.embedding_init(next(keys), cfg.type_vocab, cfg.hidden),
            "ln": cm.layer_norm_init(cfg.hidden),
        },
        "layers": [],
        "pooler": cm.dense_init(next(keys), cfg.hidden, cfg.hidden),
        "classifier": cm.dense_init(next(keys), cfg.hidden, cfg.num_labels),
    }
    for _ in range(cfg.layers):
        params["layers"].append(
            {
                "q": cm.dense_init(next(keys), cfg.hidden, cfg.hidden),
                "k": cm.dense_init(next(keys), cfg.hidden, cfg.hidden),
                "v": cm.dense_init(next(keys), cfg.hidden, cfg.hidden),
                "attn_out": cm.dense_init(next(keys), cfg.hidden, cfg.hidden),
                "attn_ln": cm.layer_norm_init(cfg.hidden),
                "ffn_in": cm.dense_init(next(keys), cfg.hidden, cfg.ffn),
                "ffn_out": cm.dense_init(next(keys), cfg.ffn, cfg.hidden),
                "ffn_ln": cm.layer_norm_init(cfg.hidden),
            }
        )
    # stack per-layer params into leading-axis pytrees for lax.scan over layers
    params["layers"] = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params["layers"])
    return params


def encode(params: dict, cfg: BertConfig, input_ids, attention_mask,
           *, positions=None, pair_mask=None, segments=None):
    """[B, S] ids/mask -> [B, S, hidden] bf16 encodings.

    ``positions``/``pair_mask``/``segments`` are the packed-execution hooks
    (tpu/packing.py): per-token position ids, a full [B,1,Sq,Sk]
    block-diagonal mask, or (instead of the mask) per-token segment ids
    driving the segment flash kernel — the mask disables the ragged flash
    kernel (it reads prefix lengths, which cannot express segment
    structure); ``segments`` routes to ``ops/segment_attention.py``, which
    derives the mask in-kernel without O(S^2) HBM traffic.
    """
    b, s = input_ids.shape
    if positions is None:
        positions = jnp.arange(s)[None, :]
    x = (
        cm.embedding(params["embed"]["word"], input_ids)
        + cm.embedding(params["embed"]["position"], positions)
        + cm.embedding(params["embed"]["token_type"], jnp.zeros_like(input_ids))
    )
    x = cm.layer_norm(params["embed"]["ln"], x, cfg.ln_eps)
    if pair_mask is not None:
        mask = pair_mask
    else:
        mask = attention_mask[:, None, None, :].astype(bool)  # [B,1,1,Sk]
    lengths = attention_mask.astype(jnp.int32).sum(axis=1)  # contiguous-prefix masks
    flash_ok = pair_mask is None and segments is None

    def _pow2_tile() -> int:
        # largest pow2 tile (<=128) dividing the bucket length, so any
        # configured seq bucket works
        tile = 1
        while tile * 2 <= min(s, 128) and s % (tile * 2) == 0:
            tile *= 2
        return tile

    def _attend(q, k, v):
        # s is static at trace time: each bucket decides flash-vs-XLA
        # independently, so one stream can serve seq-32 on XLA and seq-512
        # on the ragged kernel from the same config
        if segments is not None:
            from arkflow_tpu.ops.segment_attention import segment_flash_attention

            tile = _pow2_tile()
            qh = jnp.einsum("bshd->bhsd", q)
            kh = jnp.einsum("bshd->bhsd", k)
            vh = jnp.einsum("bshd->bhsd", v)
            out = segment_flash_attention(
                qh, kh, vh, segments, tile_q=tile, tile_k=tile,
                interpret=cfg.flash_interpret,
            )
            return jnp.einsum("bhsd->bshd", out)
        if flash_ok and cfg.use_flash_attention and s >= (cfg.flash_min_seq or 0):
            from arkflow_tpu.ops.ragged_attention import ragged_flash_attention

            tile = _pow2_tile()
            qh = jnp.einsum("bshd->bhsd", q)
            kh = jnp.einsum("bshd->bhsd", k)
            vh = jnp.einsum("bshd->bhsd", v)
            out = ragged_flash_attention(
                qh, kh, vh, lengths, tile_q=tile, tile_k=tile,
                interpret=cfg.flash_interpret,
            )
            return jnp.einsum("bhsd->bshd", out)
        return cm.attention(q, k, v, mask,
                            softmax_dtype=jnp.dtype(cfg.softmax_dtype))

    def layer(x, lp):
        h = cfg.heads
        dh = cfg.hidden // h
        q = cm.dense(lp["q"], x).reshape(b, s, h, dh)
        k = cm.dense(lp["k"], x).reshape(b, s, h, dh)
        v = cm.dense(lp["v"], x).reshape(b, s, h, dh)
        attn = _attend(q, k, v).reshape(b, s, cfg.hidden)
        x = cm.layer_norm(lp["attn_ln"], x + cm.dense(lp["attn_out"], attn), cfg.ln_eps)
        ff = cm.dense(lp["ffn_out"], cm.gelu(cm.dense(lp["ffn_in"], x)))
        x = cm.layer_norm(lp["ffn_ln"], x + ff, cfg.ln_eps)
        return x, None

    # scan over stacked layers: one traced layer body regardless of depth
    x, _ = jax.lax.scan(layer, x, params["layers"])
    return x


def apply(params: dict, cfg: BertConfig, *, input_ids, attention_mask) -> dict:
    x = encode(params, cfg, input_ids, attention_mask)
    pooled = jnp.tanh(cm.dense(params["pooler"], x[:, 0, :]))
    logits = cm.dense(params["classifier"], pooled).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return {
        "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
        "score": jnp.max(probs, axis=-1),
        "logits": logits,
    }


def apply_packed(params: dict, cfg: BertConfig, *, input_ids, segment_ids,
                 position_ids, example_row, example_pos) -> dict:
    """Packed-execution forward (tpu/packing.py layout): [P, S] packed rows
    holding E examples. Attention is block-diagonal on ``segment_ids``
    (tokens never attend across examples; 0 marks dead positions), position
    embeddings follow ``position_ids``, and each example's [CLS] encoding is
    gathered from (example_row, example_pos) — outputs are [E] in original
    example order. Fully-dead padded rows are sliced away by the caller
    (their un-gathered encodings are path-dependent: uniform attention on
    the XLA pair-mask path, exact zeros on the segment-kernel path).
    """
    seg = segment_ids
    live = (seg > 0).astype(jnp.int32)
    if cfg.packed_flash and input_ids.shape[1] >= (cfg.flash_min_seq or 0):
        # opt-in segment flash kernel (ops/segment_attention.py): in-kernel
        # block-diagonal masking, no O(S^2) mask in HBM. cfg-resolved (see
        # packed_flash) so the kill switch and backend checks happen at
        # runner altitude, never as an env read inside the jit.
        x = encode(params, cfg, input_ids, live,
                   positions=position_ids, segments=seg)
    else:
        pair = (seg[:, None, :] == seg[:, :, None]) & (seg > 0)[:, None, :]
        pair_mask = pair[:, None, :, :]  # [P, 1, Sq, Sk], broadcast over heads
        x = encode(params, cfg, input_ids, live,
                   positions=position_ids, pair_mask=pair_mask)
    cls = x[example_row, example_pos, :]  # [E, hidden]
    pooled = jnp.tanh(cm.dense(params["pooler"], cls))
    logits = cm.dense(params["classifier"], pooled).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    return {
        "label": jnp.argmax(logits, axis=-1).astype(jnp.int32),
        "score": jnp.max(probs, axis=-1),
        "logits": logits,
    }


def input_spec(cfg: BertConfig) -> dict:
    return {"input_ids": ("int32", ("seq",)), "attention_mask": ("int32", ("seq",))}


def packed_input_spec(cfg: BertConfig) -> dict:
    """Input spec for packed execution. Leading-dim roles: ``packed`` arrays
    share the packed-row dim P; ``example`` arrays share the example dim E."""
    return {
        "input_ids": ("int32", ("seq",)),
        "segment_ids": ("int32", ("seq",)),
        "position_ids": ("int32", ("seq",)),
        "example_row": ("int32", ()),
        "example_pos": ("int32", ()),
    }


def param_specs(cfg: BertConfig, axes: dict) -> dict:
    """PartitionSpecs for tensor-parallel serving: heads/FFN sharded on ``tp``.

    ``axes`` maps logical axis roles to mesh axis names, e.g. {"tp": "tp"}.
    """
    tp = axes.get("tp")
    d = lambda spec_w: {"w": spec_w, "b": P(spec_w[-1])}  # bias follows output dim
    layer = {
        "q": d(P(None, tp)),
        "k": d(P(None, tp)),
        "v": d(P(None, tp)),
        "attn_out": d(P(tp, None)),
        "attn_ln": {"scale": P(None), "bias": P(None)},
        "ffn_in": d(P(None, tp)),
        "ffn_out": d(P(tp, None)),
        "ffn_ln": {"scale": P(None), "bias": P(None)},
    }
    # layer params are stacked with a leading scan axis -> prepend None
    layer = jax.tree_util.tree_map(lambda s: P(None, *s), layer,
                                   is_leaf=lambda x: isinstance(x, P))
    return {
        "embed": {
            "word": {"table": P(tp, None)},
            "position": {"table": P(None, None)},
            "token_type": {"table": P(None, None)},
            "ln": {"scale": P(None), "bias": P(None)},
        },
        "layers": layer,
        "pooler": d(P(None, tp)),
        "classifier": d(P(None, None)),
    }


def from_hf_state_dict(state: dict, cfg: BertConfig) -> dict:
    """Convert a HuggingFace ``BertForSequenceClassification`` state_dict
    (torch tensors — any dtype including bfloat16 — or numpy) into this
    model's param pytree."""

    def t(name, transpose=False):
        return cm.hf_tensor(state, name, transpose)

    def lin(prefix):
        return {"w": t(f"{prefix}.weight", transpose=True), "b": t(f"{prefix}.bias")}

    def ln(prefix):
        return {"scale": t(f"{prefix}.weight"), "bias": t(f"{prefix}.bias")}

    e = "bert.embeddings"
    layers = []
    for i in range(cfg.layers):
        p = f"bert.encoder.layer.{i}"
        layers.append(
            {
                "q": lin(f"{p}.attention.self.query"),
                "k": lin(f"{p}.attention.self.key"),
                "v": lin(f"{p}.attention.self.value"),
                "attn_out": lin(f"{p}.attention.output.dense"),
                "attn_ln": ln(f"{p}.attention.output.LayerNorm"),
                "ffn_in": lin(f"{p}.intermediate.dense"),
                "ffn_out": lin(f"{p}.output.dense"),
                "ffn_ln": ln(f"{p}.output.LayerNorm"),
            }
        )
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *layers)
    return {
        "embed": {
            "word": {"table": t(f"{e}.word_embeddings.weight")},
            "position": {"table": t(f"{e}.position_embeddings.weight")},
            "token_type": {"table": t(f"{e}.token_type_embeddings.weight")},
            "ln": ln(f"{e}.LayerNorm"),
        },
        "layers": stacked,
        "pooler": lin("bert.pooler.dense"),
        "classifier": lin("classifier"),
    }


register_model(
    ModelFamily(
        name="bert_classifier",
        make_config=BertConfig,
        init=init,
        apply=apply,
        input_spec=input_spec,
        param_specs=param_specs,
        extras={
            "from_hf_state_dict": from_hf_state_dict,
            "apply_packed": apply_packed,
            "packed_input_spec": packed_input_spec,
        },
    )
)
