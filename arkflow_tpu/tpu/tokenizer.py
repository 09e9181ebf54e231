"""Tokenization for streaming text models.

Prefers a real HuggingFace fast tokenizer when its files are cached locally
(this image has no network egress); otherwise falls back to a deterministic
hashing tokenizer so every pipeline stays hermetic. Throughput note: host-side
tokenization is the classic bottleneck ahead of the TPU (SURVEY.md section 7
hard part (d)) — the HF fast path releases the GIL and batches internally; the
fallback is vectorised regex + stable hashing.
"""

from __future__ import annotations

import re
from typing import Optional, Sequence

import numpy as np

from arkflow_tpu import native

_WORD = re.compile(rb"[a-z0-9]+|[^\sa-z0-9]")


def _fnv1a32(data: bytes) -> int:
    h = 2166136261
    for b in data:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


class HashTokenizer:
    """Deterministic hashing tokenizer: whitespace/punct split, stable ids.

    ids: 0=pad, 1=cls, 2=sep, 3=unk; tokens FNV-1a-hash into [4, vocab).
    Uses the native C++ batch kernel when available (identical semantics);
    the Python path is the reference implementation.
    """

    def __init__(self, vocab_size: int = 30522):
        self.vocab_size = vocab_size
        self.pad_id, self.cls_id, self.sep_id = 0, 1, 2
        self._cache: dict[bytes, int] = {}

    def _token_id(self, tok: bytes) -> int:
        tid = self._cache.get(tok)
        if tid is None:
            tid = 4 + _fnv1a32(tok) % (self.vocab_size - 4)
            if len(self._cache) < 1_000_000:
                self._cache[tok] = tid
        return tid

    def encode_batch(self, texts: Sequence[bytes], max_len: int) -> tuple[np.ndarray, np.ndarray]:
        raw = [t if isinstance(t, bytes) else t.encode() for t in texts]
        nat = native.hash_tokenize_batch(raw, max_len, self.vocab_size)
        if nat is not None:
            return nat
        return self._encode_rows(raw, max_len)

    def encode_batch_view(self, values: np.ndarray, offsets: np.ndarray,
                          max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """Tokenize straight off an Arrow payload view (``MessageBatch.
        payload_view``): the native kernel reads the values buffer in place —
        zero per-row Python objects on the fast path. The pure-Python
        fallback slices rows out of the buffer lazily."""
        nat = native.hash_tokenize_view(values, offsets, max_len, self.vocab_size)
        if nat is not None:
            return nat
        n = len(offsets) - 1
        base = int(offsets[0]) if n else 0
        buf = values[base : int(offsets[n]) if n else 0].tobytes()
        return self._encode_rows(
            [buf[offsets[i] - base : offsets[i + 1] - base] for i in range(n)],
            max_len)

    def _encode_rows(self, raw: Sequence[bytes], max_len: int) -> tuple[np.ndarray, np.ndarray]:
        n = len(raw)
        ids = np.zeros((n, max_len), np.int32)
        mask = np.zeros((n, max_len), np.int32)
        for i, t in enumerate(raw):
            toks = _WORD.findall(t.lower())
            row = [self.cls_id] + [self._token_id(tok) for tok in toks[: max_len - 2]] + [self.sep_id]
            ids[i, : len(row)] = row
            mask[i, : len(row)] = 1
        return ids, mask

    def decode(self, ids: Sequence[int]) -> str:
        """Hashing has no inverse vocabulary; render ids as text verbatim."""
        return " ".join(str(i) for i in ids)

    def decode_column(self, flat: np.ndarray, offsets: np.ndarray):
        """Vectorized decode of a ragged id column (flat values + offsets,
        the shape ``tpu_generate``'s flat gather produces): ids cast to
        their decimal strings and space-joined per row with two Arrow
        kernels — zero per-row Python. HF tokenizers have a real inverse
        vocabulary and decode row-wise instead (no ``decode_column``)."""
        import pyarrow as pa
        import pyarrow.compute as pc

        lst = pa.ListArray.from_arrays(
            pa.array(np.asarray(offsets, np.int32), pa.int32()),
            pc.cast(pa.array(np.asarray(flat)), pa.string()))
        return pc.binary_join(lst, " ")


class HFTokenizer:
    """transformers fast-tokenizer wrapper (local files only)."""

    def __init__(self, name: str):
        from transformers import AutoTokenizer

        self._tok = AutoTokenizer.from_pretrained(name, local_files_only=True, use_fast=True)

    def encode_batch(self, texts: Sequence[bytes], max_len: int) -> tuple[np.ndarray, np.ndarray]:
        decoded = [t.decode("utf-8", "replace") if isinstance(t, bytes) else t for t in texts]
        enc = self._tok(
            decoded, padding="max_length", truncation=True, max_length=max_len,
            return_tensors="np", return_attention_mask=True,
        )
        return enc["input_ids"].astype(np.int32), enc["attention_mask"].astype(np.int32)

    def encode_batch_view(self, values: np.ndarray, offsets: np.ndarray,
                          max_len: int) -> tuple[np.ndarray, np.ndarray]:
        """HF tokenizers want ``str`` rows; decode them off the buffer view
        (one big decode + string slicing beats per-row bytes round trips).
        Only the window the rows reference is materialized (sliced batches
        share a larger parent buffer)."""
        n = len(offsets) - 1
        base = int(offsets[0]) if n else 0
        buf = values[base : int(offsets[n]) if n else 0].tobytes()
        text = buf.decode("utf-8", "replace")
        # byte offsets only index the decoded str when every byte decoded to
        # one char (pure ASCII); otherwise decode per row
        if len(text) == len(buf):
            rows = [text[offsets[i] - base : offsets[i + 1] - base] for i in range(n)]
        else:
            rows = [buf[offsets[i] - base : offsets[i + 1] - base].decode("utf-8", "replace")
                    for i in range(n)]
        return self.encode_batch(rows, max_len)

    def decode(self, ids: Sequence[int]) -> str:
        return self._tok.decode(list(ids), skip_special_tokens=True)


class ByteTokenizer:
    """A byte-level vocabulary (``tokenizer: bytes``; EvaByte's layout): a
    UTF-8 byte ``b`` is id ``b + 64``, the 64 ids below it are specials (0 pad,
    1 bos, 2 eos; the rest unused here), 320 ids in all. Needs no file. A row
    is [bos] + its bytes, cut to ``max_len``; decoding drops the specials."""

    OFFSET = 64
    vocab_size = 256 + OFFSET

    def __init__(self):
        self.pad_id, self.cls_id, self.sep_id = 0, 1, 2

    def encode_batch(self, texts: Sequence[bytes], max_len: int) -> tuple[np.ndarray, np.ndarray]:
        ids = np.zeros((len(texts), max_len), np.int32)
        mask = np.zeros((len(texts), max_len), np.int32)
        for i, t in enumerate(texts):
            raw = np.frombuffer(t if isinstance(t, bytes) else t.encode(), np.uint8)
            n = min(len(raw), max_len - 1)
            ids[i, 0] = self.cls_id
            ids[i, 1:n + 1] = raw[:n].astype(np.int32) + self.OFFSET
            mask[i, :n + 1] = 1
        return ids, mask

    def encode_batch_view(self, values: np.ndarray, offsets: np.ndarray,
                          max_len: int) -> tuple[np.ndarray, np.ndarray]:
        buf = np.asarray(values, np.uint8)
        return self.encode_batch(
            [buf[offsets[i]:offsets[i + 1]].tobytes()
             for i in range(len(offsets) - 1)], max_len)

    def decode(self, ids: Sequence[int]) -> str:
        return bytes(i - self.OFFSET for i in ids
                     if i >= self.OFFSET).decode("utf-8", "replace")


def build_tokenizer(name: Optional[str], vocab_size: int = 30522):
    """``bytes``: the byte-level vocabulary (no file); else an HF tokenizer
    when cached locally; hashing fallback otherwise."""
    if name == "bytes":
        if vocab_size != ByteTokenizer.vocab_size:
            from arkflow_tpu.errors import ConfigError

            raise ConfigError(
                f"tokenizer: bytes has {ByteTokenizer.vocab_size} ids (256 "
                f"bytes + 64 specials); the model's vocab_size is {vocab_size}")
        return ByteTokenizer()
    if name:
        try:
            return HFTokenizer(name)
        except Exception:
            pass
    return HashTokenizer(vocab_size)
