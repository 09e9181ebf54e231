"""The one general traffic generator: a data file of parameters in, rows out.

A traffic mix is ``benchmark/traffic/<name>.json``. Its keys:

``kind``
    ``classify`` (rows are texts to classify) or ``generate`` (rows are
    prompts; each yields ``max_new_tokens`` output tokens).
``arrival``
    ``backlog`` — the input always has a batch of ``batch_rows`` rows ready
    and the stream pulls as fast as its back-pressure allows; or ``paced`` —
    open loop at ``rate_rows_per_s``, rows delivered in the batch that is due
    every ``tick_ms``, each stamped with the time it was due.
``lengths``
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
    in tokens as the hash tokenizer counts them (words + [CLS] + [SEP]), or
    ``{"dist": "uniform", "min": a, "max": b}``.
``pool_rows``
    how many distinct rows a run cycles through.
``shared_prefix_tokens`` (optional)
    every row starts with the same seeded preamble of that many tokens.
``burst_rows`` (optional, paced)
    arrivals come in bursts of that many rows.
``warmup_batches`` (optional)
    batches sent first, one at a time, each written before the next is read,
    so that every shape the mix can reach is compiled — through the path
    real rows take — before the window opens. Each entry is a list of token
    lengths, or ``{"rows": n, "tokens": t}`` for n rows of t tokens.
``order`` (optional)
    ``seeded`` (default): the seed permutes the multiset. ``fixed``: every
    seed offers the lengths in the same order and only the words (and the
    weights) differ — for mixes whose few, long rows make the order itself
    a large part of the work a window sees.
``stratify`` (optional)
    k: every k consecutive rows of the pool hold one row from each of the k
    bands of the sorted multiset, so that batches of k rows carry nearly
    equal work and a window that ends mid-pool has seen a fair sample.

Every seed gets the SAME multiset of lengths (the quantiles of the
distribution at ``pool_rows`` points) and, when paced, the same multiset of
gaps between arrivals (the quantiles of the exponential distribution); the
seed only permutes them and picks the words. So the work offered does not
vary with the seed.
"""

from __future__ import annotations

import json
import math
import os
from dataclasses import dataclass
from statistics import NormalDist
from typing import Optional

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: rows carry two columns besides the payload: which pool row this is (so
#: the outputs can be held to the reference) and when the row was due
ROW_ID = "bench_row"
DUE_NS = "bench_due_ns"

_SYLLABLES = ("ka lo mi ra tu ne so vi da pe xu zo be ti ga ru fe no ly wa "
              "qi ho cu je ma").split()


def load_traffic(name: str) -> dict:
    path = os.path.join(HERE, "traffic", name + ".json")
    with open(path) as f:
        spec = json.load(f)
    spec["name"] = name
    return spec


def lengths_multiset(spec: dict, n: int) -> np.ndarray:
    """``n`` token lengths, sorted: the quantiles of the distribution at the
    midpoints ``(i + 0.5) / n`` — a fixed multiset, whatever the seed."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(x)) for x in q])
        vals = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    elif spec["dist"] == "uniform":
        vals = lo + q * (hi - lo + 1)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(vals), lo, hi).astype(np.int64)


def gaps_multiset(rate: float, n: int) -> np.ndarray:
    """``n`` gaps between arrivals in seconds: the quantiles of the
    exponential distribution of mean ``1 / rate``, rescaled so that they sum
    to exactly ``n / rate`` — every seed offers the same rows in the same
    time, in another order."""
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)
    return gaps * (n / rate) / gaps.sum()


def vocabulary(size: int = 4096) -> list[str]:
    """A fixed list of pronounceable pseudo-words (no seed: the seed picks
    which words a row holds, not what the words are)."""
    words = []
    k = len(_SYLLABLES)
    i = 0
    while len(words) < size:
        a, b, c = i % k, (i // k) % k, (i // (k * k)) % k
        words.append(_SYLLABLES[a] + _SYLLABLES[b] + _SYLLABLES[c])
        i += 1
    return words


@dataclass
class Pool:
    """The rows of one run: payloads, their token lengths, and the pool
    doubled into Arrow arrays so that any window of up to ``n`` consecutive
    rows (wrapping) is one zero-copy slice."""

    texts: list[bytes]
    tokens: np.ndarray  # token length of each row, as the tokenizer counts
    #: row ids run from ``id_base``; the warm-up pool's are negative, so
    #: the comparison with the reference can tell its rows apart
    id_base: int = 0

    def __post_init__(self):
        import pyarrow as pa

        self.n = len(self.texts)
        self._payload = pa.array(self.texts + self.texts, pa.binary())
        ids = np.arange(self.n, dtype=np.int32) + np.int32(self.id_base)
        self._ids = pa.array(np.concatenate([ids, ids]))

    def window(self, start: int, rows: int, due_ns: np.ndarray):
        """``rows`` consecutive pool rows from ``start`` (wrapping) as a
        ``MessageBatch`` with the row-id and due-time columns."""
        import pyarrow as pa

        from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD, MessageBatch

        if rows > self.n:
            raise ValueError(f"window of {rows} rows from a pool of {self.n}")
        off = start % self.n
        rb = pa.RecordBatch.from_arrays(
            [self._payload.slice(off, rows), self._ids.slice(off, rows),
             pa.array(due_ns)],
            names=[DEFAULT_BINARY_VALUE_FIELD, ROW_ID, DUE_NS])
        return MessageBatch(rb)


def _texts(rng: np.random.Generator, tokens: np.ndarray, prefix: list[str],
           tag: str) -> list[bytes]:
    """One text per length: ``tokens - 2`` words ([CLS] and [SEP] are the
    tokenizer's), the first a row tag so that no two rows are equal, then
    the shared prefix (if any), then seeded words."""
    vocab = np.array(vocabulary())
    total = int(np.maximum(tokens - 2, 1).sum())
    draw = vocab[rng.integers(0, len(vocab), total)]
    out, pos = [], 0
    for i, t in enumerate(tokens):
        k = max(int(t) - 2, 1)
        words = [f"{tag}{i}", *prefix][:k]
        words += draw[pos:pos + k - len(words)].tolist()
        pos += k
        out.append(" ".join(words).encode())
    return out


def build_pool(spec: dict, seed: int, *, scale: float = 1.0) -> Pool:
    """The run's rows: the fixed multiset of lengths, permuted and given
    words by ``seed``. ``scale`` < 1 shrinks lengths for a CPU rehearsal."""
    n = int(spec["pool_rows"])
    tokens = lengths_multiset(spec["lengths"], n)
    if scale != 1.0:
        tokens = np.maximum((tokens * scale).astype(np.int64), 3)
    rng = np.random.default_rng([int(seed), 0xA11CE])
    order_rng = rng if spec.get("order", "seeded") == "seeded" \
        else np.random.default_rng(0xF17ED)
    k = int(spec.get("stratify", 1))
    if k > 1:
        if n % k:
            raise ValueError(f"pool_rows {n} is not a multiple of stratify {k}")
        # band j holds the j-th k-quantile of the sorted multiset; each band
        # is permuted by the seed, group i takes the i-th row of every band,
        # and the rows of a group are shuffled
        bands = [order_rng.permutation(band)
                 for band in tokens.reshape(k, n // k)]
        groups = np.stack(bands, axis=1)
        tokens = np.concatenate([order_rng.permutation(g) for g in groups])
    else:
        tokens = tokens[order_rng.permutation(n)]
    prefix: list[str] = []
    if spec.get("shared_prefix_tokens"):
        vocab = vocabulary()
        m = max(1, int(int(spec["shared_prefix_tokens"]) * scale))
        prefix = [vocab[j] for j in rng.integers(0, len(vocab), m)]
    return Pool(_texts(rng, tokens, prefix, "r"), tokens)


def warmup_pools(spec: dict, seed: int, *, scale: float = 1.0) -> list[Pool]:
    """One pool per entry of ``warmup_batches`` (row ids negative, so the
    comparison with the reference tells them apart); empty when the mix
    names none."""
    pools, base = [], 0
    for j, entry in enumerate(spec.get("warmup_batches") or []):
        if isinstance(entry, dict):
            entry = [int(entry["tokens"])] * int(entry["rows"])
        tokens = np.array(entry, np.int64)
        if scale != 1.0:
            tokens = np.maximum((tokens * scale).astype(np.int64), 3)
        rng = np.random.default_rng([int(seed), 0xB0075, j])
        base -= len(tokens)
        pools.append(Pool(_texts(rng, tokens, [], f"w{j}x"), tokens, id_base=base))
    return pools


def arrival_offsets(spec: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Seconds from the start of pacing at which each row is due, out to
    ``horizon_s``: the fixed multiset of gaps, permuted by ``seed`` and
    repeated. With ``burst_rows`` = b, b rows share each arrival and the
    gaps stretch by b, so the mean rate stays ``rate_rows_per_s``."""
    rate = float(spec["rate_rows_per_s"])
    burst = int(spec.get("burst_rows", 1))
    jitter = spec.get("jitter")
    n = int(spec["pool_rows"])
    rng = np.random.default_rng([int(seed), 0xC10C])
    if jitter is not None:
        # evenly spaced with a bounded seeded jitter: u in [-j, +j] gaps
        u = (np.arange(n) + 0.5) / n * 2 * float(jitter) - float(jitter)
        gaps = (1.0 + u) * burst / rate
    else:
        gaps = gaps_multiset(rate / burst, n)
    reps = max(1, math.ceil(horizon_s * rate / burst / n) + 1)
    offsets = np.concatenate(
        [gaps[rng.permutation(n)] for _ in range(reps)]).cumsum()
    offsets = offsets[offsets <= horizon_s]
    return np.repeat(offsets, burst) if burst > 1 else offsets
