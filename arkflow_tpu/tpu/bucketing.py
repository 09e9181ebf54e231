"""Shape bucketing: the bridge between ragged streams and XLA static shapes.

XLA compiles one executable per input shape. A streaming engine sees ragged
batch sizes and sequence lengths, so the runner pads every micro-batch up to a
small set of (batch, seq) buckets and keeps the compiled executable for each
bucket warm (SURVEY.md section 7 "hard parts" (a); the buffer layer owns
right-sizing, this module owns the bucket policy + padding).

Defaults are powers of two — each dimension at most doubles, so padding waste
is bounded by 50% and the executable count stays logarithmic.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Collection, Optional, Sequence

import numpy as np

from arkflow_tpu.errors import ConfigError

if TYPE_CHECKING:
    from arkflow_tpu.batch import MessageBatch
    from arkflow_tpu.components.base import Ack


def pow2_buckets(lo: int, hi: int) -> list[int]:
    out = []
    b = max(1, lo)
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return out


@dataclass(frozen=True)
class BucketPolicy:
    batch_buckets: tuple[int, ...] = tuple(pow2_buckets(8, 256))
    seq_buckets: tuple[int, ...] = tuple(pow2_buckets(32, 512))
    #: packed serving only: how far past the row grid the EXAMPLE-dim bucket
    #: grid extends (a packed row holds several examples, so a full row
    #: bucket of short texts carries ~seq/len(example) times more examples
    #: than rows). 1 keeps the example grid identical to the row grid.
    example_scale: int = 1

    @classmethod
    def from_config(cls, config: dict, *, max_batch: Optional[int] = None,
                    max_seq: Optional[int] = None,
                    default_example_scale: int = 1) -> "BucketPolicy":
        bb = config.get("batch_buckets")
        sb = config.get("seq_buckets")
        if bb is None:
            bb = pow2_buckets(8, max_batch or 256)
        if sb is None:
            sb = pow2_buckets(32, max_seq or 512)
        bb = tuple(sorted(int(x) for x in bb))
        sb = tuple(sorted(int(x) for x in sb))
        if not bb or not sb or bb[0] <= 0 or sb[0] <= 0:
            raise ConfigError("bucket lists must be non-empty positive ints")
        es = config.get("example_scale", default_example_scale)
        if not isinstance(es, int) or isinstance(es, bool) or es < 1:
            raise ConfigError(
                f"example_scale must be an int >= 1, got {es!r}")
        return cls(bb, sb, es)

    @staticmethod
    def _pick(n: int, buckets: Sequence[int]) -> int:
        for b in buckets:
            if n <= b:
                return b
        return buckets[-1]

    def batch_bucket(self, n: int) -> int:
        return self._pick(n, self.batch_buckets)

    def seq_bucket(self, n: int) -> int:
        return self._pick(n, self.seq_buckets)

    def max_batch(self) -> int:
        return self.batch_buckets[-1]

    # -- packed serving: example-dim grid + token-budget grid ---------------

    def example_buckets(self) -> tuple[int, ...]:
        """Bucket grid for the packed path's EXAMPLE dim: the row grid,
        pow2-extended up to ``max_batch * example_scale`` (and at least the
        top seq bucket, so one worst-case row of minimum-length examples
        always has a servable example bucket). Derived from the row grid on
        purpose: ``capped``/``dp_scaled`` rescale it automatically."""
        out = list(self.batch_buckets)
        top = self.batch_buckets[-1]
        want = max(top * self.example_scale, self.seq_buckets[-1]) \
            if self.example_scale > 1 else top
        while top < want:
            top *= 2
            out.append(top)
        return tuple(out)

    def example_bucket(self, n: int) -> int:
        return self._pick(n, self.example_buckets())

    def max_examples(self) -> int:
        return self.example_buckets()[-1]

    def token_buckets(self, seq: int) -> tuple[int, ...]:
        """Token-budget grid for packed serving at row width ``seq``: each
        batch bucket's row capacity in tokens (rows x seq). Composes with
        ``dp_scaled`` (batch buckets already carry the x dp) and ``capped``
        (OOM-dropped row buckets vanish from the token grid too)."""
        if seq < 1:
            raise ConfigError(f"token_buckets seq must be >= 1, got {seq}")
        return tuple(b * seq for b in self.batch_buckets)

    def token_budget(self, seq: int) -> int:
        """Tokens that fill the LARGEST compiled (rows, seq) shape — the
        natural emission target for a token-budget coalescer feeding
        ``pack_tokens``."""
        return self.token_buckets(seq)[-1]

    def capped(self, below: int) -> Optional["BucketPolicy"]:
        """OOM degradation: the grid with only batch buckets strictly below
        ``below`` (the bucket the device just failed to hold). ``None`` when
        no smaller bucket exists — the caller can't degrade further and must
        surface the failure instead."""
        smaller = tuple(b for b in self.batch_buckets if b < below)
        if not smaller:
            return None
        return BucketPolicy(smaller, self.seq_buckets, self.example_scale)

    def dp_scaled(self, dp: int) -> "BucketPolicy":
        """The policy for dp-sharded dispatch: every batch bucket times
        ``dp``, so each global bucket splits into per-chip shards that land
        EXACTLY on this policy's original grid (a [8,16,32] policy at dp=4
        compiles global buckets [32,64,128] = per-chip [8,16,32]). Scaling by
        multiplication — rather than rounding up to a multiple — keeps
        per-chip shapes bucket-exact and makes divisibility by dp structural
        rather than checked per dispatch."""
        if dp < 1:
            raise ConfigError(f"dp must be >= 1, got {dp}")
        if dp == 1:
            return self
        return BucketPolicy(tuple(b * dp for b in self.batch_buckets),
                            self.seq_buckets, self.example_scale)


#: Fixed charge per dispatched step in the length split's objective, in token
#: slots: the time of the smallest warm step over the per-slot slope of the
#: large ones. On a v5e, BERT-base in bfloat16 (chip run, PR 28): the 8 x 32
#: step takes 3.15 ms through ``infer_sync`` and steps of 256+ rows cost
#: 1.9 (seq 32) to 3.3 (seq 512) us a slot, 2.4 in the 1,024 x 512 step of a
#: mixed read: 3.15 ms / 2.4 us. The time of a 1,024-row read moves by under
#: 2 % for any charge from 256 to 2,048 slots.
STEP_CHARGE_SLOTS = 1300

#: The charge of a step whose program is not compiled yet. A cold compile of
#: one (rows, seq) program takes 1.8-3.4 s on the chip (PR 28), a million
#: slots of device time, charged here as if spread over 64 batches. It makes
#: a process that did not warm its grid settle on a few large programs, the
#: same ones whatever the read (log-normal reads of 1,024 rows name the same
#: five in 85 % of seeds, against 43 different sets in 60 seeds at the warm
#: charge), so a restart finds them in the compile cache; a finer program is
#: compiled later only where it saves this much in one batch.
COLD_STEP_CHARGE_SLOTS = 16384


def carve_by_length(lengths: Sequence[int], batch_buckets: Sequence[int],
                    seq_buckets: Sequence[int],
                    compiled: Optional[Collection[tuple[int, int]]] = None,
                    ) -> list[tuple[np.ndarray, int, int]]:
    """Carve one batch's rows by token length into sub-batches that each sit
    on a ``(batch_bucket, seq_bucket)`` pair of the grid, instead of padding
    every row to the longest one.

    Returns the pieces as ``(row indices, bb, sb)``: every row in exactly one
    piece, ``bb`` the batch bucket the runner pads ``len(indices)`` rows to,
    ``sb`` the seq bucket of the piece's longest row. Rows sorted by length,
    longest first, are cut into consecutive pieces that minimise

        sum(bb_i * sb_i + charge_i)

    with ``charge_i`` = ``STEP_CHARGE_SLOTS`` for a program in ``compiled``
    (None: the whole grid is) and ``COLD_STEP_CHARGE_SLOTS`` for one that
    would compile on first sight. Every piece but the last is exactly one
    batch bucket full (a shorter row riding in a longer piece's spare rows
    costs nothing, so row padding only ever pays at the shortest seq
    bucket), which also decomposes a row count that is off the batch grid
    (351 short rows -> 256 + 128, not 512) when that is cheaper. The charge keeps a trickle
    whole: 8 mixed rows split five ways would dispatch 8 x (32 + ... + 512)
    slots against 8 x 512 unsplit. The unsplit batch is one of the cuts
    weighed, so the result never costs more than it.

    A batch whose rows share one seq bucket is never cut: it comes back as
    the single ``(batch_bucket(n), sb)`` piece the runner pads it to anyway.
    """
    lens = np.asarray(lengths, dtype=np.int64)
    n = int(lens.shape[0])
    bbs = tuple(sorted(int(b) for b in batch_buckets))
    sbs = np.asarray(sorted(int(s) for s in seq_buckets), dtype=np.int64)
    if n == 0:
        return [(np.arange(0), bbs[0], int(sbs[0]))]
    order = np.argsort(-lens, kind="stable")
    # seq bucket of each row, longest first (over-long rows: the top bucket)
    row_sb = sbs[np.minimum(np.searchsorted(sbs, lens[order]), len(sbs) - 1)]
    if row_sb[0] == row_sb[-1]:
        return [(np.arange(n), BucketPolicy._pick(n, bbs), int(row_sb[0]))]
    # dynamic programming over the offsets full pieces can reach, from the
    # shortest rows back: cost[i] is the cheapest cut of rows i..n
    cost: dict[int, int] = {n: 0}
    take: dict[int, int] = {}
    for i in sorted(_reachable(n, bbs), reverse=True):
        sb = int(row_sb[i])
        for bb in bbs:
            # a piece that holds all that is left is the last one, padded to
            # its bucket; a larger bucket for it only pads more
            nxt = min(i + bb, n)
            if nxt in cost:
                warm = compiled is None or (bb, sb) in compiled
                c = (bb * sb + cost[nxt]
                     + (STEP_CHARGE_SLOTS if warm else COLD_STEP_CHARGE_SLOTS))
                if i not in cost or c < cost[i]:
                    cost[i], take[i] = c, bb
            if nxt == n:
                break
    pieces, i = [], 0
    while i < n:
        bb = take[i]
        pieces.append((order[i:i + bb], bb, int(row_sb[i])))
        i += bb
    return pieces


def _reachable(n: int, sizes: Sequence[int]) -> set[int]:
    """Offsets in ``[0, n)`` that sums of ``sizes`` reach from 0."""
    seen = {0}
    frontier = [0]
    while frontier:
        i = frontier.pop()
        for s in sizes:
            if i + s < n and i + s not in seen:
                seen.add(i + s)
                frontier.append(i + s)
    return seen


class BucketCapBus:
    """Process-wide fanout of device OOM bucket caps to live coalescers.

    The runner and the memory buffer's coalescer are independent components
    wired from different config sections; when the device proves it cannot
    hold a bucket (``RESOURCE_EXHAUSTED``), the runner caps its own grid AND
    announces the cap here so every registered coalescer stops merging
    emissions the device will just OOM on again. Process-global on purpose:
    one host serves one device topology, and a cap is a statement about the
    device, not about any single stream.

    Thread-tolerant: ``announce`` runs on runner executor threads while
    coalescers live on the event loop — ``cap()`` only shrinks a tuple and an
    int, both atomic reassignments, so the worst case is one more emission at
    the old target (which the runner then splits, not loses).
    """

    def __init__(self) -> None:
        import threading
        import weakref

        self._lock = threading.Lock()
        self._coalescers: "weakref.WeakSet[MicroBatchCoalescer]" = weakref.WeakSet()
        self._cap: Optional[int] = None
        #: shape listeners (memory buffers): objects with a
        #: ``retarget_shapes(batch_buckets, token_budget, deadline_s)``
        #: method — they own the coalesce deadline and the kwargs late
        #: tenant lanes are minted from, which no single coalescer can see
        self._listeners: "weakref.WeakSet" = weakref.WeakSet()

    @property
    def cap(self) -> Optional[int]:
        return self._cap

    def register(self, coalescer: "MicroBatchCoalescer") -> None:
        with self._lock:
            self._coalescers.add(coalescer)
            if self._cap is not None:
                coalescer.cap(self._cap)

    def register_listener(self, listener) -> None:
        """Register a buffer-level shape listener for future retargets.
        Unlike caps, committed retargets are NOT replayed onto late
        registrations: a cap is a device fact, a retarget is one stream's
        tuning preference — a component built later starts on its
        configured grid and follows from the tuner's next commit (the row
        grid a commit ``expect``-matches against never changes, so the next
        commit always reaches it)."""
        with self._lock:
            self._listeners.add(listener)

    def announce(self, cap: int) -> None:
        with self._lock:
            self._cap = cap if self._cap is None else min(self._cap, cap)
            for c in list(self._coalescers):
                c.cap(self._cap)

    def _clamped(self, buckets: tuple[int, ...],
                 token_budget: Optional[int]) -> tuple[tuple[int, ...], Optional[int]]:
        """An OOM cap always wins over a retarget: clamp the broadcast grid
        (and scale the budget like ``MicroBatchCoalescer.cap`` does) so a
        tuner commit can never re-grow buckets the device proved it cannot
        hold."""
        if self._cap is None or not buckets:
            return buckets, token_budget
        fitting = tuple(b for b in buckets if b <= self._cap)
        if not fitting:
            fitting = (max(1, int(self._cap)),)
        if token_budget is not None and fitting[-1] != buckets[-1]:
            token_budget = max(1, int(token_budget * fitting[-1] / buckets[-1]))
        return fitting, token_budget

    def clamp(self, batch_buckets: Sequence[int],
              token_budget: Optional[int] = None
              ) -> tuple[tuple[int, ...], Optional[int]]:
        """Apply the current OOM cap (if any) to a grid/budget pair —
        stream-bound retargets (which bypass the broadcast) clamp through
        here so a cap is honored no matter which path a flip takes."""
        with self._lock:
            return self._clamped(tuple(int(b) for b in batch_buckets),
                                 token_budget)

    def retarget(self, batch_buckets: Sequence[int], *,
                 token_budget: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 expect: Optional[Sequence[int]] = None) -> None:
        """Shape-tuner commit fanout: live coalescers whose CURRENT grid
        matches ``expect`` (None = all) adopt the new grid/budget, and
        buffer listeners additionally adopt the new coalesce deadline.
        Scoped by ``expect`` on purpose — the bus is process-global, and a
        retune of one stream's shapes must not disturb another stream's
        bucket-exactness. The OOM cap, when present, clamps the broadcast
        (a cap is a statement about the device; a retarget is merely a
        preference)."""
        bb = tuple(sorted(int(b) for b in batch_buckets))
        exp = tuple(sorted(int(b) for b in expect)) if expect is not None else None
        with self._lock:
            cb, ct = self._clamped(bb, token_budget)
            for c in list(self._coalescers):
                if exp is None or c.buckets == exp:
                    c.retarget(cb, ct)
            for listener in list(self._listeners):
                try:
                    listener.retarget_shapes(cb, ct, deadline_s, expect=exp)
                except Exception:
                    import logging

                    logging.getLogger("arkflow.tpu").exception(
                        "bucket retarget listener failed")

    def reset(self) -> None:
        """Test hook: forget the cap and any registrations (coalescers
        already shrunk/retargeted stay as they are)."""
        with self._lock:
            self._cap = None
            self._coalescers.clear()
            self._listeners.clear()


_GLOBAL_CAP_BUS = BucketCapBus()


def bucket_cap_bus() -> BucketCapBus:
    return _GLOBAL_CAP_BUS


class MicroBatchCoalescer:
    """Merges sub-bucket micro-batches into bucket-exact emissions.

    Streaming sources emit whatever batch size the broker delivered; padding
    each one to its compiled bucket alone wastes MXU cycles on zero rows
    (``arkflow_padding_waste_frac``). The coalescer holds written
    ``(batch, ack)`` pairs and carves emissions of EXACTLY the largest
    compiled batch bucket — splitting the batch that straddles the boundary
    and sharing its ack across the two emissions via ``split_ack`` — so
    steady-state device steps run at fill ratio 1.0. The caller (the memory
    buffer plugin) owns the deadline that bounds how long rows wait for a
    full bucket; ``pop_flush`` carves the remainder bucket-exact on
    deadline/close.

    Token-budget mode (``token_budget``): pending work is bucketed by TOTAL
    TOKEN COUNT instead of row count — per-row token estimates come from the
    payload column's Arrow offsets (``extract.payload_token_estimates``: one
    vectorized pass, no per-row Python), and emissions carve the row prefix
    whose token sum fills ``token_budget``. The budget is sized to fill a
    compiled ``(rows, seq)`` shape after ``pack_tokens`` packing
    (``BucketPolicy.token_budget(seq)``), so the packed row count lands
    bucket-exact where row-count carving would leave the packer starved or
    overflowing. Splits still happen on ROW boundaries (rows are atomic),
    with the same ``split_ack`` share semantics as row mode.

    At-least-once is preserved: every emission carries a composite ack over
    the source acks (or their split shares), so a quarantined merged batch
    acks exactly the source batches whose rows it contained, and a nacked
    one redelivers them.

    Poison isolation: the stream counts delivery attempts per MERGED batch
    fingerprint, so a poison source batch whose redeliveries kept regrouping
    with fresh traffic would mint a new fingerprint every round and nack-loop
    forever. The coalescer therefore watches its own emission acks — sources
    of a nacked emission are marked suspect, and a suspect batch re-arriving
    is emitted SOLO (stable fingerprint), so the stream's attempt budget
    converges and quarantine fires. A suspect that then succeeds is cleared.
    """

    #: bound on the suspect table; entries clear on ack, so this only
    #: matters with thousands of concurrently failing source batches
    MAX_SUSPECTS = 1024

    def __init__(self, batch_buckets: Sequence[int], *,
                 token_budget: Optional[int] = None,
                 token_field: Optional[str] = None,
                 token_bytes: Optional[float] = None,
                 max_row_tokens: Optional[int] = None):
        buckets = tuple(sorted(int(b) for b in batch_buckets))
        if not buckets or buckets[0] <= 0:
            raise ConfigError("coalesce batch_buckets must be non-empty positive ints")
        if token_budget is not None and token_budget < 1:
            raise ConfigError(
                f"coalesce token_budget must be a positive int, got {token_budget}")
        if token_bytes is not None and token_bytes <= 0:
            raise ConfigError(
                f"coalesce token_bytes must be positive, got {token_bytes}")
        if max_row_tokens is not None and max_row_tokens < 1:
            raise ConfigError(
                f"coalesce max_row_tokens must be >= 1, got {max_row_tokens}")
        self.buckets = buckets
        self.target = buckets[-1]
        #: token-budget mode: emissions carve this many estimated tokens
        #: instead of ``target`` rows (None = row mode)
        self.token_budget = int(token_budget) if token_budget is not None else None
        self._token_field = token_field
        self._token_bytes = token_bytes
        self._max_row_tokens = max_row_tokens
        #: held entries: (batch, ack, token-estimates, monotonic add time) —
        #: the add time of the oldest row consumed by a pop becomes
        #: ``last_pop_wait_s``, the coalescer-wait the trace layer records
        self._held: deque[tuple["MessageBatch", "Ack", Optional[np.ndarray], float]] = deque()
        #: suspect (previously-nacked) batches, emitted alone and first
        self._solo: deque[tuple["MessageBatch", "Ack", Optional[np.ndarray], float]] = deque()
        #: monotonic wait of the oldest row in the LAST popped emission
        self.last_pop_wait_s: float = 0.0
        #: fingerprint -> row count of each currently-suspect source batch
        self._suspects: dict[bytes, int] = {}
        #: cheap prefilter so healthy adds/acks skip hashing: row counts of
        #: current suspects (hash only on a row-count match)
        self._suspect_rows: set[int] = set()
        self._rows = 0
        self._tokens = 0

    @property
    def rows(self) -> int:
        return self._rows

    @property
    def tokens(self) -> int:
        """Estimated tokens held (token-budget mode; 0 in row mode)."""
        return self._tokens

    @property
    def pending(self) -> int:
        """Held entries — covers zero-row batches whose acks still await."""
        return len(self._held) + len(self._solo)

    def cap(self, max_bucket: int) -> None:
        """Shrink the target grid after a device OOM (see ``BucketCapBus``):
        drop buckets above ``max_bucket`` so future emissions stay within
        what the device can actually hold. If even the smallest bucket is
        above the cap, the cap itself becomes the only bucket. Already-held
        rows simply drain at the new, smaller target. Token-budget mode
        shrinks the token budget by the same ratio: the budget was sized to
        fill the old top (rows, seq) shape, and the device just proved it
        cannot hold that many rows."""
        fitting = tuple(b for b in self.buckets if b <= max_bucket)
        if not fitting:
            fitting = (max(1, int(max_bucket)),)
        if fitting == self.buckets:
            return
        if self.token_budget is not None:
            self.token_budget = max(
                1, int(self.token_budget * fitting[-1] / self.target))
        self.buckets = fitting
        self.target = fitting[-1]

    def retarget(self, batch_buckets: Sequence[int],
                 token_budget: Optional[int] = None) -> None:
        """Adopt a NEW target grid (shape-tuner flip; see ``BucketCapBus.
        retarget``). Unlike ``cap`` this may move buckets in either
        direction — the tuner only broadcasts after the runner's grid
        already flipped and every new shape is warm, so emissions carved at
        the new target land on compiled executables. Already-held rows
        simply drain at the new target. The token budget updates only when
        the coalescer is ALREADY in token mode (a mode flip would change
        emission semantics under the buffer's feet); ``None`` leaves the
        budget untouched."""
        buckets = tuple(sorted(int(b) for b in batch_buckets))
        if not buckets or buckets[0] <= 0:
            return
        self.buckets = buckets
        self.target = buckets[-1]
        if token_budget is not None and self.token_budget is not None:
            self.token_budget = max(1, int(token_budget))

    # -- token estimation (token-budget mode) -------------------------------

    def _row_tokens(self, batch: "MessageBatch") -> np.ndarray:
        """Per-row token estimates off the payload column's Arrow offsets
        (zero per-row Python; see ``extract.payload_token_estimates``).
        Batches without a usable payload column estimate conservatively —
        each row counts as ``max_row_tokens`` (or 1) — so malformed traffic
        still flows instead of wedging the budget accounting."""
        from arkflow_tpu.errors import ArkError
        from arkflow_tpu.tpu.extract import payload_token_estimates

        from arkflow_tpu.batch import DEFAULT_BINARY_VALUE_FIELD

        field = self._token_field or DEFAULT_BINARY_VALUE_FIELD
        try:
            col = batch.column(field)
            return payload_token_estimates(
                col, token_bytes=self._token_bytes,
                max_tokens=self._max_row_tokens)
        except ArkError:
            return np.full(batch.num_rows, self._max_row_tokens or 1,
                           dtype=np.int64)

    # -- suspect tracking (hashing only on failure paths, plus on adds/acks
    # -- that pass the row-count prefilter while failures are outstanding —
    # -- the all-healthy pipeline never serializes a batch) ----------------

    @staticmethod
    def _fingerprint(batch: "MessageBatch") -> bytes:
        """Shared with the stream's attempt budget (``batch_fingerprint``):
        solo-emission convergence requires the two to hash identically."""
        from arkflow_tpu.batch import batch_fingerprint

        return batch_fingerprint(batch)

    def _mark_suspect(self, batch: "MessageBatch") -> None:
        key = self._fingerprint(batch)
        if key not in self._suspects and len(self._suspects) >= self.MAX_SUSPECTS:
            self._suspects.pop(next(iter(self._suspects)))
        self._suspects[key] = batch.num_rows
        self._suspect_rows.add(batch.num_rows)

    def _clear_suspect(self, batch: "MessageBatch") -> None:
        if batch.num_rows not in self._suspect_rows:
            return  # prefilter: healthy acks never pay the hash either
        if self._suspects.pop(self._fingerprint(batch), None) is not None:
            self._suspect_rows = set(self._suspects.values())

    def _observed(self, batch: "MessageBatch", ack: "Ack") -> "Ack":
        """Wrap a source ack so emission outcomes feed the suspect table."""
        return _SuspectObserverAck(self, batch, ack)

    def add(self, batch: "MessageBatch", ack: "Ack") -> None:
        import time

        ack = self._observed(batch, ack)
        lens = self._row_tokens(batch) if self.token_budget is not None else None
        entry = (batch, ack, lens, time.monotonic())
        if (batch.num_rows in self._suspect_rows
                and self._fingerprint(batch) in self._suspects):
            self._solo.append(entry)
        else:
            self._held.append(entry)
        self._rows += batch.num_rows
        if lens is not None:
            self._tokens += int(lens.sum())

    def _note_wait(self, oldest_t_add: float) -> None:
        import time

        self.last_pop_wait_s = max(0.0, time.monotonic() - oldest_t_add)

    def _carve(self, rows: int) -> tuple["MessageBatch", "Ack"]:
        """Take exactly ``rows`` held rows as one merged emission, splitting
        the boundary batch (its source ack is shared across both emissions)."""
        from arkflow_tpu.batch import MessageBatch
        from arkflow_tpu.components.base import VecAck, split_ack

        parts: list["MessageBatch"] = []
        acks: list["Ack"] = []
        need = rows
        self._note_wait(self._held[0][3])
        while need > 0:
            batch, ack, _, t_add = self._held.popleft()
            if batch.num_rows <= need:
                parts.append(batch)
                acks.append(ack)
                need -= batch.num_rows
            else:
                head_ack, tail_ack = split_ack(ack, 2)
                parts.append(batch.slice(0, need))
                acks.append(head_ack)
                # the tail keeps its ORIGINAL add time: its rows have been
                # waiting since then, and the next pop's wait must say so
                self._held.appendleft((batch.slice(need), tail_ack, None, t_add))
                need = 0
        self._rows -= rows
        return MessageBatch.concat(parts), VecAck(acks)

    def _carve_tokens(self, budget: int) -> tuple["MessageBatch", "Ack"]:
        """Take the longest held row prefix whose estimated token sum fits
        ``budget``, splitting the boundary batch at a ROW edge (rows are
        atomic; the boundary source ack is shared via ``split_ack``). A
        single row whose estimate alone exceeds the budget emits solo —
        downstream packing/truncation owns over-long rows."""
        from arkflow_tpu.batch import MessageBatch
        from arkflow_tpu.components.base import VecAck, split_ack

        parts: list["MessageBatch"] = []
        acks: list["Ack"] = []
        took_rows = 0
        took_tokens = 0
        need = budget
        if self._held:
            self._note_wait(self._held[0][3])
        while need > 0 and self._held:
            batch, ack, lens, t_add = self._held[0]
            total = int(lens.sum())
            if total <= need:
                self._held.popleft()
                parts.append(batch)
                acks.append(ack)
                took_rows += batch.num_rows
                took_tokens += total
                need -= total
                continue
            # boundary batch: rows [0, k) fit the remaining budget
            cs = np.cumsum(lens)
            k = int(np.searchsorted(cs, need, side="right"))
            if k == 0:
                if parts:
                    break  # next row alone would overflow; emit under-budget
                k = 1  # a single over-budget row still has to flow
            if k >= batch.num_rows:
                # the whole batch fits after all (a single over-budget row):
                # take it intact — splitting would strand an empty tail and
                # its ack share in the queue
                self._held.popleft()
                parts.append(batch)
                acks.append(ack)
                took_rows += batch.num_rows
                took_tokens += total
                break
            self._held.popleft()
            head_ack, tail_ack = split_ack(ack, 2)
            parts.append(batch.slice(0, k))
            acks.append(head_ack)
            self._held.appendleft((batch.slice(k), tail_ack, lens[k:], t_add))
            took_rows += k
            took_tokens += int(cs[k - 1])
            break
        self._rows -= took_rows
        self._tokens -= took_tokens
        return MessageBatch.concat(parts), VecAck(acks)

    def _pop_solo(self) -> Optional[tuple["MessageBatch", "Ack"]]:
        if not self._solo:
            return None
        batch, ack, lens, t_add = self._solo.popleft()
        self._note_wait(t_add)
        self._rows -= batch.num_rows
        if lens is not None:
            self._tokens -= int(lens.sum())
        return batch, ack

    def pop_exact(self) -> Optional[tuple["MessageBatch", "Ack"]]:
        """Next emission: a suspect batch alone (stable fingerprint for the
        stream's attempt budget), else exactly ``target`` carved rows (row
        mode) / a ``token_budget``-filling row prefix (token mode)."""
        emission = self._pop_solo()
        if emission is not None:
            return emission
        if self.token_budget is not None:
            if self._tokens < self.token_budget:
                return None
            return self._carve_tokens(self.token_budget)
        if self._rows < self.target:
            return None
        return self._carve(self.target)

    def pop_flush(self) -> Optional[tuple["MessageBatch", "Ack"]]:
        """Deadline/close flush, one emission per call: carve the LARGEST
        bucket that the held rows fill exactly (so a 40-row flush against
        buckets [8,16,32] emits 32 then 8, zero padding), and only the
        sub-minimum remainder emits unpadded-to-bucket as one merged batch.
        Token mode: full-budget emissions first, then the whole remainder as
        one merged batch — the packer right-sizes its row count to a smaller
        bucket, so sub-budget flushes stay dense. Suspects drain through
        ``pop_exact`` first."""
        from arkflow_tpu.batch import MessageBatch
        from arkflow_tpu.components.base import VecAck

        emission = self.pop_exact()
        if emission is not None:
            return emission
        if not self._held:
            return None
        if self.token_budget is not None:
            self._note_wait(self._held[0][3])
            self._tokens = 0
            self._rows -= sum(b.num_rows for b, _, _, _ in self._held)
            parts = [b for b, _, _, _ in self._held]
            acks = VecAck([a for _, a, _, _ in self._held])
            self._held.clear()
            return MessageBatch.concat(parts), acks
        held_rows = self._rows
        fitting = [b for b in self.buckets if b <= held_rows]
        if fitting:
            return self._carve(fitting[-1])
        self._note_wait(self._held[0][3])
        parts = [b for b, _, _, _ in self._held]
        acks = VecAck([a for _, a, _, _ in self._held])
        self._held.clear()
        self._rows = 0
        return MessageBatch.concat(parts), acks


class _SuspectObserverAck:
    """Source-ack wrapper feeding emission outcomes back to the coalescer's
    suspect table: a nack marks the batch suspect (its redelivery emits
    solo), a final ack — delivered or quarantined — clears it."""

    __slots__ = ("_coalescer", "_batch", "_inner")

    def __init__(self, coalescer: MicroBatchCoalescer, batch: "MessageBatch",
                 inner: "Ack"):
        self._coalescer = coalescer
        self._batch = batch
        self._inner = inner

    @property
    def redeliverable(self) -> bool:
        return bool(getattr(self._inner, "redeliverable", False))

    async def ack(self) -> None:
        self._coalescer._clear_suspect(self._batch)
        await self._inner.ack()

    async def nack(self) -> None:
        # mark BEFORE the inner nack: the broker may requeue synchronously,
        # and the redelivered write must already see the suspicion
        self._coalescer._mark_suspect(self._batch)
        await self._inner.nack()


def pad_batch_dim(arr: np.ndarray, target: int) -> np.ndarray:
    """Pad axis 0 with zeros up to ``target`` rows."""
    n = arr.shape[0]
    if n == target:
        return arr
    if n > target:
        raise ValueError(f"batch {n} exceeds bucket {target}")
    pad = [(0, target - n)] + [(0, 0)] * (arr.ndim - 1)
    return np.pad(arr, pad)


def pad_seq_dim(arr: np.ndarray, target: int, axis: int = 1) -> np.ndarray:
    n = arr.shape[axis]
    if n == target:
        return arr
    if n > target:
        slicer = [slice(None)] * arr.ndim
        slicer[axis] = slice(0, target)
        return arr[tuple(slicer)]
    pad = [(0, 0)] * arr.ndim
    pad[axis] = (0, target - n)
    return np.pad(arr, pad)
