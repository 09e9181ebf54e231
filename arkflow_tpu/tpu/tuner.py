"""Traffic-adaptive shapes: the runtime tuner that closes the feedback loop.

Every performance-critical shape knob — the seq bucket grid, the coalescer's
``token_budget`` and ``deadline``, ``example_scale`` — ships as static YAML
chosen once against one synthetic workload, and the bench artifacts show the
cost: ~6% packed-fill headroom and padding waste over-weighted by tail
windows whenever live traffic drifts from the assumed length mix (ROADMAP
item 4). This module learns those knobs from the live signals the repo
already exports and reconfigures them ON THE FLY, without ever paying a
compile or a flap on the serving path:

1. **Observe.** The inference processor feeds every batch's true token
   lengths into a windowed :class:`WorkloadSketch` (ring buffer + arrival
   EWMA — the tokenized twin of the PR-6 ``payload_token_estimates`` pass);
   the runner's per-bucket dispatch counts, fill/waste histograms
   (``arkflow_padding_waste_frac``), and the overload controller's step
   EWMA + AIMD window ride along in the report.
2. **Propose.** :func:`plan_shapes` is a deterministic planner (no RL,
   seeded by nothing but the sketch): quantile-aligned seq bucket edges
   instead of blind pow2, a token budget sized by simulating the real
   first-fit packing against the observed length mix so packed fill p50
   targets ``target_fill``, a coalesce deadline sized from the arrival rate
   so the budget actually fills before the deadline flush, and an
   ``example_scale`` that keeps token-budget emissions example-servable.
   Proposals whose predicted waste does not beat the incumbent's by
   ``min_improvement`` — or that would mint more than ``max_compiles`` new
   executables — are rejected (hysteresis: a stable workload never flaps).
3. **Warm.** Every shape of the accepted grid precompiles OFF the serving
   path through the persistent XLA cache (``tpu/jaxcache.py``) via
   ``ModelRunner.warm_shapes`` — warmed shapes are marked seen, so the flip
   itself costs ZERO on-path recompiles (``arkflow_tpu_compiles_total``
   stays flat; warm-path compiles count in
   ``arkflow_tpu_warm_compiles_total`` instead).
4. **Flip.** The swap-unit machinery from the hot-swap layer is reused
   verbatim: each serving unit (a runner, or every pool member) retargets
   its grid atomically, runs one health-gated probe step on the NEW grid,
   and any probe failure rolls every unit back to the incumbent grid with
   nothing flushed. Only after every probe passes does the
   :class:`~arkflow_tpu.tpu.bucketing.BucketCapBus` broadcast retarget the
   live coalescers' grids/budgets/deadlines (the OOM-cap plumbing already
   proves coalescers can follow a live grid change), and a config epoch
   folds into the response cache via the commit hooks — a post-flip
   duplicate can never be answered with bytes produced under the old
   padding.

Ground (PAPERS.md): "Optimizing Inference Performance of Transformers on
CPUs" (bucket the shapes you actually observe) and "Flex-TPU: runtime
reconfigurable dataflow" (reconfigure what the chip runs per workload, not
per deployment).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, replace
from typing import Any, Callable, Mapping, Optional, Sequence

import numpy as np

from arkflow_tpu.errors import ConfigError, TunerError
from arkflow_tpu.obs import global_registry
from arkflow_tpu.tpu.bucketing import carve_by_length

logger = logging.getLogger("arkflow.tpu.tuner")

#: chaos fault kinds a test/soak may arm on a tuner (consumed by the next
#: cycle's probe step — the rollback path a sick device would take)
TUNER_FAULT_KINDS = ("probe_fail",)


# -- config ------------------------------------------------------------------


@dataclass(frozen=True)
class TunerConfig:
    """Knobs for the ``tuner:`` block on ``tpu_inference``."""

    enabled: bool = True
    #: seconds between autonomous observe->propose cycles (0 disables the
    #: background loop; ``POST /admin/tune`` still forces cycles)
    interval_s: float = 30.0
    #: predicted-waste margin a proposal must beat the incumbent by —
    #: the hysteresis that prevents flapping on a stable workload
    min_improvement: float = 0.02
    #: packed fill p50 the token budget is sized toward
    target_fill: float = 0.97
    #: seq bucket edges round up to a multiple of this (lane alignment)
    align: int = 8
    #: bound on proposed seq-grid size (incumbent top bucket always kept)
    max_seq_buckets: int = 4
    #: reject proposals that would mint more than this many new executables
    max_compiles: int = 64
    #: length samples required before a proposal is considered
    min_samples: int = 256
    #: sliding window of per-row token lengths the sketch retains
    window: int = 4096
    #: clamp on the derived coalesce deadline
    deadline_min_s: float = 0.01
    deadline_max_s: float = 1.0
    #: deadline = slack x predicted budget fill time (headroom so the budget
    #: genuinely fills before the deadline flush)
    deadline_slack: float = 1.25


_TUNER_KEYS = {
    "enabled", "interval", "min_improvement", "target_fill", "align",
    "max_seq_buckets", "max_compiles", "min_samples", "window",
    "deadline_min", "deadline_max", "deadline_slack",
}


def parse_tuner_config(cfg: Any, who: str = "tpu_inference") -> Optional[TunerConfig]:
    """Pure parse of a ``tuner:`` block (config.py runs this at --validate
    without building a tuner or importing jax). None = no tuner."""
    if cfg is None or cfg is False:
        return None
    if cfg is True:
        return TunerConfig()
    if not isinstance(cfg, Mapping):
        raise ConfigError(f"{who}.tuner must be a mapping or boolean, got {cfg!r}")
    unknown = set(cfg) - _TUNER_KEYS
    if unknown:
        raise ConfigError(
            f"{who}.tuner: unknown keys {sorted(unknown)} "
            f"(allowed: {sorted(_TUNER_KEYS)})")
    from arkflow_tpu.utils.duration import parse_duration

    out: dict[str, Any] = {}
    enabled = cfg.get("enabled", True)
    if not isinstance(enabled, bool):
        raise ConfigError(f"{who}.tuner.enabled must be a bool, got {enabled!r}")
    out["enabled"] = enabled

    def _dur(key: str, attr: str, *, allow_zero: bool = False) -> None:
        v = cfg.get(key)
        if v is None:
            return
        s = parse_duration(v)
        if s < 0 or (s == 0 and not allow_zero):
            raise ConfigError(f"{who}.tuner.{key} must be positive, got {v!r}")
        out[attr] = s

    _dur("interval", "interval_s", allow_zero=True)
    _dur("deadline_min", "deadline_min_s")
    _dur("deadline_max", "deadline_max_s")

    def _frac(key: str, attr: str, lo: float, hi: float) -> None:
        v = cfg.get(key)
        if v is None:
            return
        if isinstance(v, bool) or not isinstance(v, (int, float)) \
                or not (lo <= float(v) <= hi):
            raise ConfigError(
                f"{who}.tuner.{key} must be a number in [{lo}, {hi}], got {v!r}")
        out[attr] = float(v)

    _frac("min_improvement", "min_improvement", 0.0, 1.0)
    _frac("target_fill", "target_fill", 0.1, 1.0)

    def _int(key: str, attr: str, minimum: int) -> None:
        v = cfg.get(key)
        if v is None:
            return
        if isinstance(v, bool) or not isinstance(v, int) or v < minimum:
            raise ConfigError(
                f"{who}.tuner.{key} must be an int >= {minimum}, got {v!r}")
        out[attr] = v

    _int("align", "align", 1)
    _int("max_seq_buckets", "max_seq_buckets", 1)
    _int("max_compiles", "max_compiles", 1)
    _int("min_samples", "min_samples", 1)
    _int("window", "window", 8)
    slack = cfg.get("deadline_slack")
    if slack is not None:
        if isinstance(slack, bool) or not isinstance(slack, (int, float)) \
                or float(slack) < 1.0:
            raise ConfigError(
                f"{who}.tuner.deadline_slack must be a number >= 1, got {slack!r}")
        out["deadline_slack"] = float(slack)
    parsed = TunerConfig(**out)
    if parsed.deadline_min_s > parsed.deadline_max_s:
        raise ConfigError(
            f"{who}.tuner: deadline_min ({parsed.deadline_min_s}s) exceeds "
            f"deadline_max ({parsed.deadline_max_s}s)")
    return parsed


# -- the workload sketch -----------------------------------------------------


@dataclass(frozen=True)
class SketchView:
    """Immutable snapshot of the sketch — the planner's ONLY input, so a
    saved view replays to an identical proposal (determinism tests pin
    this)."""

    #: per-row token lengths, arrival order (the window's worth)
    lengths: np.ndarray
    #: EWMA of offered rows per second (0.0 = unknown/idle)
    arrival_rows_per_sec: float
    #: rows observed since the sketch was created (not just the window)
    rows_seen: int

    @property
    def n(self) -> int:
        return int(self.lengths.size)

    def quantile(self, q: float) -> float:
        if not self.lengths.size:
            return 0.0
        return float(np.quantile(self.lengths, q))

    @property
    def mean_len(self) -> float:
        return float(self.lengths.mean()) if self.lengths.size else 0.0


class WorkloadSketch:
    """Windowed workload observation: a ring buffer of recent per-row token
    lengths plus an arrival-rate EWMA. ``observe`` runs on the serving path
    (processor threads AND the event loop), so it is O(rows) numpy under a
    small lock; everything analytical happens on :meth:`snapshot` copies,
    off-path."""

    def __init__(self, window: int = 4096,
                 clock: Callable[[], float] = time.monotonic):
        self._window = max(8, int(window))
        self._buf = np.zeros(self._window, np.int64)
        self._pos = 0
        self._filled = 0
        self._rows_seen = 0
        self._rate_ewma = 0.0
        self._last_t: Optional[float] = None
        self._clock = clock
        self._lock = threading.Lock()

    def observe(self, lengths: np.ndarray) -> None:
        lengths = np.asarray(lengths, np.int64).reshape(-1)
        if lengths.size == 0:
            return
        now = self._clock()
        with self._lock:
            n = min(lengths.size, self._window)
            take = lengths[-n:]
            end = self._pos + n
            if end <= self._window:
                self._buf[self._pos:end] = take
            else:
                split = self._window - self._pos
                self._buf[self._pos:] = take[:split]
                self._buf[:end - self._window] = take[split:]
            self._pos = end % self._window
            self._filled = min(self._window, self._filled + n)
            self._rows_seen += int(lengths.size)
            if self._last_t is not None:
                dt = now - self._last_t
                if dt > 1e-6:
                    sample = lengths.size / dt
                    self._rate_ewma += 0.2 * (sample - self._rate_ewma)
            self._last_t = now

    def snapshot(self) -> SketchView:
        with self._lock:
            if self._filled < self._window:
                lengths = self._buf[:self._filled].copy()
            else:
                # unroll the ring into arrival order
                lengths = np.concatenate(
                    [self._buf[self._pos:], self._buf[:self._pos]])
            return SketchView(lengths=lengths,
                              arrival_rows_per_sec=self._rate_ewma,
                              rows_seen=self._rows_seen)


# -- shapes ------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    """One complete shape configuration — the unit proposals and rollbacks
    move around in."""

    batch_buckets: tuple[int, ...]
    seq_buckets: tuple[int, ...]
    example_scale: int = 1
    packed: bool = False
    #: coalescer token budget (packed serving); None = row-mode coalescing
    token_budget: Optional[int] = None
    #: coalesce deadline; None = leave the buffer's configured deadline
    deadline_s: Optional[float] = None

    def to_policy(self):
        from arkflow_tpu.tpu.bucketing import BucketPolicy

        return BucketPolicy(self.batch_buckets, self.seq_buckets,
                            self.example_scale)

    def report(self) -> dict:
        out = {"batch_buckets": list(self.batch_buckets),
               "seq_buckets": list(self.seq_buckets),
               "example_scale": self.example_scale}
        if self.token_budget is not None:
            out["token_budget"] = self.token_budget
        if self.deadline_s is not None:
            out["deadline_ms"] = round(self.deadline_s * 1000.0, 3)
        return out


@dataclass(frozen=True)
class Proposal:
    shape: ShapeConfig
    predicted_waste: float
    predicted_fill: float
    incumbent_waste: float
    #: incumbent_waste - predicted_waste (the hysteresis margin input)
    improvement: float
    notes: tuple[str, ...] = ()

    def report(self) -> dict:
        return {"shape": self.shape.report(),
                "predicted_waste": round(self.predicted_waste, 4),
                "predicted_fill": round(self.predicted_fill, 4),
                "incumbent_predicted_waste": round(self.incumbent_waste, 4),
                "improvement": round(self.improvement, 4),
                **({"notes": list(self.notes)} if self.notes else {})}


# -- the deterministic planner ----------------------------------------------


def _pick(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def _align_up(n: float, align: int) -> int:
    n = max(1, int(np.ceil(n)))
    return ((n + align - 1) // align) * align


def quantile_aligned_edges(lengths: np.ndarray, top: int, *, align: int,
                           qs: Sequence[float]) -> tuple[int, ...]:
    """Seq bucket edges aligned to the OBSERVED length distribution: one
    ``align``-rounded edge per requested quantile, deduped, clamped to
    ``top`` — which is always kept as the final bucket (the configured top
    bucket is the truncation contract; the tuner only re-cuts the interior
    edges)."""
    edges: list[int] = []
    for q in qs:
        e = _align_up(float(np.quantile(lengths, q)), align)
        if align <= e < top and e not in edges:
            edges.append(e)
    return tuple(sorted(edges) + [top])


def _ffd_rows(lengths: np.ndarray, seq: int) -> int:
    """First-fit-decreasing bin count — the planner's twin of
    ``pack_tokens``'s binning (same order, same fit rule), so predicted row
    counts match what the packer will actually produce."""
    ls = np.minimum(np.maximum(np.asarray(lengths, np.int64), 1), seq)
    if ls.size == 0:
        return 0
    order = np.sort(ls)[::-1]
    bin_free = np.empty(ls.size, np.int64)
    n_bins = 0
    for length in order:
        fits = bin_free[:n_bins] >= length
        if n_bins and fits.any():
            b = int(np.argmax(fits))
        else:
            b = n_bins
            n_bins += 1
            bin_free[b] = seq
        bin_free[b] -= length
    return n_bins


def _emission_slices(lengths: np.ndarray, budget: int) -> list[np.ndarray]:
    """Split the sample (arrival order) into consecutive token-budget
    emissions, rows atomic — mirrors ``MicroBatchCoalescer._carve_tokens``'s
    carving discipline (a single over-budget row still flows solo)."""
    out: list[np.ndarray] = []
    cs = np.cumsum(lengths)
    start = 0
    base = 0
    while start < lengths.size:
        k = int(np.searchsorted(cs, base + budget, side="right"))
        if k <= start:
            k = start + 1
        out.append(lengths[start:k])
        base = float(cs[k - 1])
        start = k
    return out


def predict_waste(view: SketchView, shape: ShapeConfig) -> tuple[float, float]:
    """(capacity-weighted padding waste, fill) the workload in ``view``
    would pay under ``shape`` — the ONE evaluator both the incumbent and
    every proposal are scored with, so the hysteresis margin compares
    apples to apples. Deterministic in (view, shape)."""
    lengths = view.lengths
    if lengths.size == 0:
        return 0.0, 1.0
    true = 0.0
    cap = 0.0
    if shape.packed:
        budget = shape.token_budget
        if budget is None:
            budget = shape.batch_buckets[-1] * shape.seq_buckets[-1]
        for em in _emission_slices(lengths, budget):
            sb = _pick(int(em.max()), shape.seq_buckets)
            ls = np.minimum(em, sb)
            rows = _ffd_rows(ls, sb)
            top = shape.batch_buckets[-1]
            # over-top emissions carve into top-bucket windows cascading
            # down the grid (carve_row_windows); model the pad-up per chunk
            while rows > top:
                cap += top * sb
                rows -= top
            cap += _pick(rows, shape.batch_buckets) * sb
            true += float(ls.sum())
    else:
        # coalesced steady state: bucket-exact emissions of the top row
        # bucket, each carved by length across the grid exactly as the
        # processor serves it (one piece when its rows share a seq bucket)
        rows_per = shape.batch_buckets[-1]
        for start in range(0, lengths.size, rows_per):
            em = lengths[start:start + rows_per]
            cap += sum(bb * sb for _, bb, sb in carve_by_length(
                em, shape.batch_buckets, shape.seq_buckets))
            true += float(np.minimum(em, shape.seq_buckets[-1]).sum())
    if cap <= 0:
        return 0.0, 1.0
    fill = true / cap
    return 1.0 - fill, fill


def plan_shapes(view: SketchView, incumbent: ShapeConfig,
                cfg: TunerConfig) -> Proposal:
    """Deterministic shape proposal for the observed workload.

    Candidate seq grids are generated from quantile-aligned edges (several
    quantile sets, so skewed AND bimodal mixes both get a grid that hugs
    their modes), the packed token budget comes from simulating the real
    first-fit packing at the candidate grid, the deadline from the arrival
    rate, and the winner is whichever candidate the shared
    :func:`predict_waste` evaluator scores best. Pure function of
    ``(view, incumbent, cfg)`` — same inputs, same proposal, always."""
    if view.n == 0:
        return Proposal(shape=incumbent, predicted_waste=0.0,
                        predicted_fill=1.0, incumbent_waste=0.0,
                        improvement=0.0, notes=("empty sketch",))
    lengths = view.lengths
    top_seq = incumbent.seq_buckets[-1]
    row_buckets = incumbent.batch_buckets  # the row grid is a capacity
    # contract (backpressure bound, OOM caps); the tuner re-cuts seq edges,
    # budget, deadline and example_scale around it
    inc_waste, _ = predict_waste(view, incumbent)

    # candidate seq grids: quantile-edge sets (interior edges; top kept).
    # Several sets on purpose: skewed mixes want mid/high quantiles,
    # 50/50 bimodal mixes want a LOW quantile hugging the short mode (the
    # median falls between modes and helps neither) — the shared evaluator
    # below picks whichever grid the observed mix actually scores best on
    candidate_grids: list[tuple[int, ...]] = []
    for qs in ((0.5, 0.9), (0.75,), (0.5, 0.75, 0.95), (0.9,),
               (0.25, 0.5, 0.9), (0.45, 0.9), ()):
        grid = quantile_aligned_edges(lengths, top_seq, align=cfg.align,
                                      qs=qs[:max(0, cfg.max_seq_buckets - 1)])
        if grid not in candidate_grids:
            candidate_grids.append(grid)

    notes: list[str] = []
    best: Optional[tuple[float, float, ShapeConfig]] = None
    for grid in candidate_grids:
        if incumbent.packed:
            for shape in _packed_candidates(view, incumbent, grid, cfg):
                waste, fill = predict_waste(view, shape)
                if best is None or waste < best[0] - 1e-12:
                    best = (waste, fill, shape)
        else:
            shape = replace(incumbent, seq_buckets=grid, deadline_s=None)
            waste, fill = predict_waste(view, shape)
            if best is None or waste < best[0] - 1e-12:
                best = (waste, fill, shape)
    assert best is not None
    waste, fill, shape = best

    # deadline: size from the arrival rate so the emission target actually
    # fills before the deadline flush (no rate observed -> leave configured)
    rate = view.arrival_rows_per_sec
    if rate > 0:
        if shape.packed and shape.token_budget:
            fill_time = shape.token_budget / max(rate * max(view.mean_len, 1.0), 1e-6)
        else:
            fill_time = row_buckets[-1] / max(rate, 1e-6)
        deadline = min(max(cfg.deadline_slack * fill_time,
                           cfg.deadline_min_s), cfg.deadline_max_s)
        shape = replace(shape, deadline_s=deadline)
    else:
        notes.append("no arrival rate observed; deadline left as configured")

    return Proposal(shape=shape, predicted_waste=waste, predicted_fill=fill,
                    incumbent_waste=inc_waste,
                    improvement=inc_waste - waste, notes=tuple(notes))


def _packed_candidates(view: SketchView, incumbent: ShapeConfig,
                       grid: tuple[int, ...],
                       cfg: TunerConfig) -> list[ShapeConfig]:
    """Token-budget + example_scale candidates for one seq grid: the budget
    that fills the top (rows, seq) shape at the SIMULATED packing
    efficiency of the observed mix, plus small perturbations (the simulator
    scores them; the best survives)."""
    lengths = view.lengths
    top_rows = incumbent.batch_buckets[-1]
    sb_hat = _pick(int(np.quantile(lengths, 0.99)), grid)
    rows_all = _ffd_rows(lengths, sb_hat)
    eta = (float(np.minimum(lengths, sb_hat).sum()) / (rows_all * sb_hat)
           if rows_all else 1.0)
    base = max(sb_hat, int(top_rows * sb_hat * min(eta, cfg.target_fill + 0.03)))
    out: list[ShapeConfig] = []
    for scale in (1.0, 0.95, 1.05):
        budget = max(sb_hat, int(base * scale))
        # example grid must cover a budget emission's example count: es is
        # the pow2 extension of the row grid that reaches it
        mean_len = max(view.mean_len, 1.0)
        examples = int(np.ceil(budget / mean_len))
        es = 1
        while top_rows * es < examples and es < 64:
            es *= 2
        out.append(replace(incumbent, seq_buckets=grid, token_budget=budget,
                           example_scale=es, deadline_s=None))
    return out


# -- the manager -------------------------------------------------------------


class ShapeTuner:
    """Closes the observe -> propose -> warm -> flip loop for one serving
    processor, entirely off the serving path.

    The serving path's only contributions are O(rows) sketch observations;
    planning, warming (compiles) and probing all run in cycle tasks on
    executor threads. The flip reuses the hot-swap layer's unit discipline:
    every ``swap_units()`` member retargets and probes individually, and a
    failed probe rolls every flipped unit back to the incumbent grid with
    nothing flushed and the old shapes serving throughout.
    """

    def __init__(self, runner, *, model: str, cfg: Optional[TunerConfig] = None,
                 packed: bool = False, bus=None):
        from arkflow_tpu.tpu.bucketing import bucket_cap_bus

        self.runner = runner
        self.cfg = cfg or TunerConfig()
        self.packed = packed
        self.sketch = WorkloadSketch(self.cfg.window)
        self._bus = bus if bus is not None else bucket_cap_bus()
        self._controller = None
        self._commit_hooks: list[Callable[[], None]] = []
        #: stream-bound retarget listeners (the stream wires its OWN buffer
        #: here at build): when any are bound, commits notify exactly them
        #: and never touch the process-global bus — two streams with
        #: coincidentally-equal grids can each tune without disturbing the
        #: other. The bus broadcast remains the fallback for unbound use.
        self._bound_listeners: list[Any] = []
        self._chaos: deque[str] = deque()
        self._lock = asyncio.Lock()
        self._task: Optional[asyncio.Task] = None
        self.epoch = 0
        self._incumbent = self._shape_from_runner()
        self._last_decision: Optional[dict] = None
        self._last_error: Optional[str] = None

        reg = global_registry()
        labels = {"model": model}
        self.m_epoch = reg.gauge(
            "arkflow_tuner_epoch",
            "shape-config epoch (increments on each committed retune)", labels)
        self.m_epoch.set(0)
        self.m_predicted_waste = reg.gauge(
            "arkflow_tuner_predicted_waste",
            "planner-predicted capacity-weighted padding waste of the "
            "CURRENTLY-SERVING shape config against the live sketch", labels)
        self.m_proposals = reg.counter(
            "arkflow_tuner_proposals_total", "tuner proposals planned", labels)
        self.m_commits = reg.counter(
            "arkflow_tuner_commits_total", "tuner proposals committed", labels)
        self.m_rollbacks = reg.counter(
            "arkflow_tuner_rollbacks_total",
            "tuner flips rolled back (probe failure) with the incumbent "
            "grid serving throughout", labels)
        self.m_rejected = reg.counter(
            "arkflow_tuner_rejected_total",
            "tuner proposals rejected by hysteresis/compile gates", labels)

    # -- wiring ------------------------------------------------------------

    def _shape_from_runner(self) -> ShapeConfig:
        b = self.runner.buckets
        return ShapeConfig(
            batch_buckets=tuple(b.batch_buckets),
            seq_buckets=tuple(b.seq_buckets),
            example_scale=b.example_scale,
            packed=self.packed,
            token_budget=(b.token_budget(b.seq_buckets[-1])
                          if self.packed else None))

    def attach_overload_controller(self, controller) -> None:
        """Stream hook: the controller's step EWMA + AIMD window join the
        sketch report (and /health)."""
        self._controller = controller

    def bind_listener(self, listener) -> None:
        """Stream hook: bind a shape listener (the stream's own buffer) so
        commits retarget exactly this stream's coalescers — never another
        stream's that merely shares a grid."""
        if listener not in self._bound_listeners:
            self._bound_listeners.append(listener)

    def add_commit_hook(self, hook: Callable[[], None]) -> None:
        """Run after every COMMITTED flip (never on rejection/rollback):
        the response cache's epoch bump registers here, so a duplicate
        arriving after a shape flip recomputes instead of returning bytes
        produced under the old padding."""
        self._commit_hooks.append(hook)

    def inject_fault(self, kind: str) -> None:
        """Arm a one-shot chaos fault consumed by the NEXT cycle's probe
        (``probe_fail``): the flip must roll back to the incumbent grid."""
        if kind not in TUNER_FAULT_KINDS:
            raise ConfigError(
                f"unknown tuner fault kind {kind!r} ({'/'.join(TUNER_FAULT_KINDS)})")
        self._chaos.append(kind)

    def observe(self, lengths) -> None:
        """Serving-path feed: one batch's per-row token lengths."""
        self.sketch.observe(np.asarray(lengths))

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        """Start the background cycle loop on the running event loop."""
        if self._task is not None or self.cfg.interval_s <= 0:
            return
        self._task = asyncio.get_running_loop().create_task(self._run_loop())

    async def stop(self) -> None:
        task, self._task = self._task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except (asyncio.CancelledError, Exception):
                pass

    async def _run_loop(self) -> None:
        while True:
            await asyncio.sleep(self.cfg.interval_s)
            try:
                await self.run_cycle()
            except asyncio.CancelledError:
                raise
            except TunerError:
                pass  # rolled back; the decision/report carries the story
            except Exception:
                logger.exception("tuner cycle failed")

    # -- the cycle ---------------------------------------------------------

    async def run_cycle(self, force: bool = False) -> dict:
        """One observe->propose->warm->flip cycle. Returns the decision
        report; raises :class:`TunerError` when a probe failure rolled the
        flip back (the incumbent grid serving throughout). ``force``
        (``POST /admin/tune``) skips the sample-count gate down to a
        handful of rows — the hysteresis margin still applies, so a forced
        cycle on a stable workload is a no-op, not a flap."""
        async with self._lock:
            return await self._cycle_locked(force)

    async def _cycle_locked(self, force: bool) -> dict:
        loop = asyncio.get_running_loop()
        live_bb = tuple(self.runner.buckets.batch_buckets)
        if live_bb != self._incumbent.batch_buckets:
            # an OOM cap shrank the row grid under us: adopt it — the row
            # grid is a device FACT the planner must carry forward, or the
            # next flip would resurrect the exact buckets the device just
            # proved it cannot hold
            self._incumbent = replace(self._incumbent, batch_buckets=live_bb)
        view = self.sketch.snapshot()
        need = 8 if force else self.cfg.min_samples
        if view.n < need:
            decision = {"action": "skipped",
                        "reason": f"insufficient samples ({view.n} < {need})"}
            self._last_decision = decision
            return self._decision_report(decision)

        # planning simulates the real packing against the whole window —
        # tens of ms at full window — so it runs off the event loop like
        # every other tuner stage (the serving path only ever pays the
        # O(rows) sketch insert)
        proposal = await loop.run_in_executor(
            None, plan_shapes, view, self._incumbent, self.cfg)
        self.m_proposals.inc()
        # keep the serving-shape prediction gauge fresh even on rejection:
        # predicted-vs-measured waste is the tuner's honesty metric
        self.m_predicted_waste.set(proposal.incumbent_waste)

        if self._grids_equal(proposal.shape, self._incumbent):
            decision = {"action": "rejected", "reason": "proposal equals incumbent",
                        "proposal": proposal.report()}
            self.m_rejected.inc()
            self._last_decision = decision
            return self._decision_report(decision)
        if proposal.improvement < self.cfg.min_improvement:
            decision = {"action": "rejected",
                        "reason": (f"improvement {proposal.improvement:.4f} < "
                                   f"min_improvement {self.cfg.min_improvement}"),
                        "proposal": proposal.report()}
            self.m_rejected.inc()
            self._last_decision = decision
            return self._decision_report(decision)

        policy = proposal.shape.to_policy()
        # member 0's count is the honest cost for pools too: the other
        # members replay its compiles from the persistent cache
        n_new = self.runner.count_new_shapes(policy)
        if n_new > self.cfg.max_compiles:
            decision = {"action": "rejected",
                        "reason": (f"{n_new} new executables > max_compiles "
                                   f"{self.cfg.max_compiles}"),
                        "proposal": proposal.report()}
            self.m_rejected.inc()
            self._last_decision = decision
            return self._decision_report(decision)

        # warm: every new shape compiles OFF the serving path through the
        # persistent cache — each compile holds the in-flight permit (no
        # interleaving with live device schedules) and runs under the
        # first-compile watchdog, so a wedged compile aborts the cycle
        # instead of holding the tuner lock forever. Nothing has flipped
        # yet, so a warm failure needs no rollback.
        try:
            warmed = await self.runner.warm_shapes_live(policy)
        except Exception as e:
            decision = {"action": "warm_failed", "error": str(e),
                        "proposal": proposal.report()}
            self._last_decision = decision
            self._last_error = str(e)
            raise TunerError(
                f"shape warm failed before any flip: {e}; incumbent grid "
                "still serving") from e

        # flip + probe, one unit at a time; roll every flipped unit back on
        # any probe failure (the swap-unit discipline, reused verbatim)
        flipped: list[tuple[Any, Any]] = []
        try:
            for _label, member in self.runner.swap_units():
                old_policy = member.retarget_buckets(policy)
                flipped.append((member, old_policy))
                await self._probe(member, policy)
        except Exception as e:
            for member, old_policy in reversed(flipped):
                try:
                    member.retarget_buckets(old_policy)
                except Exception:
                    logger.exception("tuner rollback retarget failed")
            self.m_rollbacks.inc()
            decision = {"action": "rolled_back", "error": str(e),
                        "proposal": proposal.report()}
            self._last_decision = decision
            self._last_error = str(e)
            raise TunerError(
                f"shape flip rolled back at probe: {e}; incumbent grid "
                "still serving") from e

        # commit: only now do live coalescers retarget (a rollback must
        # flush/retarget nothing), and the config epoch folds into caches.
        # With stream-bound listeners the notification goes to exactly this
        # stream's buffer(s) — never across streams; the process-global bus
        # broadcast is the fallback for unbound (test/tool) tuners. Either
        # path clamps under any announced OOM cap.
        if self._bound_listeners:
            bb, tb = self._bus.clamp(proposal.shape.batch_buckets,
                                     proposal.shape.token_budget)
            for listener in self._bound_listeners:
                try:
                    applied = listener.retarget_shapes(
                        bb, tb, proposal.shape.deadline_s,
                        expect=self._incumbent.batch_buckets)
                    if applied is False:
                        # grid mismatch on the stream's OWN buffer is a
                        # misconfiguration (e.g. coalesce.dp not matching
                        # mesh dp) — say so instead of silently shipping
                        # half a commit
                        logger.warning(
                            "[tuner] commit did not retarget the stream's "
                            "coalescer: its grid does not match the "
                            "incumbent %s (check buffer.coalesce matches "
                            "the runner's grid, incl. dp scaling)",
                            self._incumbent.batch_buckets)
                except Exception:
                    logger.exception("tuner bound-listener retarget failed")
        else:
            self._bus.retarget(
                proposal.shape.batch_buckets,
                token_budget=proposal.shape.token_budget,
                deadline_s=proposal.shape.deadline_s,
                expect=self._incumbent.batch_buckets)
        self._incumbent = proposal.shape
        self.epoch += 1
        self.m_epoch.set(self.epoch)
        self.m_commits.inc()
        self.m_predicted_waste.set(proposal.predicted_waste)
        self._last_error = None
        for hook in self._commit_hooks:
            try:
                hook()
            except Exception:
                logger.exception("tuner commit hook failed")
        decision = {"action": "committed", "epoch": self.epoch,
                    "warmed_shapes": warmed, "new_shapes": n_new,
                    "proposal": proposal.report()}
        self._last_decision = decision
        logger.info("[tuner] committed shape epoch %d: %s", self.epoch,
                    proposal.shape.report())
        return self._decision_report(decision)

    @staticmethod
    def _grids_equal(a: ShapeConfig, b: ShapeConfig) -> bool:
        return (a.batch_buckets == b.batch_buckets
                and a.seq_buckets == b.seq_buckets
                and a.example_scale == b.example_scale
                and a.token_budget == b.token_budget)

    async def _probe(self, member, policy) -> None:
        """One real health-gated step on the NEW grid's top shape, through
        the runner's own serving path (heal gate, deadline watchdog) — the
        same dispatcher discipline as a hot-swap unit probe: a failing
        member enters its probe/backoff schedule."""
        if self._chaos and self._chaos[0] == "probe_fail":
            self._chaos.popleft()
            err = TunerError("chaos: injected tuner probe failure")
            try:
                member.core.note_external_failure(err)
            except Exception:
                pass
            raise err
        try:
            await member.infer(self._probe_inputs(member, policy))
        except Exception as e:
            try:
                member.core.note_external_failure(e)
            except Exception:
                pass
            raise

    def _probe_inputs(self, member, policy) -> dict[str, np.ndarray]:
        from arkflow_tpu.tpu.swap import golden_inputs

        seq = policy.seq_buckets[-1]
        rows = min(2, policy.batch_buckets[0])
        if not self.packed:
            return golden_inputs(member.spec, member.cfg, rows, seed=0x7DE,
                                 seq=seq)
        from arkflow_tpu.tpu.packing import pack_tokens

        rng = np.random.default_rng(0x7DE)
        vocab = int(getattr(member.cfg, "vocab_size", 256) or 256)
        ids = rng.integers(1, max(vocab, 2), size=(rows, seq)).astype(np.int32)
        pk = pack_tokens(ids, np.full(rows, seq, np.int64), seq)
        return {"input_ids": pk.input_ids, "segment_ids": pk.segment_ids,
                "position_ids": pk.position_ids, "example_row": pk.example_row,
                "example_pos": pk.example_pos}

    # -- introspection -----------------------------------------------------

    def _decision_report(self, decision: dict) -> dict:
        return {"epoch": self.epoch, **decision}

    def report(self) -> dict:
        """JSON-able snapshot for the engine's ``/health``."""
        from arkflow_tpu.tpu.jaxcache import cache_info

        view = self.sketch.snapshot()
        out: dict[str, Any] = {
            "enabled": self.cfg.enabled,
            "epoch": self.epoch,
            "packed": self.packed,
            "interval_s": self.cfg.interval_s,
            "incumbent": self._incumbent.report(),
            "predicted_waste": round(float(self.m_predicted_waste.value), 4),
            "proposals": int(self.m_proposals.value),
            "commits": int(self.m_commits.value),
            "rollbacks": int(self.m_rollbacks.value),
            "rejected": int(self.m_rejected.value),
            "sketch": {
                "rows_seen": view.rows_seen,
                "window_rows": view.n,
                "arrival_rows_per_sec": round(view.arrival_rows_per_sec, 2),
                "len_p50": round(view.quantile(0.5), 1),
                "len_p90": round(view.quantile(0.9), 1),
                "len_p99": round(view.quantile(0.99), 1),
            },
            "jax_cache": cache_info(),
        }
        # per-bucket dispatch counts from the runner(s): the observe side's
        # ground truth for which compiled shapes traffic actually lands on
        counts = getattr(self.runner, "dispatch_counts", None)
        if counts is not None:
            out["bucket_dispatches"] = _summarize_dispatches(counts())
        if self._controller is not None:
            try:
                out["overload"] = self._controller.signals()
            except Exception:
                pass
        if self._last_decision is not None:
            out["last_decision"] = self._last_decision
        if self._last_error:
            out["last_error"] = self._last_error
        return out


def _summarize_dispatches(counts: Mapping[tuple, int]) -> dict[str, int]:
    """Shape-key dispatch counts -> a compact ``"rows x seq" -> n`` map."""
    out: dict[str, int] = {}
    for key, n in counts.items():
        rows = seq = None
        for _, shape in key:
            if len(shape) >= 2 and seq is None:
                rows, seq = shape[0], shape[1]
        if rows is None and key:
            rows = key[0][1][0] if key[0][1] else 0
        label = f"{rows}x{seq}" if seq is not None else f"{rows}"
        out[label] = out.get(label, 0) + n
    return out


def build_shape_tuner(runner, *, model: str, cfg: Optional[TunerConfig],
                      packed: bool, cache=None) -> Optional[ShapeTuner]:
    """Processor-builder entry: None when the block is absent/disabled."""
    if cfg is None or not cfg.enabled:
        return None
    tuner = ShapeTuner(runner, model=model, cfg=cfg, packed=packed)
    if cache is not None:
        tuner.add_commit_hook(cache.bump_epoch)
    return tuner
