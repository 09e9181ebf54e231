"""Error classes.

Single error family with typed control-flow variants, mirroring the reference's
``Error`` enum (ref: crates/arkflow-core/src/lib.rs:66-110). Two variants are
control flow, not failures:

- ``EndOfInput``  -- graceful end of a finite source; the stream drains and shuts
  down (ref ``Error::EOF``, stream/mod.rs:178-181).
- ``Disconnection`` -- transient transport loss; the input task enters a
  reconnect loop (ref ``Error::Disconnection``, stream/mod.rs:183-194).
"""

from __future__ import annotations


class ArkError(Exception):
    """Base class for all engine errors."""


class ConfigError(ArkError):
    """Invalid or missing configuration."""


class ConnectError(ArkError):
    """Failed to establish a connection to an external system."""


class ReadError(ArkError):
    """Failed to read from an input."""


class FrameIntegrityError(ReadError):
    """A flight frame failed its crc32 integrity check: the bytes on the
    wire do not match what the peer sent. Corruption is never silent —
    the message names the frame class (infer request, kv_push slab, ...)
    so a flipped bit in a raw bf16 slab surfaces as a loud, attributable
    error instead of garbage logits."""


class WriteError(ArkError):
    """Failed to write to an output."""


class ProcessError(ArkError):
    """A processor failed on a batch."""


class CodecError(ArkError):
    """Encode/decode failure."""


class EndOfInput(ArkError):
    """Control flow: the input is exhausted; shut the stream down gracefully."""

    def __init__(self, msg: str = "end of input"):
        super().__init__(msg)


class Disconnection(ArkError):
    """Control flow: transient disconnect; the runtime retries the connection."""

    def __init__(self, msg: str = "disconnected"):
        super().__init__(msg)


class Overloaded(ArkError):
    """The engine is shedding load: admission rejected the batch/request
    before the worker queue (deadline cannot be met, queue window full, or
    priority band browned out). Carries the controller's drain estimate so
    transports can tell clients when to retry (HTTP 429 ``Retry-After``)."""

    def __init__(self, msg: str = "overloaded", retry_after_s: float = 1.0):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class StepDeadlineExceeded(ArkError):
    """A device step missed its ``step_deadline``: the runner treats the
    device as hung (UNHEALTHY), abandons the in-flight step, and the stream
    nacks the batch so the source redelivers (at-least-once preserved)."""


class RunnerDead(ArkError):
    """A runner (or every member of a device pool) exhausted its recovery
    probes and was marked DEAD; batches can no longer be served by it."""


class SwapError(ArkError):
    """A live model hot-swap (``tpu/swap.py``) was rejected or rolled back:
    the candidate checkpoint failed to restore, the canary found the new
    weights disagreeing with the live model, a post-flip probe failed, or a
    swap was already in progress. The PRIOR params are serving throughout —
    a SwapError never implies an interruption of traffic."""


class TunerError(ArkError):
    """A runtime shape retune (``tpu/tuner.py``) was rejected or rolled
    back: the post-flip probe failed on the proposed grid, so every flipped
    unit re-adopted the incumbent bucket configuration. Like ``SwapError``,
    a TunerError never implies an interruption of traffic — the incumbent
    shapes served throughout, and no coalescer or cache was touched."""


class UnsupportedSql(ArkError):
    """Raised by the Arrow-native SQL planner when a query needs the fallback engine."""
