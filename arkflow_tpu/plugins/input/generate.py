"""Synthetic generator input — the primary test/bench source.

Mirrors the reference's ``generate`` input (ref:
crates/arkflow-plugin/src/input/generate.rs:26-100): fixed payload emitted at
``interval``, ``batch_size`` rows per read, optional ``count`` cap after which
the stream EOFs. Config:

    type: generate
    payload: '{"sensor":"t1","temp":21.5}'
    payloads: ['{"a":1}', '{"b":2}']   # alternative: rotate a payload mix
                                       # across rows (ragged-traffic benches)
    interval: 10ms        # optional; 0 = as fast as downstream pulls
    batch_size: 128
    count: 100000         # optional total-row cap
    codec: json           # optional; raw __value__ bytes otherwise
"""

from __future__ import annotations

import asyncio
from typing import Optional

from arkflow_tpu.batch import MessageBatch
from arkflow_tpu.components import Ack, Input, NoopAck, Resource, register_input
from arkflow_tpu.errors import ConfigError, EndOfInput
from arkflow_tpu.plugins.codec.helper import build_codec, decode_payloads
from arkflow_tpu.utils.duration import parse_duration


class GenerateInput(Input):
    def __init__(self, payloads: list[bytes], interval_s: float, batch_size: int,
                 count: Optional[int], codec=None):
        if batch_size <= 0:
            raise ConfigError("generate.batch_size must be positive")
        if not payloads:
            raise ConfigError("generate input requires a payload")
        self.payloads = payloads
        self.interval_s = interval_s
        self.batch_size = batch_size
        self.count = count
        self.codec = codec
        self._emitted = 0
        self._template: Optional[MessageBatch] = None

    async def connect(self) -> None:
        self._emitted = 0

    async def read(self) -> tuple[MessageBatch, Ack]:
        if self.count is not None and self._emitted >= self.count:
            raise EndOfInput()
        if self.interval_s > 0:
            await asyncio.sleep(self.interval_s)
        n = self.batch_size
        if self.count is not None:
            n = min(n, self.count - self._emitted)
        # identical rows: build once, slice thereafter (hot path for benches);
        # a payload mix rotates across rows of the template
        if self._template is None or self._template.num_rows < n:
            size = max(n, self.batch_size)
            rows = [self.payloads[i % len(self.payloads)] for i in range(size)]
            self._template = decode_payloads(rows, self.codec)
        batch = self._template if n == self._template.num_rows else self._template.slice(0, n)
        self._emitted += n
        return batch.with_source("generate"), NoopAck()


@register_input("generate")
def _build(config: dict, resource: Resource) -> GenerateInput:
    # 'context' is the reference's field name (generate.rs:26-100);
    # 'payload' is the clearer alias — both accepted. 'payloads' rotates a
    # mix of rows (ragged-traffic benches / tests).
    import json

    mix = config.get("payloads")
    if mix is not None:
        if not isinstance(mix, (list, tuple)) or not mix:
            raise ConfigError("generate.payloads must be a non-empty list")
        payloads = [
            (json.dumps(p) if isinstance(p, (dict, list)) else str(p)).encode()
            for p in mix
        ]
    else:
        payload = config.get("payload", config.get("context"))
        if payload is None:
            raise ConfigError("generate input requires 'payload' (or 'context')")
        if isinstance(payload, (dict, list)):
            payload = json.dumps(payload)
        payloads = [str(payload).encode()]
    interval = parse_duration(config.get("interval", 0))
    return GenerateInput(
        payloads=payloads,
        interval_s=interval,
        batch_size=int(config.get("batch_size", 1)),
        count=int(config["count"]) if config.get("count") is not None else None,
        codec=build_codec(config.get("codec"), resource),
    )
