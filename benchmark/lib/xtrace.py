"""Reduction of a ``jax.profiler`` trace to what the metrics read.

The profiler writes an ``.xplane.pb``; ``load_xplane`` turns it into plain
lists (planes -> lines -> ``[name, start_ns, duration_ns]`` events) with
nothing but JAX, and everything else here works on that plain form — so the
tests can check the arithmetic on a small recorded trace kept as JSON.

What a TPU trace holds (seen on a v5e, JAX 0.9): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
operation and whose line ``XLA Modules`` has one event per executed program
(named after the jitted function); and ``/host:CPU`` with one line per host
thread, holding the runtime's own annotations (``PjitFunction(...)``,
``np.asarray(jax.Array)``, transfers, ...). On the CPU backend (a rehearsal)
there is no device plane: the executor threads' op events stand in for it.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Optional

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: an idle stretch shorter than this is not a gap anyone can act on
MIN_GAP_NS = 2_000
#: how many of the longest gaps get a name; the rest are summed as "other"
NAMED_GAPS = 4000


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    planes = []
    for pl in ProfileData.from_file(path).planes:
        lines = []
        for ln in pl.lines:
            events = [[e.name, float(e.start_ns), float(e.duration_ns)]
                      for e in ln.events]
            if events:
                lines.append({"name": ln.name, "events": events})
        if lines:
            planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def head(trace: dict, per_line: int) -> dict:
    """The first ``per_line`` events of every line: small enough to read by
    hand, or to keep beside a test."""
    return {"planes": [
        {"name": pl["name"], "lines": [
            {"name": ln["name"], "events": ln["events"][:per_line]}
            for ln in pl["lines"]]} for pl in trace["planes"]]}


def clean(name: str) -> str:
    """A name the ledger can carry: letters, digits, ``_``, ``.``, ``-``,
    ``:`` kept; anything else becomes ``_``; at most 80 characters."""
    return re.sub(r"[^A-Za-z0-9_.:\-]", "_", name)[:80]


def _line(plane: dict, name: str) -> list:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def device_planes(trace: dict) -> list[dict]:
    """One ``{"name", "ops", "modules"}`` per device. On a TPU, the planes
    ``/device:TPU:n``. On the CPU backend, one pseudo-device made of the
    executor threads' events (no module line)."""
    out = []
    for pl in trace["planes"]:
        if pl["name"].startswith("/device:TPU:"):
            out.append({"name": pl["name"], "ops": _line(pl, OPS_LINE),
                        "modules": _line(pl, MODULES_LINE)})
    if out:
        return out
    ops = []
    for pl in trace["planes"]:
        if pl["name"] != "/host:CPU":
            continue
        for ln in pl["lines"]:
            if ln["name"].startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen")):
                ops += [e for e in ln["events"]
                        if e[2] > 0 and "::" not in e[0]
                        and not e[0].startswith("end: ")]
    return [{"name": "/host:CPU(executor)", "ops": ops, "modules": []}] if ops else []


def host_events(trace: dict) -> list:
    """Events of the host's threads (the python thread, runtime threads),
    without the executor threads that stand in for the device on the CPU."""
    out = []
    for pl in trace["planes"]:
        if pl["name"] != "/host:CPU":
            continue
        for ln in pl["lines"]:
            if ln["name"].startswith(("tf_XLAPjRtCpuClient", "tf_XLAEigen",
                                      "tf_xla-cpu-codegen")):
                continue
            out += [e for e in ln["events"] if e[2] > 0]
    return out


def busy_intervals(ops: list) -> np.ndarray:
    """Union of the op intervals, as an ``[n, 2]`` array of (start, end)."""
    if not ops:
        return np.zeros((0, 2))
    iv = np.array(sorted((e[1], e[1] + e[2]) for e in ops))
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out)


def window_ns(trace: dict) -> tuple[float, float]:
    """The traced window: from the first event start to the last event end
    over every plane (the host's threads are never silent for long, so this
    is the profiler session to within a millisecond)."""
    lo, hi = float("inf"), 0.0
    for pl in trace["planes"]:
        for ln in pl["lines"]:
            ev = ln["events"]
            lo = min(lo, min(e[1] for e in ev))
            hi = max(hi, max(e[1] + e[2] for e in ev))
    return lo, hi


def op_name(event_name: str) -> str:
    """The operation's own name. A TPU trace names an op event by its whole
    HLO line (``%fusion.136 = f32[256,512]{...} fusion(...)``); the name is
    what stands before the ``=``."""
    m = re.match(r"%?([^\s=]+)\s*=", event_name)
    return m.group(1) if m else event_name


def op_totals(ops: list) -> dict[str, float]:
    """Seconds of SELF time by operation name. Ops nest on the line (a
    ``while`` holds the ops of its body): an op's self time is its duration
    less that of the ops directly inside it, so that the totals add up to
    the busy time and a loop does not hide what runs in it."""
    tot: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            tot[name] = tot.get(name, 0.0) + max(self_ns, 0.0) * 1e-9

    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([op_name(name), start + dur, dur])
    close(float("inf"))
    return tot


def name_gaps(busy: np.ndarray, lo: float, hi: float, host: list) -> dict[str, float]:
    """Idle seconds of one device by what the host was doing. Every instant
    of an idle stretch (between busy intervals, and from the window's edges)
    goes to the SHORTEST host event that covers it — the innermost, most
    specific one; an instant no host event covers goes to
    ``untraced_host_time``: the host was in code the runtime does not
    annotate (Python, the event loop, a sleep). Only the ``NAMED_GAPS``
    longest stretches are apportioned; the rest sum under
    ``other_short_gaps``."""
    edges = np.concatenate([[lo], busy.reshape(-1), [hi]])
    gaps = np.stack([edges[0::2], edges[1::2]], axis=1)
    gaps = gaps[(gaps[:, 1] - gaps[:, 0]) >= MIN_GAP_NS]
    out: dict[str, float] = {}
    if len(gaps) == 0:
        return out
    order = np.argsort(gaps[:, 0] - gaps[:, 1])
    rest = gaps[order[NAMED_GAPS:]]
    if len(rest):
        out["other_short_gaps"] = float((rest[:, 1] - rest[:, 0]).sum() * 1e-9)
    gaps = gaps[order[:NAMED_GAPS]]
    if not host:
        out["untraced_host_time"] = float((gaps[:, 1] - gaps[:, 0]).sum() * 1e-9)
        return out
    hs = np.array([e[1] for e in host])
    he = hs + np.array([e[2] for e in host])
    by_start = np.argsort(hs)
    hs, he = hs[by_start], he[by_start]
    names = [host[i][0] for i in by_start]
    for g0, g1 in gaps:
        upto = int(np.searchsorted(hs, g1, side="left"))
        idx = np.nonzero(he[:upto] > g0)[0]
        # innermost first: shorter events claim their instants before longer
        idx = idx[np.argsort((he[idx] - hs[idx]))]
        free = [(g0, g1)]  # instants of the gap not yet claimed
        for i in idx:
            if not free:
                break
            a, b = max(hs[i], g0), min(he[i], g1)
            got, left = 0.0, []
            for f0, f1 in free:
                c0, c1 = max(f0, a), min(f1, b)
                if c1 <= c0:
                    left.append((f0, f1))
                    continue
                got += c1 - c0
                if f0 < c0:
                    left.append((f0, c0))
                if c1 < f1:
                    left.append((c1, f1))
            free = left
            if got > 0:
                key = clean(names[int(i)])
                out[key] = out.get(key, 0.0) + got * 1e-9
        rest_ns = sum(f1 - f0 for f0, f1 in free)
        if rest_ns > 0:
            out["untraced_host_time"] = out.get("untraced_host_time", 0.0) + rest_ns * 1e-9
    return out


def modules_by_name(modules: list) -> dict[str, list[float]]:
    """Program name -> durations in seconds of its executions. The profiler
    appends the program's id in parentheses; it is dropped."""
    out: dict[str, list[float]] = {}
    for name, _, dur in modules:
        out.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(dur * 1e-9)
    return out


def ops_inside(ops: list, modules: list, module_re: str, op_re: str
               ) -> tuple[float, int]:
    """Seconds of ops matching ``op_re`` that ran inside executions of
    programs matching ``module_re``, and how many such executions."""
    mre, ore = re.compile(module_re), re.compile(op_re)
    spans = sorted((m[1], m[1] + m[2]) for m in modules if mre.search(m[0]))
    if not spans:
        return 0.0, 0
    starts = np.array([s for s, _ in spans])
    ends = np.array([e for _, e in spans])
    total = 0.0
    for name, start, dur in ops:
        if not ore.search(name):
            continue
        i = int(np.searchsorted(starts, start, side="right")) - 1
        if i >= 0 and start < ends[i]:
            total += dur * 1e-9
    return total, len(spans)


def reduce_trace(trace: dict) -> dict:
    """Everything the harness and the metric readers take from a trace."""
    devs = device_planes(trace)
    if not devs:
        return {"devices": 0}
    lo, hi = window_ns(trace)
    host = host_events(trace)
    busy = [busy_intervals(d["ops"]) for d in devs]
    busy_s = [float((b[:, 1] - b[:, 0]).sum() * 1e-9) if len(b) else 0.0
              for b in busy]
    first = devs[0]
    totals = op_totals(first["ops"])
    gaps = name_gaps(busy[0], lo, hi, host)

    def top(d: dict) -> list:
        return [[clean(k), float(v)] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:10]]

    return {
        "devices": len(devs),
        "window_s": (hi - lo) * 1e-9,
        "busy_s": float(np.mean(busy_s)),
        "busy_s_per_device": busy_s,
        "device_ops": top(totals),
        "idle_gaps": top(gaps),
        "op_totals": totals,
        "modules": modules_by_name(first["modules"]),
        "first_device": first,
    }
