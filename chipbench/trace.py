"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
idle gaps and per-operation time.

``jax.profiler.ProfileData`` reads the trace.  Device planes are named
``/device:<KIND>:<n>``; on each, the line ``XLA Ops`` holds one event per
operation the device ran.  Host planes (``/host:...``) hold what the host's
threads were doing, which names the idle gaps.  All times are nanoseconds
from the start of the trace.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
# host events that say nothing about what the host was doing: the
# profiler's own thread-pool markers
_HOST_NOISE = re.compile(r"^(ThreadpoolListener::|\$)")


@dataclass
class Trace:
    """Device operations per chip and host events, with the traced
    window ``[0, window_ns]``."""

    window_ns: float
    device_ops: Dict[str, List[Tuple[str, float, float]]] = field(
        default_factory=dict)  # plane -> [(op name, start, end)]
    host: List[Tuple[str, str, float, float]] = field(
        default_factory=list)  # [(thread, event name, start, end)]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, window_ns: float) -> Trace:
    """Read device ops and host events from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace(window_ns=float(window_ns))
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = tr.device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, float(e.start_ns), float(e.end_ns))
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.host.extend(
                    (line.name, e.name, float(e.start_ns), float(e.end_ns))
                    for e in line.events
                    if e.duration_ns > 0 and not _HOST_NOISE.match(e.name))
    return tr


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    """Intervals cut to ``[lo, hi]``, empty ones dropped, sorted."""
    out = [(max(s, lo), min(e, hi)) for s, e in intervals]
    return sorted((s, e) for s, e in out if e > s)


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def busy_ns(intervals: Iterable[Interval], lo: float, hi: float) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in union(clip(intervals, lo, hi)))


def gaps(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    """The idle gaps of ``[lo, hi]``: what the union leaves uncovered."""
    out, cur = [], lo
    for s, e in union(clip(intervals, lo, hi)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_time(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float,
            pattern: Optional[str] = None) -> float:
    """Summed device time (ns) of the ops whose name matches ``pattern``
    (every op when ``None``), clipped to ``[lo, hi]``."""
    rx = re.compile(pattern) if pattern is not None else None
    return sum(max(0.0, min(e, hi) - max(s, lo)) for n, s, e in ops
               if rx is None or rx.search(n))


_OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def short_name(name: str) -> str:
    """An op's trace name is its HLO instruction text; keep the
    instruction's name, its opcode and a custom call's target."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(rest)
    out = f"{head} {m.group(1)}" if m else head
    t = _TARGET.search(rest)
    return f"{out} {t.group(1)}" if t else out


def self_times(ops: Sequence[Tuple[str, float, float]], lo: float,
               hi: float) -> Dict[str, float]:
    """Device time of each op name, less the time of the ops nested in
    it (a while loop holds its body's ops on the same line), clipped to
    ``[lo, hi]``, in ns."""
    per: Dict[str, float] = {}
    stack: List[List] = []  # [end, name] of the enclosing ops
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        d = max(0.0, min(e, hi) - max(s, lo))
        per[name] = per.get(name, 0.0) + d
        if stack:  # the part of this op inside its parent is not the
            parent_end, parent = stack[-1]  # parent's own time
            inner = max(0.0, min(e, parent_end, hi) - max(s, lo))
            per[parent] = per.get(parent, 0.0) - inner
        stack.append([e, name])
    return per


def top_ops(ops: Sequence[Tuple[str, float, float]], lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` ops with the most device self time, in seconds, by short
    name."""
    per: Dict[str, float] = {}
    for name, t in self_times(ops, lo, hi).items():
        k = short_name(name)
        per[k] = per.get(k, 0.0) + t
    ranked = sorted(per.items(), key=lambda kv: -kv[1])[:n]
    return [(k, v / 1e9) for k, v in ranked if v > 0]


def host_activity(host: Sequence[Tuple[str, str, float, float]],
                  gap: Interval) -> str:
    """What the host was doing in ``gap``: the host event that overlaps
    the gap most (the innermost of equal overlaps), as ``thread/event``;
    ``"no host event"`` when none does."""
    s0, e0 = gap
    best, best_key = "no host event", (0.0, 0.0)
    for thread, name, s, e in host:
        ov = min(e, e0) - max(s, s0)
        if ov <= 0:
            continue
        key = (ov, -(e - s))  # more overlap first, then the shorter event
        if key > best_key:
            best, best_key = f"{thread}/{name}", key
    return best


def longest_gaps(tr: Trace, plane: str, n: int = 10
                 ) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of one device, each named by what the
    host was doing in it, in seconds."""
    iv = [(s, e) for _, s, e in tr.device_ops.get(plane, [])]
    gs = sorted(gaps(iv, 0.0, tr.window_ns), key=lambda g: g[0] - g[1])[:n]
    return [(host_activity(tr.host, g), (g[1] - g[0]) / 1e9) for g in gs]


def summary(tr: Trace) -> Dict:
    """Busy seconds averaged over the traced devices, the window, and the
    breakdown (top device ops and longest gaps of the busiest device)."""
    planes = sorted(tr.device_ops)
    if not planes:
        return {"busy_s": 0.0, "window_s": tr.window_ns / 1e9,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    busy = [busy_ns([(s, e) for _, s, e in tr.device_ops[p]], 0.0,
                    tr.window_ns) for p in planes]
    lead = planes[busy.index(max(busy))]
    return {
        "busy_s": sum(busy) / len(busy) / 1e9,
        "window_s": tr.window_ns / 1e9,
        "breakdown": {
            "device_ops": [[k, v] for k, v in
                           top_ops(tr.device_ops[lead], 0.0, tr.window_ns)],
            "idle_gaps": [[k, v] for k, v in longest_gaps(tr, lead)],
        },
    }
