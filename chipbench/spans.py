"""What the span readers under ``metrics/`` share: the program's own
profiler spans (``jax.profiler.TraceAnnotation``), read from the host
events of the traced window.

Each span name is opened by one thread role only, so a name alone says
whose time it is: ``gofs.stage`` on the prefetch pool, ``gofs.wait``,
``engine.put``, ``engine.build`` and ``engine.gather`` on the pass's
caller, ``service.execute`` on the serve thread.  Spans share the clock of
the device planes, so the device's idle time under them can be measured.
"""
from __future__ import annotations

from typing import Iterable, List, Optional

from chipbench import layers, trace as trace_mod
from chipbench.trace import Interval


def intervals(run, name: str) -> Optional[List[Interval]]:
    """The intervals of the host events named ``name`` in the traced
    window, sorted; ``None`` without a trace or without such an event."""
    if run.trace is None:
        return None
    iv = trace_mod.clip(((s, e) for _, n, s, e in run.trace.host
                         if n == name), 0.0, run.trace.window_ns)
    return iv or None


def seconds(run, name: str) -> Optional[float]:
    """Summed seconds of the spans named ``name``."""
    iv = intervals(run, name)
    return None if iv is None else sum(e - s for s, e in iv) / 1e9


def count(run, name: str) -> Optional[int]:
    """How many spans named ``name`` the traced window holds."""
    iv = intervals(run, name)
    return None if iv is None else len(iv)


def idle_seconds_under(run, names: Iterable[str]) -> Optional[float]:
    """Seconds in which the spans named ``names`` were open and no
    operation ran on the device, |U S| - |U S n U D| = |U (S + D)| - |U D|,
    averaged over the device planes as ``layers.device_idle`` averages
    them.  ``None`` without a trace, without such a span, or without a
    device plane."""
    if run.trace is None or not run.trace.device_ops:
        return None
    spans = [iv for n in names for iv in (intervals(run, n) or [])]
    if not spans:
        return None
    w = run.trace.window_ns
    idle = []
    for ops in run.trace.device_ops.values():
        dev = [(s, e) for _, s, e in ops]
        idle.append(trace_mod.busy_ns(spans + dev, 0.0, w)
                    - trace_mod.busy_ns(dev, 0.0, w))
    return sum(idle) / len(idle) / 1e9


def per_instance(value: Optional[float], run) -> Optional[float]:
    """``value`` over the instances of the window's completed passes;
    ``None`` when either is missing."""
    n = sum(p["instances"] for p in layers.window_passes(run))
    return None if value is None or not n else value / n


def per_batch_ms(value: Optional[float], run) -> Optional[float]:
    """``value`` seconds, in ms, over the ``service.execute`` spans (the
    batches the service executed) of the traced window."""
    n = count(run, "service.execute")
    return None if value is None or not n else value * 1e3 / n
