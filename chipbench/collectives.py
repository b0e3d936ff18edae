"""What the exchange readers under ``metrics/`` share: the boundary bytes
the program counts, and the collective operations of the device trace.

The program publishes each run's ``exchange_bytes`` (the boundary bytes
one device sent) as a ``jax.monitoring`` scalar, under the name
``repro.gopher.session.EXCHANGE_BYTES_EVENT``.  ``ExchangeWatch`` listens
for it from the moment a reader is imported, which is when a cell is
resolved, before its run starts, and keeps each value with the host
clock's time.  A program that names no such scalar leaves the watch
empty, and the readers read ``None``.

A device op's trace name is its HLO text; a collective is an op whose
opcode or instruction name holds one of ``KINDS`` (an async pair's
``-start`` and ``-done`` as well).
"""
from __future__ import annotations

import re
import time
from typing import List, Optional, Tuple

from chipbench import layers, spans, trace as trace_mod

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
COLLECTIVE = re.compile("|".join(KINDS))


class ExchangeWatch:
    """(host clock, value) of every exchange-bytes scalar the program
    published since the watch started.  One listener per process: ``ExchangeWatch.get()``."""

    _one: Optional["ExchangeWatch"] = None

    def __init__(self):
        import jax
        from repro.gopher import session

        # None where the program publishes no exchange bytes
        self.event = getattr(session, "EXCHANGE_BYTES_EVENT", None)
        self.events: List[Tuple[float, float]] = []
        jax.monitoring.register_scalar_listener(self._on)

    @classmethod
    def get(cls) -> "ExchangeWatch":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event: str, value, **_kw) -> None:
        if self.event is not None and event == self.event:
            self.events.append((time.perf_counter(), float(value)))

    def between(self, lo: float, hi: float) -> Optional[float]:
        """The values published in ``[lo, hi]``, summed; ``None`` when
        none was."""
        vals = [v for t, v in self.events if lo <= t <= hi]
        return sum(vals) if vals else None


def exchange_bytes_per_instance(run, watch: ExchangeWatch
                                ) -> Optional[float]:
    """Boundary bytes one device sent per instance over the window's
    completed passes, from what the program published during them."""
    ps = layers.window_passes(run)
    if not ps:
        return None
    total = watch.between(ps[0]["t_start"], ps[-1]["t_end"])
    return spans.per_instance(total, run)


_OPCODE = re.compile(r" ([a-z][\w\-.]*)\(")


def is_collective(name: str) -> bool:
    """Whether a device op is a collective: by its opcode, or by its
    instruction name (a collective the compiler fused keeps it), never by
    the names of its operands."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return bool(COLLECTIVE.search(name))
    m = _OPCODE.search(" " + rest)
    return bool(COLLECTIVE.search(head)
                or (m is not None and COLLECTIVE.search(m.group(1))))


def collective_seconds(run) -> Optional[float]:
    """Device seconds of the collective ops, averaged over the chips;
    ``None`` without a trace or without any collective op in it."""
    if run.trace is None or not run.trace.device_ops:
        return None
    w = run.trace.window_ns
    per_chip, seen = [], False
    for ops in run.trace.device_ops.values():
        coll = [(s, e) for n, s, e in ops if is_collective(n)]
        seen = seen or bool(coll)
        per_chip.append(trace_mod.busy_ns(coll, 0.0, w))
    if not seen:
        return None
    return sum(per_chip) / len(per_chip) / 1e9
