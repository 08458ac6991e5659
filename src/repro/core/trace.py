"""Host spans of the program, on one clock.

``with span("ckpt.save", step=42) as s:`` records ``(seq, name, thread,
t0, t1, step)`` on ``time.perf_counter`` into one process-wide ring that
keeps the newest :data:`RING` records; after the block ``s.seconds`` is
its length.  :func:`records` returns a snapshot of the ring, oldest
first, in the order the spans ended.

Where ``jax`` is already imported, a span also enters
``jax.profiler.TraceAnnotation(name)``: a profile taken meanwhile holds
the span on the host plane of the thread that ran it, on the same clock
as the device's operations.  This module imports nothing of JAX itself,
so the store's layers below the trainer stay free of it.

There is no switch: a span costs a few microseconds of host time with
no profile running.  The store's counters (``TierStats``, ``StoreStats``) stay
where they are; spans time, counters count.
"""

from __future__ import annotations

import collections
import sys
import threading
import time
from typing import NamedTuple

#: records kept; the oldest are dropped first
RING = 65536

_ring: collections.deque = collections.deque(maxlen=RING)
_lock = threading.Lock()
_seq = 0
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


class SpanRecord(NamedTuple):
    seq: int
    name: str
    thread: str
    t0: float
    t1: float
    step: int | None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def _find_annotation():
    global _annotation
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class span:
    """Context manager timing one named phase; see the module docstring."""

    __slots__ = ("name", "step", "t0", "t1", "_ann")

    def __init__(self, name: str, step: int | None = None) -> None:
        self.name = name
        self.step = step
        self.t0 = self.t1 = None
        self._ann = None

    def __enter__(self) -> "span":
        ann = _annotation or _find_annotation()
        if ann is not None:
            self._ann = ann(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        global _seq
        self.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        thread = threading.current_thread().name
        with _lock:
            _ring.append(SpanRecord(_seq, self.name, thread, self.t0, self.t1, self.step))
            _seq += 1

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def records() -> list[SpanRecord]:
    """The ring's records, oldest first (ascending ``seq``)."""
    with _lock:
        return list(_ring)
