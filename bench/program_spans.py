"""The program's own spans in a run's window, for the per-layer readers.

The program records its spans in one ring (``repro.core.trace``); the
readers run in the benchmark's process after the kind, so they read that
ring.  In it:

- the window's steps are the run's last ``train.steps`` loop steps (the
  counter the kind leaves in the run record), each known by the step
  number its ``train.dispatch`` span carries;
- the window's saves are those of a saved step at or after the first
  window step;
- the window's write-back waits are the ``store.writeback_wait`` spans
  from the start of the first window save's ``ckpt.pack`` on.

A program without the tracer, or a ring that no longer holds the first
window step and the step before it, gives ``None``: no number.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Window:
    host_gaps: list[float]  # s, per window step
    snapshots: list[float]  # s, ckpt.snapshot per window save
    packs: list[float]  # s, ckpt.pack per window save
    writeback_wait_s: float | None  # s, summed; None without a window save


def program_records():
    """The program's span ring, or ``None`` where the program has none."""
    try:
        from repro.core.trace import records
    except ImportError:
        return None
    return records()


def window(recs, n_steps: int) -> Window | None:
    """The window's spans among ``recs`` (oldest first)."""
    if not recs or n_steps <= 0:
        return None
    dispatch = [r for r in recs if r.name == "train.dispatch"]
    if len(dispatch) < n_steps:
        return None
    dispatch = dispatch[-n_steps:]
    loop = dispatch[-1].thread
    first = dispatch[0].step
    mine = [r for r in recs if r.thread == loop]
    result_end = {r.step: r.t1 for r in mine if r.name == "train.result_wait"}
    ckpts = [r for r in mine if r.name == "train.ckpt"]
    gaps = []
    for d in dispatch:
        prev = result_end.get(d.step - 1)
        if prev is None:
            return None
        held = sum(c.seconds for c in ckpts if prev <= c.t0 and c.t1 <= d.t1)
        gaps.append(d.t1 - prev - held)
    saves = sorted({r.step for r in mine if r.name == "ckpt.save" and r.step >= first})
    snaps = [r for r in mine if r.name == "ckpt.snapshot" and r.step in saves]
    packs = [r for r in recs if r.name == "ckpt.pack" and r.step in saves]
    wait = None
    if packs:
        since = min(p.t0 for p in packs)
        wait = sum((r.seconds for r in recs if r.name == "store.writeback_wait" and r.t0 >= since),
                   0.0)
    return Window(gaps, [r.seconds for r in snaps], [r.seconds for r in packs], wait)


def window_of(rec) -> Window | None:
    """The window of the run record ``rec``."""
    return window(program_records(), int(rec.counters.get("train.steps", 0)))


def mean(xs: list[float]) -> float | None:
    return sum(xs) / len(xs) if xs else None
