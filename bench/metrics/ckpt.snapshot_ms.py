"""Device-to-host snapshot of the state inside ``CheckpointManager.save``,
per save in the window (ms; the program's ``ckpt.snapshot`` span)."""

from bench.program_spans import mean, window_of


def read(rec):
    w = window_of(rec)
    m = mean(w.snapshots) if w else None
    return None if m is None else 1e3 * m
