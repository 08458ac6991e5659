"""Time the store's puts waited on its full write-back queue, summed from
the start of the window save's packing on (s; the program's
``store.writeback_wait`` span)."""

from bench.program_spans import window_of


def read(rec):
    w = window_of(rec)
    return w.writeback_wait_s if w else None
