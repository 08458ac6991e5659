"""Packing of the snapshot's leaves into chunks on the save lane, per save
in the window (s; the program's ``ckpt.pack`` span)."""

from bench.program_spans import mean, window_of


def read(rec):
    w = window_of(rec)
    return mean(w.packs) if w else None
