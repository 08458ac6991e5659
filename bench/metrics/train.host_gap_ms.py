"""Mean time per window step in which the chip has no step queued because
the host is busy (ms): from the end of the previous step's
``train.result_wait`` to the end of this step's ``train.dispatch``, less
any ``train.ckpt`` between them (the program's spans)."""

from bench.program_spans import mean, window_of


def read(rec):
    w = window_of(rec)
    m = mean(w.host_gaps) if w else None
    return None if m is None else 1e3 * m
