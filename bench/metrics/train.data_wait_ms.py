"""Mean time a window step waited on the loader's ``next()`` (ms)."""


def read(rec):
    waits = rec.spans_named("bench.train.data_wait")
    return 1e3 * sum(waits) / len(waits) if waits else None
