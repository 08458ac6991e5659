"""Mean time ``CheckpointManager.save`` held the train loop, per save in
the window (ms): the async save's device-to-host snapshot."""


def read(rec):
    saves = rec.spans_named("bench.ckpt.save")
    return 1e3 * sum(saves) / len(saves) if saves else None
