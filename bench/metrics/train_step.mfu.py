"""Model FLOP/s utilization of training: forward and backward operations
per token from shapes (no recompute) times tokens per second of the
window, over the chip's peak (%)."""


def read(rec):
    peak = rec.peaks.get("bf16_flops_per_s")
    tokens = rec.counters.get("train.tokens")
    if not peak or not tokens:
        return None
    return 100.0 * rec.counters["train.flops_per_token"] * tokens / rec.window_s / peak
