"""Median host time of one train step, ended by ``block_until_ready``."""
from benchmarks.stats import median

NAME, UNIT, LAYER = "train_step_ms", "ms", "train step"
MOVES, SOURCE = "train_tokens_per_s", "host_clock"


def read(observed):
    steps = observed.window.get("step_s")
    return 1e3 * median(steps) if steps else None
