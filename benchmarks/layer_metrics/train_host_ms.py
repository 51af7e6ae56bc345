"""Median host time of one ``train/step`` span: ``fit_batch`` from the
batch's first ``device_put`` to the return of the compiled step's
dispatch.  The device works on after it; the benchmark's drawing of the
ids lies outside it."""
from benchmarks import program_spans
from benchmarks.stats import median

NAME, UNIT, LAYER = "train_host_ms", "ms", "train step"
MOVES, SOURCE = "train_tokens_per_s", "program_span"


def read(observed):
    steps = program_spans.named(program_spans.of(observed), "train/step")
    return median([s.dur_ns / 1e6 for s in steps]) if steps else None
