"""Seconds JAX spent tracing and lowering inside the program's phases
(``compile_seconds_total{stage=trace}`` + ``{stage=lower}``, every phase
but ``none``): what the persistent compile cache cannot save, and a warm
bundle or fewer, stacked programs can."""
from benchmarks.harness import load_layer_metric

NAME, UNIT, LAYER = "startup_trace_lower_s", "s", "start-up"
MOVES, SOURCE = "setup_s", "program_counter"


def read(observed):
    acc = load_layer_metric("startup_program_s").account(observed)
    if acc is None:
        return None
    return acc["compile"].get("trace", 0.0) + acc["compile"].get("lower", 0.0)
