"""Seconds spent reading the persistent compile cache and loading what
it held, inside the program's phases
(``compile_seconds_total{stage=cache_read}``: a
``backend_compile_duration`` that followed a hit, booked whole)."""
from benchmarks.harness import load_layer_metric

NAME, UNIT, LAYER = "startup_cache_read_s", "s", "start-up"
MOVES, SOURCE = "setup_s", "program_counter"


def read(observed):
    acc = load_layer_metric("startup_program_s").account(observed)
    if acc is None:
        return None
    return acc["compile"].get("cache_read", 0.0)
