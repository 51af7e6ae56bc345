"""Seconds of the one execution ``DecodeEngine.load`` makes of each
serve-path executable, to its results' being ready: the sum of the
``serve/first_run`` phases (``startup_seconds_total{phase=serve/first_run}``).
A program that never loaded an engine reads nothing here."""
from benchmarks.harness import load_layer_metric

NAME, UNIT, LAYER = "startup_first_run_s", "s", "start-up"
MOVES, SOURCE = "setup_s", "program_counter"


def read(observed):
    acc = load_layer_metric("startup_program_s").account(observed)
    if acc is None:
        return None
    return acc["phases"].get("serve/first_run")
