"""Seconds of backend compiles inside the program's phases
(``compile_seconds_total{stage=backend}``: a ``backend_compile_duration``
that followed a miss of the persistent cache, or no cache at all): 0 on a
warm run, the bulk of a cold one.  Says the cache's hits and misses
inside the phases beside it."""
from benchmarks.harness import load_layer_metric, say

NAME, UNIT, LAYER = "startup_compile_s", "s", "start-up"
MOVES, SOURCE = "setup_s", "program_counter"


def read(observed):
    acc = load_layer_metric("startup_program_s").account(observed)
    if acc is None:
        return None
    say(f"start-up account: compile cache hits {acc['cache'].get('hit', 0)}, "
        f"misses {acc['cache'].get('miss', 0)} inside the program's phases")
    return acc["compile"].get("backend", 0.0)
