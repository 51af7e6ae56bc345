"""Median wait of a request from ``generate_async`` to its slot: the
``queue_wait_ms`` argument of the traced ``serve/admit`` spans (the
engine's own clock).  Few admissions fall into the traced seconds, so
the run says how many."""
from benchmarks import program_spans
from benchmarks.harness import say
from benchmarks.stats import median

NAME, UNIT, LAYER = "queue_wait_ms.closed", "ms", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_span"


def read(observed):
    waits = [float(s.args["queue_wait_ms"])
             for s in program_spans.named(program_spans.of(observed),
                                          "serve/admit")
             if "queue_wait_ms" in s.args]
    if not waits:
        return None
    say(f"{NAME}: median over {len(waits)} admission(s) in the traced part")
    return median(waits)
