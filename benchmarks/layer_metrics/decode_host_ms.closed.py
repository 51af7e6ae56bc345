"""The host's own work per decode step: over the traced turns of the
engine's loop (``serve/iteration``) that hold a ``serve/decode_step``,
the median of the turn's duration less its ``serve/step_wait`` (the
blocking read-back: the device works inside it) and less any
``serve/prefill`` in it."""
from benchmarks import program_spans
from benchmarks.stats import median

NAME, UNIT, LAYER = "decode_host_ms.closed", "ms", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_span"


def read(observed):
    host_ms = []
    for turn in program_spans.named(program_spans.of(observed),
                                    "serve/iteration"):
        waits = turn.inside("serve/step_wait")
        if not waits:       # no step of the program's in this turn
            continue
        away = sum(s.dur_ns for s in waits + turn.inside("serve/prefill"))
        host_ms.append((turn.dur_ns - away) / 1e6)
    return median(host_ms) if host_ms else None
