"""Of the engine's slots, the share that a decode dispatch steps:
``n_active`` summed over the traced ``serve/decode_step`` spans, over
their count times ``max_slots``.  ``slot_occupancy.closed`` counts a slot
that holds a half-read prompt as full; this one counts it as not
decoding."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "slots_decoding_share.closed", "%", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    stepped = [float(s.args["n_active"])
               for s in program_spans.named(program_spans.of(observed),
                                            "serve/decode_step")
               if "n_active" in s.args]
    slots = observed.window.get("max_slots")
    if not stepped or not slots:
        return None
    return 100.0 * sum(stepped) / (len(stepped) * slots)
