"""Of the KV pages the stepped slots hold reserved, the share that holds
at least one token: ``pages_filled`` over ``pages_reserved``, summed
over the traced ``serve/decode_step`` spans."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "kv_pages_filled_share.closed", "%", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "pages_reserved" in s.args and "pages_filled" in s.args]
    reserved = sum(float(a["pages_reserved"]) for a in steps)
    if not reserved:
        return None
    return 100.0 * sum(float(a["pages_filled"]) for a in steps) / reserved
