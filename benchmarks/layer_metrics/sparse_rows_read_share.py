"""Of the rows the stepped slots hold, the share whose K and V the decode
step's attention reads after the selection: the spans' ``attn_rows_read``
over their ``rows_held`` (both summed over the layers and, in a fused
dispatch, over its steps), summed over the traced ``serve/decode_step``
spans.  100% says the selection does not reach the cache's read.  A
program that does not count them reads nothing here."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "sparse_rows_read_share", "%", "sparse attention"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "attn_rows_read" in s.args and "rows_held" in s.args]
    held = sum(float(a["rows_held"]) for a in steps)
    if not held:
        return None
    return 100.0 * sum(float(a["attn_rows_read"]) for a in steps) / held
