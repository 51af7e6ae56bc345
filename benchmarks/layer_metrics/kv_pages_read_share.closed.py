"""Of the KV pages a decode step's attention could read (every slot's
whole window: ``max_slots`` x pages per slot), the share it does read a
layer and pool: the spans' ``kv_pages_read`` (the pages the stepped
slots hold, where the program attends over those only) summed over the
traced ``serve/decode_step`` spans.  A program that does not count it
reads nothing here."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "kv_pages_read_share.closed", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "kv_pages_read" in s.args]
    if not steps:
        return None
    program = observed.cell.config.get("program", {})
    try:
        window = (program["max_slots"]
                  * (program["max_len"] // program["page_size"]))
    except KeyError:
        return None
    could = window * sum(float(a.get("tokens", 1)) for a in steps)
    return 100.0 * sum(float(a["kv_pages_read"]) for a in steps) / could
