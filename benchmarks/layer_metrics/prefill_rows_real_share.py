"""Of the rows the traced prefill chunks computed, the share that were
real tokens: ``prompt_tokens`` over ``bucket``, both summed over the
traced ``serve/prefill`` spans.  What short prompts leave of a bucket:
the rest is padding the chunk's program computes all the same."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "prefill_rows_real_share", "%", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_span"


def read(observed):
    chunks = [s.args for s in program_spans.named(program_spans.of(observed),
                                                  "serve/prefill")
              if float(s.args.get("bucket", 0) or 0) > 0
              and "prompt_tokens" in s.args]
    rows = sum(float(a["bucket"]) for a in chunks)
    if not rows:
        return None
    return 100.0 * sum(float(a["prompt_tokens"]) for a in chunks) / rows
