"""How often the token budget of an admission round is what ends it: of
the traced turns of the engine's loop (``serve/iteration``) that hold a
``serve/decode_step``, the share whose span carries
``admit_budget_bound`` (slots stood free and requests waited when the
round's prompt tokens ran out)."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "admit_budget_bound_share.closed", "%", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_span"


def read(observed):
    turns = [turn for turn in program_spans.named(program_spans.of(observed),
                                                  "serve/iteration")
             if turn.inside("serve/decode_step")]
    if not turns:
        return None
    bound = sum(1 for turn in turns if "admit_budget_bound" in turn.args)
    return 100.0 * bound / len(turns)
