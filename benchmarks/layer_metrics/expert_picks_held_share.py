"""Of the routed picks the traced decode steps and prefill chunks made,
the share that fell on experts held on this chip: ``expert_picks_held``
over ``expert_picks``, the arguments the program's ``serve/decode_step``
and ``serve/prefill`` spans carry.  12 of 384 held is 3.1% under even
routing: the number ties the cut to the deployment."""
from benchmarks import program_spans

NAME, UNIT, LAYER = "expert_picks_held_share", "%", "expert layer"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"
SPANS = ("serve/decode_step", "serve/prefill")


def routed(observed):
    """The arguments of the traced spans that routed anything."""
    forest = program_spans.of(observed)
    return [s.args for name in SPANS for s in program_spans.named(forest, name)
            if float(s.args.get("expert_picks", 0) or 0) > 0]


def read(observed):
    args = routed(observed)
    picks = sum(float(a["expert_picks"]) for a in args)
    if not picks:
        return None
    return 100.0 * sum(float(a["expert_picks_held"]) for a in args) / picks
