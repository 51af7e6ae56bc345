"""The part of ``setup_s`` that is the program's, by its own clock: the
seconds of the start-up account's outermost phases (``lm/init``, the
LM's constructor; ``serve/load``, the whole of ``DecodeEngine.load``;
``train/first_step``, the first ``fit_batch``), summed from
``startup_seconds_total{phase=...}`` in the program's process-global
registry.  The benchmark's own seeded-weights call, its warm requests
and the reference's compiles fall outside every phase and stay out.
A program without the account (the parent) reads nothing here.

``account`` is what the other ``startup_*`` readers share."""

NAME, UNIT, LAYER = "startup_program_s", "s", "start-up"
MOVES, SOURCE = "setup_s", "program_counter"

#: the phases that stand in no other
ROOTS = ("lm/init", "serve/load", "train/first_step")
#: the label of a compile event that fell outside every phase
NO_PHASE = "none"


def series(name):
    """[(labels, value)] of one labelled counter of the global registry."""
    from deeplearning4j_tpu import obs

    out = []
    for key, value in obs.get_registry().counter(name).snapshot().items():
        if "{" not in key:
            continue
        labels = dict(kv.split("=", 1) for kv in
                      key[key.index("{") + 1:-1].split(","))
        out.append((labels, value))
    return out


def account(observed):
    """{"phases": {name: seconds}, "compile": {stage: seconds inside the
    program's phases}, "cache": {result: count inside them}}, or None
    without a cell or where the account holds no phase."""
    if observed.cell is None:
        return None
    phases = {lb["phase"]: v for lb, v in series("startup_seconds_total")}
    if not any(r in phases for r in ROOTS):
        return None
    compile_s, cache = {}, {}
    for lb, v in series("compile_seconds_total"):
        if lb["phase"] != NO_PHASE:
            compile_s[lb["stage"]] = compile_s.get(lb["stage"], 0.0) + v
    for lb, v in series("compile_cache_total"):
        if lb["phase"] != NO_PHASE:
            cache[lb["result"]] = cache.get(lb["result"], 0) + int(v)
    return {"phases": phases, "compile": compile_s, "cache": cache}


def read(observed):
    acc = account(observed)
    if acc is None:
        return None
    return sum(acc["phases"].get(r, 0.0) for r in ROOTS)
