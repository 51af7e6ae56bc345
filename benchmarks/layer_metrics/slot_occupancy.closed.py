"""The engine's ``active_slots`` gauge over ``max_slots``, sampled by
the load loop at its periodic wake-ups (every quarter second in which
no request returns; a wake-up at a completion would see the freed slot)."""

NAME, UNIT, LAYER = "slot_occupancy.closed", "%", "decode scheduler"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    samples = observed.window.get("occupancy")
    if not samples:
        return None
    return 100.0 * sum(samples) / len(samples) / observed.window["max_slots"]
