"""The straggler a grouped product waits for: over the held experts of
every expert layer, the fullest expert's picks (``expert_load_max``: the
program sums each layer's maximum) over the mean expert's
(``expert_picks_held`` / experts held, a layer), summed over the traced
decode steps and prefill chunks.  1.0 is even routing."""
from benchmarks import harness

NAME, UNIT, LAYER = "expert_load_max_over_mean", "x", "expert layer"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    args = harness.load_layer_metric("expert_picks_held_share").routed(observed)
    on_held = sum(float(a["expert_picks_held"]) for a in args)
    if not on_held:
        return None
    held = observed.cell.config["n_routed_experts"]
    return sum(float(a["expert_load_max"]) for a in args) * held / on_held
