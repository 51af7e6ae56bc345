"""Of the bytes the traced decode steps must move
(``ssm_decode_bytes_roofline``'s floor), the share that is the
state-space layers' per-slot state and convolution tails, read and
written once a stepped slot a layer: how much of a step the mechanism
that keeps state per slot, and not per token, is.  From the counts the
program puts on ``serve/decode_step``; a program that does not count
them reads nothing here."""
from benchmarks import harness
from benchmarks.kernels import ssm_decode_bytes

NAME, UNIT, LAYER = "ssm_state_bytes_share", "%", "recurrent state"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    if observed.trace is None:
        return None
    roofline = harness.load_layer_metric("ssm_decode_bytes_roofline")
    steps = roofline.dispatches(observed)
    cfg = observed.cell.config
    whole = sum(roofline.dispatch_bytes(cfg, a) for a in steps)
    if not whole:
        return None
    state = sum(ssm_decode_bytes.state_step_bytes(
        cfg, float(a["state_slots_stepped"])) for a in steps)
    return 100.0 * state / whole
