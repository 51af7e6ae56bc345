"""Of the bytes the traced decode steps must move
(``conv_decode_bytes_roofline``'s floor), the share that is the routed
experts that got at least one pick, three matrices each: how much of a
step the expert layer is, and so what evener or sparser routing could
move.  From the counts the program puts on ``serve/decode_step``; a
program that does not count them reads nothing here."""
from benchmarks import harness
from benchmarks.kernels import conv_decode_bytes

NAME, UNIT, LAYER = "expert_bytes_share", "%", "expert layer"
MOVES, SOURCE = "serve_tokens_per_s", "program_counter"


def read(observed):
    if observed.trace is None:
        return None
    roofline = harness.load_layer_metric("conv_decode_bytes_roofline")
    steps = roofline.dispatches(observed)
    cfg = observed.cell.config
    whole = sum(roofline.dispatch_bytes(cfg, a) for a in steps)
    if not whole:
        return None
    experts = sum(conv_decode_bytes.expert_step_bytes(
        cfg, float(a["experts_hit"])) for a in steps)
    return 100.0 * experts / whole
