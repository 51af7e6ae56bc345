"""The least time the chip could take for a traced decode dispatch (one
step, or the ``tokens`` steps of a fused horizon), by the bytes each of
its steps must move (``benchmarks/kernels/conv_decode_bytes.py``: every
mixer, norm and router, the leading dense layer's feed-forward and the
tied head once a step, the routed experts that got a pick, the K and V
rows the stepped slots hold in the grouped-query layers, and for every
stepped slot and convolution layer the tail read AND written once) at
the published memory bandwidth, over the time ``jit_step`` /
``jit_step_multi`` took on the device: the share of the whole step a
later change to it is bounded by.  Decode at 128 slots is bound by bytes,
not by operations.  A program that does not count them reads nothing
here."""
from benchmarks import program_spans
from benchmarks.kernels import conv_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "conv_decode_bytes_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_step(_multi)?$"
COUNTED = ("experts_hit", "kv_rows_held", "state_slots_stepped")


def dispatches(observed):
    """The arguments of the traced ``serve/decode_step`` spans that carry
    the counts."""
    if observed.cell is None or "conv_L_cache" not in observed.cell.config:
        return []
    return [s.args for s in program_spans.named(program_spans.of(observed),
                                                "serve/decode_step")
            if all(k in s.args for k in COUNTED)]


def dispatch_bytes(cfg, a) -> float:
    """The counts are already summed over a fused dispatch's steps, and
    each step reads the fixed weights once."""
    n = float(a.get("tokens", 1))
    return n * conv_decode_bytes.step_bytes(
        cfg, *(float(a[k]) / n for k in COUNTED))


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    steps = dispatches(observed)
    runs = observed.trace.module_runs(PROGRAM)
    if not steps or not runs:
        return None
    cfg = observed.cell.config
    least = (mean([dispatch_bytes(cfg, a) for a in steps])
             / observed.peaks["hbm_bytes_per_s"])
    return 100.0 * least / mean(runs)
