"""The least time the chip could take for a traced decode dispatch (one
step, or the ``tokens`` steps of a fused horizon), by the bytes each of
its steps must move (``benchmarks/kernels/ssm_decode_bytes.py``: the held
weights outside the routed experts once a step, the tied head among
them, the routed experts that got a pick, the K and V rows the stepped
slots hold in the grouped-query layer, and for every stepped slot and
state-space layer the state and the convolution's tail read AND written
once) at the published memory bandwidth, over the time ``jit_step`` /
``jit_step_multi`` took on the device: the share of the whole step a
later change to it is bounded by.  Decode at 48 slots is bound by bytes,
not by operations.  A program that does not count them reads nothing
here."""
from benchmarks import program_spans
from benchmarks.kernels import ssm_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "ssm_decode_bytes_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_step(_multi)?$"
COUNTED = ("experts_hit", "kv_rows_held", "state_slots_stepped")


def dispatches(observed):
    """The arguments of the traced ``serve/decode_step`` spans that carry
    the counts."""
    if observed.cell is None or "mamba_n_heads" not in observed.cell.config:
        return []
    return [s.args for s in program_spans.named(program_spans.of(observed),
                                                "serve/decode_step")
            if all(k in s.args for k in COUNTED)]


def dispatch_bytes(cfg, a) -> float:
    """The counts are already summed over a fused dispatch's steps, and
    each step reads the fixed weights once."""
    n = float(a.get("tokens", 1))
    hit, held, stepped = (float(a[k]) / n for k in COUNTED)
    return n * ssm_decode_bytes.step_bytes(cfg, hit, held, stepped)


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
