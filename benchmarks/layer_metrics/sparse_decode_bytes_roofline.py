"""The least time the chip could take for a traced decode dispatch (one
step, or the ``tokens`` steps of a fused horizon), by the bytes each of
its steps must read (``benchmarks/kernels/sparse_decode_bytes.py``: the
held weights outside the routed experts, the routed experts that got a
pick, the index keys scored, the K and V rows read after the selection;
of either no more than the rows held, since the program's counts take in
its blocks' rounding and its idle slots and a floor does not) at the
published memory bandwidth, over the time ``jit_step`` /
``jit_step_multi`` took on the device: the share of the whole step a
later change to it is bounded by.  Decode at 8 slots is bound by bytes,
not by operations."""
from benchmarks import program_spans
from benchmarks.kernels import sparse_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "sparse_decode_bytes_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_step(_multi)?$"
COUNTED = ("experts_hit", "index_rows_scored", "attn_rows_read", "rows_held")


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    cfg = observed.cell.config
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if all(k in s.args for k in COUNTED)]
    runs = observed.trace.module_runs(PROGRAM)
    if not steps or not runs or "sa_config" not in cfg:
        return None
    # the counts are already summed over a fused dispatch's steps, and
    # each step reads the fixed weights once
    def dispatch_bytes(a):
        n = float(a.get("tokens", 1))
        hit, scored, read, held = (float(a[k]) / n for k in COUNTED)
        return n * sparse_decode_bytes.step_bytes(
            cfg, hit, min(scored, held), min(read, held))

    least = (mean([dispatch_bytes(a) for a in steps])
             / observed.peaks["hbm_bytes_per_s"])
    return 100.0 * least / mean(runs)
