"""Median device time of one execution of the decode-step program."""
from benchmarks.stats import median

NAME, UNIT, LAYER = "decode_step_ms.closed", "ms", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
#: the compiled program of DecodeProgram.step, as the trace names it
PROGRAM = r"^jit_step$"


def read(observed):
    if observed.trace is None:
        return None
    runs = observed.trace.module_runs(PROGRAM)
    return 1e3 * median(runs) if runs else None
