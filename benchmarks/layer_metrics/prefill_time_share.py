"""Device time inside the prefill programs over the device's busy time."""

NAME, UNIT, LAYER = "prefill_time_share", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"prefill"


def read(observed):
    trace = observed.trace
    if trace is None or not trace.busy_s:
        return None
    return 100.0 * trace.module_seconds(PROGRAM) / trace.busy_s
