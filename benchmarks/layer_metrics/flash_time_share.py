"""Device time inside the Mosaic custom calls (the flash-attention
kernels are the step's only ones) over the device's busy time (both the
mean over the chips), from the trace."""

NAME, UNIT, LAYER = "flash_time_share", "%", "kernels"
MOVES, SOURCE = "train_tokens_per_s", "device_trace"


def read(observed):
    trace = observed.trace
    if trace is None or not trace.busy_s:
        return None
    calls = sum(op.dur_ns for op in trace.mosaic_calls()) / 1e9 / trace.devices
    return 100.0 * calls / trace.busy_s if calls else None
