"""The least time the chip could take for the flash-attention calls (by
the benchmark's own operations and bytes against the published peaks)
over the time they took in the trace.  At head size 64 every call is
bound by compute."""
from benchmarks.kernels import flash_attention as fa

NAME, UNIT, LAYER = "flash_roofline", "%", "kernels"
MOVES, SOURCE = "train_tokens_per_s", "device_trace"


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    least = took = 0.0
    for op in observed.trace.mosaic_calls():
        call = fa.classify(op.shape)
        if call is None:
            continue
        least += fa.least_seconds(*call, observed.peaks)[0]
        took += op.dur_ns / 1e9
    return 100.0 * least / took if took else None
