"""Share of the traced window in which no operation ran on the device
(mean over the chips): 1 - busy / window."""

NAME, UNIT, LAYER = "device_idle_share.closed", "%", "device"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"


def read(observed):
    share = observed.trace.idle_share() if observed.trace else None
    return None if share is None else 100.0 * share
