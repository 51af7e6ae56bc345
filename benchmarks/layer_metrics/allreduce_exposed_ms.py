"""Time per train step that the core's instruction stream spends inside
a collective operation, so that no compute runs on that chip."""
from benchmarks.tracing import COLLECTIVE_OPCODES

NAME, UNIT, LAYER = "allreduce_exposed_ms", "ms", "collectives"
MOVES, SOURCE = "train_tokens_per_s", "device_trace"


def read(observed):
    trace = observed.trace
    if trace is None or trace.devices < 2:
        return None
    table = trace.module_table()
    steps = table[0][1] if table else 0        # the step is the largest program
    exposed = trace.opcode_seconds(COLLECTIVE_OPCODES)
    return 1e3 * exposed / steps if steps and exposed else None
