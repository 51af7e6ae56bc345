"""The least time the chip could take for the attention of a traced
decode step (the bytes of ``benchmarks/kernels/paged_attention.py``: the
live slots' full pages of cached K and V rows in every layer, by the
``serve/decode_step`` spans' ``pages_filled`` - ``n_active``, at the
published memory bandwidth) over the time the Mosaic calls of one
``jit_step`` execution took on the device, mean over the executions
traced whole.  A decode step's attention is bound by bytes.  A program
whose step holds no Mosaic call (one that gathers the window) reads
nothing here."""
import re
from statistics import mean

from benchmarks import program_spans, tracing
from benchmarks.kernels import paged_attention

NAME, UNIT, LAYER = "paged_attention_roofline", "%", "kernels"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_step$"


def read(observed):
    trace = observed.trace
    if trace is None or observed.peaks is None or not trace.ops:
        return None
    cfg = observed.cell.config
    page = cfg.get("program", {}).get("page_size")
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "pages_filled" in s.args and "n_active" in s.args
             and int(s.args.get("tokens", 1)) == 1]
    calls = sorted((op.start_ns, op.dur_ns) for op in trace.ops[0]
                   if op.target == tracing.MOSAIC_TARGET)
    rx = re.compile(PROGRAM)
    took = []
    for name, start, dur in trace.modules[0]:
        if not rx.search(name):
            continue
        inside = [d for s, d in calls if start <= s < start + dur]
        if len(inside) == cfg.get("n_layer"):      # an execution traced whole
            took.append(sum(inside) / 1e9)
    if not steps or not took or not page:
        return None
    # a slot's last page may hold one row only: count its full pages
    least = mean([paged_attention.step_bytes(
        cfg, max(0.0, float(a["pages_filled"]) - float(a["n_active"])) * page)
        for a in steps]) / observed.peaks["hbm_bytes_per_s"]
    return 100.0 * least / mean(took)
