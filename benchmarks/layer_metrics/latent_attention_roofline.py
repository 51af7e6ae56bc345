"""The least time the chip could take for the latent attention of a
traced decode dispatch (the bytes of ``benchmarks/kernels/decode_bytes.py``
``latent_row_bytes``: the live slots' FULL pages of cached latent rows in
every layer, by the ``serve/decode_step`` spans' ``pages_filled`` -
``n_active``, once for each of the dispatch's ``tokens`` steps, at the
published memory bandwidth) over the time the ``latent_attention``
Mosaic calls of one ``jit_step_multi`` (``jit_step`` at a horizon of 1)
execution took on the device, mean over the executions traced whole:
layers x steps calls.  (The step's grouped products are Mosaic calls of
XLA's own, ``ragged-dot``: the kernel is told by its name.)  Only rows
held are counted, so it cannot pass 100%.  A program whose step holds no
such call (one that gathers the window) reads nothing here."""
from statistics import mean

from benchmarks import program_spans, tracing
from benchmarks.kernels import decode_bytes

NAME, UNIT, LAYER = "latent_attention_roofline", "%", "kernels"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
KERNEL = "latent_attention"


def read(observed):
    trace = observed.trace
    if trace is None or observed.peaks is None or not trace.ops:
        return None
    cfg = observed.cell.config
    program = cfg.get("program", {})
    page, layers = program.get("page_size"), cfg.get("num_hidden_layers")
    horizon = int(program.get("decode_horizon", 1))
    if not page or not layers:
        return None
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "pages_filled" in s.args and "n_active" in s.args
             and int(s.args.get("tokens", 1)) == horizon]
    calls = sorted((op.start_ns, op.dur_ns) for op in trace.ops[0]
                   if op.target == tracing.MOSAIC_TARGET
                   and op.name.split(".")[0] == KERNEL)
    name = "jit_step_multi" if horizon > 1 else "jit_step"
    took = []
    for module, start, dur in trace.modules[0]:
        if module != name:
            continue
        inside = [d for s, d in calls if start <= s < start + dur]
        if len(inside) == layers * horizon:       # an execution traced whole
            took.append(sum(inside) / 1e9)
    if not steps or not took:
        return None
    # a slot's last page may hold one row only: count its full pages
    least = mean([
        max(0.0, float(a["pages_filled"]) - float(a["n_active"])) * page
        * decode_bytes.latent_row_bytes(cfg) * horizon
        for a in steps]) / observed.peaks["hbm_bytes_per_s"]
    return 100.0 * least / mean(took)
