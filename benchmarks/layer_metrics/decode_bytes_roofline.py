"""The least time the chip could take for a traced decode dispatch (one
step, or the ``tokens`` steps of a fused horizon), by the bytes each of
its steps must read (``benchmarks/kernels/decode_bytes.py``: the
held weights outside the routed experts, the routed experts that got a
pick, the cached latent rows of the live slots) at the published memory
bandwidth, over the time ``jit_step`` / ``jit_step_multi`` took on the
device.  Decode at 16
slots is bound by bytes, not by operations."""
from benchmarks import program_spans
from benchmarks.kernels import decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "decode_bytes_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_step(_multi)?$"


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    cfg = observed.cell.config
    page = cfg.get("program", {}).get("page_size")
    steps = [s.args for s in program_spans.named(program_spans.of(observed),
                                                 "serve/decode_step")
             if "experts_hit" in s.args and "pages_filled" in s.args]
    runs = observed.trace.module_runs(PROGRAM)
    if not steps or not runs or not page:
        return None
    # a slot's last page may hold one row only: count its full pages
    # (``experts_hit`` is already summed over a fused dispatch's steps)
    least = mean([decode_bytes.step_bytes(
        cfg, float(a["experts_hit"]) / float(a.get("tokens", 1)),
        max(0.0, float(a["pages_filled"]) - float(a["n_active"])) * page)
        * float(a.get("tokens", 1))
        for a in steps]) / observed.peaks["hbm_bytes_per_s"]
    return 100.0 * least / mean(runs)
