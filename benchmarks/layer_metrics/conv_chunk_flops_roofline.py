"""The least time the chip could take for a traced prefill chunk by its
operations (``benchmarks/kernels/conv_decode_bytes.chunk_flops``: the
mixers', routers' and the dense layer's products for the chunk's real
rows, the routed experts by the picks that fell on held ones, the head
for one row, the grouped-query products of each real row over the rows
before it) at the published bf16 peak, over the time ``jit_prefill_at``
took on the device.  A chunk streams the same weights a step does for
some hundreds of rows; what a bucket's padded rows cost shows here as a
lower share.  From the sizes and counts the program puts on
``serve/prefill``; a program that does not count them reads nothing
here."""
from benchmarks import program_spans
from benchmarks.kernels import conv_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "conv_chunk_flops_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_prefill_at$"
COUNTED = ("expert_picks_held", "prompt_tokens", "offset")


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    cfg = observed.cell.config
    if "conv_L_cache" not in cfg:
        return None
    chunks = [s.args for s in program_spans.named(program_spans.of(observed),
                                                  "serve/prefill")
              if all(k in s.args for k in COUNTED)]
    runs = observed.trace.module_runs(PROGRAM)
    if not chunks or not runs:
        return None
    least = mean([conv_decode_bytes.chunk_flops(
        cfg, float(a["prompt_tokens"]), float(a["offset"]),
        float(a["expert_picks_held"])) for a in chunks]) \
        / observed.peaks["bf16_flops_per_s"]
    return 100.0 * least / mean(runs)
