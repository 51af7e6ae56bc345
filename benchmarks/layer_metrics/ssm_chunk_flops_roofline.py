"""The least time the chip could take for a traced prefill chunk by its
operations (``benchmarks/kernels/ssm_decode_bytes.chunk_flops``: the
mixers', routers' and shared MLPs' products for the chunk's real rows,
the routed experts by the picks that fell on held ones, the head for one
row, the grouped-query products of each real row over the rows before
it, the state-space layers' scan at its chunk of 256 rows, or of the
bucket's where that is shorter) at the published bf16 peak, over the
time ``jit_prefill_at`` took on the device.  A chunk is bound by
operations where a decode step is bound by bytes; what a bucket's padded
rows cost shows here as a lower share.  From the sizes and counts the
program puts on ``serve/prefill``; a program that does not count them
reads nothing here."""
from benchmarks import program_spans
from benchmarks.kernels import ssm_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "ssm_chunk_flops_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_prefill_at$"
COUNTED = ("state_rows_computed", "expert_picks_held", "prompt_tokens",
           "offset", "bucket")


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    cfg = observed.cell.config
    if "mamba_n_heads" not in cfg:
        return None
    chunks = [s.args for s in program_spans.named(program_spans.of(observed),
                                                  "serve/prefill")
              if all(k in s.args for k in COUNTED)]
    runs = observed.trace.module_runs(PROGRAM)
    if not chunks or not runs:
        return None
    least = mean([ssm_decode_bytes.chunk_flops(
        cfg, float(a["prompt_tokens"]), float(a["offset"]),
        float(a["expert_picks_held"]), float(a["bucket"])) for a in chunks]) \
        / observed.peaks["bf16_flops_per_s"]
    return 100.0 * least / mean(runs)
