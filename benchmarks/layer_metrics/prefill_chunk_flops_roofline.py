"""The least time the chip could take for a traced prefill chunk by its
operations (``benchmarks/kernels/recurrent_decode_bytes.chunk_flops``:
the mixers', routers' and shared experts' products for the chunk's real
rows, the routed experts by the picks that fell on held ones, the head
for one row, the grouped-query products of each real row over the rows
before it, the linear layers' scan at its sub-chunk) at the published
bf16 peak, over the time ``jit_prefill_at`` took on the device.  A chunk
of 512 rows is bound by operations where a decode step is bound by
bytes.  From the sizes and counts the program puts on ``serve/prefill``;
a program that does not count them reads nothing here."""
from benchmarks import program_spans
from benchmarks.kernels import recurrent_decode_bytes
from statistics import mean

NAME, UNIT, LAYER = "prefill_chunk_flops_roofline", "%", "decode program"
MOVES, SOURCE = "serve_tokens_per_s", "device_trace"
PROGRAM = r"^jit_prefill_at$"
COUNTED = ("state_rows_scanned", "kv_rows_read", "expert_picks_held",
           "prompt_tokens", "offset")


def read(observed):
    if observed.trace is None or observed.peaks is None:
        return None
    cfg = observed.cell.config
    if "linear_attn_config" not in cfg:
        return None
    chunks = [s.args for s in program_spans.named(program_spans.of(observed),
                                                  "serve/prefill")
              if all(k in s.args for k in COUNTED)]
    runs = observed.trace.module_runs(PROGRAM)
    if not chunks or not runs:
        return None
    _, linear = recurrent_decode_bytes.layer_counts(cfg)
    # the real rows are what the linear layers scanned, a layer
    least = mean([recurrent_decode_bytes.chunk_flops(
        cfg, float(a["state_rows_scanned"]) / linear, float(a["offset"]),
        float(a["expert_picks_held"])) for a in chunks]) \
        / observed.peaks["bf16_flops_per_s"]
    return 100.0 * least / mean(runs)
