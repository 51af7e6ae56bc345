"""The benchmark's count of forward+backward operations per token times
the tokens per second of the median step, over the chips' published bf16
peak.  (The median step, because a traced window loses time to the
profiler; the window's own rate is the end-to-end metric.)"""
from benchmarks.opcounts import train_flops_per_token
from benchmarks.stats import median

NAME, UNIT, LAYER = "model_flops_util", "%", "train step"
MOVES, SOURCE = "train_tokens_per_s", "host_clock"


def read(observed):
    steps = observed.window.get("step_s")
    if not steps or observed.peaks is None:
        return None
    cell = observed.cell
    rate = observed.window["tokens_per_step"] / median(steps)
    per_token = train_flops_per_token(cell.config, cell.mix["seq_len"])
    return 100.0 * per_token * rate / (
        cell.chips * observed.peaks["bf16_flops_per_s"])
