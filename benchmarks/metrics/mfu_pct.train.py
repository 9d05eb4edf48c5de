"""Model FLOP/s utilization of the traced run: the model's FLOPs per token
(`lib/flops.lm_flops_per_token`; recomputation not counted) times this
run's tokens/s/chip over the chip's bf16 peak (`lib/peaks`)."""

from benchmarks.lib import peaks

LAYER = "model step"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "host_clock"


def read(trace, spans, cell):
    facts = cell["facts"]
    if "flops_per_token" not in facts:
        return None
    peak = peaks.chip_peaks(facts["device_kind"]).flops_bf16
    return 100.0 * facts["flops_per_token"] * facts["tokens_per_s_per_chip"] / peak
