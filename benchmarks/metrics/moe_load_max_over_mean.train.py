"""Tokens on the fullest expert held here over the mean over the held
experts, from the program's routing counters (`moe_load_max`,
`moe_load_mean`, each summed over layers) in the records `fit()` wrote
during the window: 1 is perfect balance, and the grouped matmuls' padding
and the slowest expert-parallel shard grow with it."""

LAYER = "model step"
UNIT = "ratio"
MOVES = "tokens_per_s_per_chip"
SOURCE = "program_span"


def read(trace, spans, cell):
    moe = cell["facts"].get("moe")
    if not moe:
        return None  # a program that reports no routing counters
    return moe["load_max_over_mean"]
