"""The grouped matmul kernels' share of their roofline on device 0 where the
experts work in a latent: `moe_gmm_roofline.train`'s own reading (its
reader, not a copy), given the contraction this layer has,
`moe_latent_size` (that reader takes `hidden_size`, four times it here, and
would read over 100 %), and this family's name for the experts held. The
rows of a call are the token-expert pairs the program's counters say an
expert layer routed to the experts held here, never the expectation."""

from benchmarks.lib import loader

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s_per_chip"
SOURCE = "device_trace"


def read(trace, spans, cell):
    facts = cell["facts"]
    numbers = facts.get("numbers", {})
    if "moe_latent_size" not in numbers:
        return None  # no latent expert space
    in_latent = {
        **numbers, "hidden_size": numbers["moe_latent_size"],
        "num_experts": numbers["n_routed_experts"],
    }
    return loader.load_metric("moe_gmm_roofline.train").read(
        trace, spans, {**cell, "facts": {**facts, "numbers": in_latent}}
    )
