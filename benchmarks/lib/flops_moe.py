"""Operations and bytes a ZAYA1-style step needs, from shapes and from the
tokens the router really sent to the experts held here.

**Model FLOPs per token** (`moe_flops_per_token`) — `lib/flops.py`'s
accounting, 6 FLOP per matmul parameter a token a time it is used (2
forward, 4 backward), over what a token passes through:

- CCA's projections, `d·(H + 2·Hkv)·hd` in and `H·hd·d` out, and its
  grouped convolution, `cca_time1·(H + Hkv)·hd·hd` (the depthwise one is
  elementwise and not counted);
- the router, `d·r + 2·r² + r·E` with E the experts it scores;
- the experts, `3·d·ff` times the share of tokens that were routed to an
  expert held here — read from the program's counters, never assumed: a
  token routed elsewhere costs this chip no expert FLOP;
- the tied head over the sliced vocabulary, once;

plus `6·L·S·H·hd` a token for causal attention over the latent, as
`lib/flops.lm_flops_per_token` counts it. Recomputation is not counted.

**Grouped matmuls** (`gmm_call_cost`) — the least one call of a
`moe_gmm_*` kernel needs for `rows` routed tokens: `2·rows·K·N` FLOP
whichever of the three it is, and every operand read once and every result
written once: the rows' operand and result at 2 bytes, the held experts'
weights at 2 bytes where they are read (`fwd`, `dlhs`) and their gradient
at 4 where it is written (`dw`). Padding rows, the zero tiles of empty
experts and a weight block fetched twice are the kernel's own: they are
not needed work, so a share of the roofline from these cannot pass 100 %.
"""

from __future__ import annotations


def matmul_params_per_token(cfg: dict, held_share: float) -> float:
    """Matmul parameters one token passes through; `held_share` is the
    mean share of tokens a layer routed to an expert held here."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    r = cfg["router_hidden_size"]
    attention = d * (h + 2 * hk) * hd + h * hd * d
    conv = cfg["cca_time1"] * (h + hk) * hd * hd
    router = d * r + 2 * r * r + r * cfg["experts_routed"]
    experts = 3 * d * cfg["moe_intermediate_size"] * held_share
    layer = attention + conv + router + experts
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * d


def moe_flops_per_token(cfg: dict, seq_len: int, held_share: float) -> float:
    latent = cfg["num_attention_heads"] * cfg["head_dim"]
    return float(
        6 * matmul_params_per_token(cfg, held_share)
        + 6 * cfg["num_hidden_layers"] * seq_len * latent
    )


GMM_KINDS = ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_dw")


def gmm_kernel_kind(event_name: str) -> str | None:
    for kind in GMM_KINDS:
        if event_name.startswith(kind):
            return kind
    return None


def gmm_call_cost(
    kind: str, *, rows: float, contract: int, cols: int, experts: int,
) -> tuple[float, float]:
    """(FLOP, bytes) one call needs for `rows` routed tokens through
    `experts` weight matrices of `contract` x `cols`."""
    flops = 2.0 * rows * contract * cols
    operand = 2.0 * rows * (contract + cols)
    weights = experts * contract * cols * (4.0 if kind == "moe_gmm_dw" else 2.0)
    return flops, operand + weights
