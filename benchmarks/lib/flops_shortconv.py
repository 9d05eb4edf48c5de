"""Bytes the recurrent mixers' short convolutions must move in a step, by
the CELL's shapes whatever implements them.

A delta-rule layer (Kimi-Linear's KDA) puts each of q, k and v [tokens, H·d]
through a causal depthwise convolution of a few taps, `silu` and (q and k) a
head's unit norm; a state-space layer (Mamba-2) its xBC [tokens, d_in +
2·G·N] through the convolution, a bias and `silu`. The least traffic, each
array taken once at the activations' 2 bytes: forward, the projection read
and the result written (2 arrays); backward, the projection and the
result's cotangent read and the projection's gradient written (3). The
taps, the bias and their gradients are a few KB and left out.

- a delta layer: 3 x 5 = 15 arrays of `tokens x H·d`;
- a state-space layer: 5 arrays of `tokens x (d_in + 2·G·N)`.

What an implementation moves beyond that (float32 copies, a pass a shifted
tap, `silu` and the norms formed again in the backward, a forward run
again under a checkpoint) is its own: a share of the roofline from these
bytes cannot pass 100 %. Memory-bound by this count (a tap, `silu` and a
norm are a few dozen vector operations an element's tile).
"""

from __future__ import annotations


def conv_layers(cfg: dict) -> tuple[int, int]:
    """(layers, lanes a layer's convolutions cover) of the stack's
    recurrent mixers; (0, 0) where it has none."""
    linear = cfg.get("linear_attn_config")
    if linear:
        layers = sum(
            i in linear["kda_layers"]
            for i in range(1, cfg["num_hidden_layers"] + 1)
        )
        return layers, 3 * linear["num_heads"] * linear["head_dim"]
    pattern = cfg.get("hybrid_override_pattern")
    if pattern and "M" in pattern:
        d_in = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
        return (
            pattern.count("M"),
            d_in + 2 * cfg["n_groups"] * cfg["ssm_state_size"],
        )
    return 0, 0


def shortconv_bytes(cfg: dict, tokens: int, dtype_bytes: int = 2) -> float:
    """Bytes a step's short convolutions must move, each array once."""
    layers, lanes = conv_layers(cfg)
    return float(5 * layers * lanes * tokens * dtype_bytes)
