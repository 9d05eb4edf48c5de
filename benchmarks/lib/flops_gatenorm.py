"""Bytes the recurrent mixers' gated norms must move in a step, by the
CELL's shapes whatever implements them.

A state-space layer (Mamba-2) takes its scan's result y, the skip's x and
the gate z, each [tokens, d_in], to `RMSNorm_group((y + D x) silu(z))` with
a learned scale; a delta-rule layer (Kimi-Linear's KDA) its rule's result o
and the gate's product, each [tokens, H·d], to `RMSNorm_head(o) w
sigmoid(gate)`. The least traffic, each array taken once at the
activations' 2 bytes:

- a state-space layer: forward y, x and z read and the result written (4
  arrays); backward the three and the result's cotangent read and the
  three gradients written (7): 11 arrays of `tokens x d_in`;
- a delta layer: forward o and the gate read and the result written (3);
  backward the two and the cotangent read and two gradients written (5):
  8 arrays of `tokens x H·d`.

The scale, D and their gradients are a few KB and left out. What an
implementation moves beyond that (float32 copies, a pass a factor, the
activation and the norm formed again in the backward, a forward run again
under a checkpoint, a copy of a lane slice) is its own: a share of the
roofline from these bytes cannot pass 100 %. Memory-bound by this count
(the activation, its slope and a norm are a few dozen vector operations an
element's tile).
"""

from __future__ import annotations


def gated_layers(cfg: dict) -> tuple[int, int, int]:
    """(layers, lanes of a layer's gated norm, arrays it must move
    forward and backward) of the stack's recurrent mixers; (0, 0, 0) where
    it has none."""
    linear = cfg.get("linear_attn_config")
    if linear:
        layers = sum(
            i in linear["kda_layers"]
            for i in range(1, cfg["num_hidden_layers"] + 1)
        )
        return layers, linear["num_heads"] * linear["head_dim"], 3 + 5
    pattern = cfg.get("hybrid_override_pattern")
    if pattern and "M" in pattern:
        return (
            pattern.count("M"),
            cfg["mamba_num_heads"] * cfg["mamba_head_dim"], 4 + 7,
        )
    return 0, 0, 0


def gatenorm_bytes(cfg: dict, tokens: int, dtype_bytes: int = 2) -> float:
    """Bytes a step's gated norms must move, each array once."""
    layers, lanes, arrays = gated_layers(cfg)
    return float(arrays * layers * lanes * tokens * dtype_bytes)
