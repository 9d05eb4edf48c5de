"""Operations and bytes a Qwen3-Next-style step needs: a Gated DeltaNet
mixer (the delta rule with a decay a HEAD over grouped key heads) in most
layers, gated softmax attention in the others, every layer over
softmax-routed top-k experts beside a gated shared one.

**Model FLOPs per token** (`gdn_flops_per_token`, by part in
`flops_by_part`) — `lib/flops.py`'s accounting, 6 FLOP per matmul parameter
a token a time it is used (2 forward, 4 backward), over what a token passes
through:

- a delta layer's projections: q and k `2·d·H_k·c`, v and the gate z
  `2·d·H·c`, b and the decay `2·d·H`, the output's `H·c·d`; the convolutions
  are elementwise and not counted;
- the delta rule's own products, by the chunked algorithm (chunks of C):
  a VALUE head's against the state, `K S`, `Q S` and `K^T Vn`, `2·c²` FLOP a
  token each, and over the causal HALF of a chunk `T R` and `M Vn`,
  `2·(C / 2)·c` each; a KEY head's raw `K K^T` and `Q K^T` over the same
  half, shared by the value heads that read it; forward, and twice that
  backward: `3·(H·(6·c² + 2·C·c) + H_k·2·C·c)`. The in-chunk triangular
  inverse (C²/3 a token a head) is left out;
- the attention layer's projections `d·A·2·D` (q and its gate), `2·d·K·D`
  and `A·D·d`, and its causal attention `3·A·(S + 1) / 2·4·D`;
- every layer's router `d·E`, shared expert `3·d·f_s` and its gate `d`, and
  routed experts `3·d·f` a row a token had routed to an expert held here
  (read from the program's counters); the untied head over the sliced
  vocabulary.

Recomputation is not counted.

**The delta rule's calls** (`gdn_call_cost`) — the least one `gdn_*` call
needs on one device, by the CELL's shapes and not the kernel's operands, so
that a change of implementation is read against the same work (one that
widened g to a head's lanes or repeated q and k would move more bytes and
read LOWER, never higher): the products above (forward once; the backward's,
twice the forward's, once); q and k at H_k heads, v and o at H, g and b at
[tokens, H] float32, once forward; q, k, dq, dk at H_k heads, v, dO, dv at
H, g, b and their gradients at [tokens, H] float32, once backward; the state
entering each chunk (`c·H·c` a chunk at 2 bytes) written once forward and
read once backward. What the kernels form beside in VMEM (the triangular
inverse, the chunk's products again in the backward) is the implementation's
own. Memory-bound by this count.
"""

from __future__ import annotations


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(delta layers, attention layers) of the stack."""
    layers, every = cfg["num_hidden_layers"], cfg["full_attention_interval"]
    attention = sum((i + 1) % every == 0 for i in range(layers))
    return layers - attention, attention


def _scan_flops_a_token(cfg: dict, chunk: int) -> float:
    """The chunked delta rule's forward products a token, all heads."""
    h, hk = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    c = cfg["linear_key_head_dim"]
    return h * (6.0 * c * c + 2.0 * chunk * c) + hk * 2.0 * chunk * c


def flops_by_part(
    cfg: dict, seq_len: int, rows_held_a_token: float, chunk: int
) -> dict:
    """Model FLOPs a token by part of the step."""
    d = cfg["hidden_size"]
    h, hk = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    c = cfg["linear_key_head_dim"]
    a, kv, hd = (
        cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    )
    delta, attention = mixer_layers(cfg)
    layers = cfg["num_hidden_layers"]
    ff, sff = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    projections = 2 * d * hk * c + 2 * d * h * c + 2 * d * h + h * c * d
    return {
        "gdn_projections": 6.0 * delta * projections,
        "gdn_scan": delta * 3.0 * _scan_flops_a_token(cfg, chunk),
        "attn_projections": 6.0 * attention * (
            d * a * 2 * hd + 2 * d * kv * hd + a * hd * d
        ),
        "attention": attention * 3.0 * a * (seq_len + 1) / 2 * 4 * hd,
        "shared_and_router": 6.0 * layers * d * (
            cfg["experts_routed"] + 3 * sff + 1
        ),
        "routed_experts": 6.0 * layers * 3 * d * ff * rows_held_a_token,
        "head": 6.0 * cfg["vocab_size"] * d,
    }


def gdn_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float, chunk: int
) -> float:
    return float(sum(
        flops_by_part(cfg, seq_len, rows_held_a_token, chunk).values()
    ))


def gdn_kernel_kind(event_name: str) -> str | None:
    """"fwd" or "bwd" for a trace event of a `gdn_*` kernel, else None."""
    for kind in ("fwd", "bwd"):
        if event_name.startswith(f"gdn_{kind}"):
            return kind
    return None


def gdn_call_cost(
    kind: str, cfg: dict, *, batch: int, seq_len: int, chunk: int,
    dtype_bytes: int = 2,
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device."""
    h, hk = cfg["linear_num_value_heads"], cfg["linear_num_key_heads"]
    c = cfg["linear_key_head_dim"]
    tokens = batch * seq_len
    forward = tokens * _scan_flops_a_token(cfg, chunk)
    states = batch * -(-seq_len // chunk) * c * h * c * dtype_bytes
    keys, values, scalars = tokens * hk * c, tokens * h * c, tokens * h * 4
    if kind == "fwd":  # q, k; v, o; g, b
        return forward, float(
            (2 * keys + 2 * values) * dtype_bytes + 2 * scalars + states
        )
    # q, k, dq, dk; v, dO, dv; g, b, dg, db
    return 2 * forward, float(
        (4 * keys + 3 * values) * dtype_bytes + 4 * scalars + states
    )
