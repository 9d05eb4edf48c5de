"""Operations and bytes a Kimi-Linear-style step needs: a gated delta-rule
mixer (KDA) in most layers, un-rotated latent attention in the others, a
leading dense layer and gated top-k experts beside a shared one.

**Model FLOPs per token** (`kda_flops_per_token`, by part in
`flops_by_part`) — `lib/flops.py`'s accounting, 6 FLOP per matmul parameter
a token a time it is used (2 forward, 4 backward), over what a token passes
through:

- a KDA layer's projections: q, k, v `3·d·D` (D = H·c), the decay's two
  thin matrices `d·c + c·D`, the step's `d·H`, the output gate's `d·c + c·D`
  and the output's `D·d`; the convolutions are elementwise and not counted;
- the delta rule's own products, by the chunked algorithm (chunks of C):
  against the state `Kd S`, `Qd S` and `Kr^T Vn`, `2·c²` FLOP a head a token
  each; over the causal HALF of a chunk `K K^T`, `Q K^T`, `T R` and `M Vn`,
  `2·(C / 2)·c` each; forward, and twice that backward: `3·H·(6·c² +
  4·C·c)`. The in-chunk triangular inverse (C²/3 a token a head) is left
  out;
- an MLA layer's projections `d·H·(nope + rope) + d·(kv_lora_rank + rope) +
  kv_lora_rank·H·(nope + v) + H·v·d` and its attention as
  `lib/flops_mla.py` counts it, `3·H·(S + 1) / 2·(2·(nope + rope) + 2·v)`;
- a dense layer's SwiGLU, a sparse layer's router, shared expert and routed
  experts (the rows a token had routed to an expert held here, read from
  the program's counters), the untied head over the sliced vocabulary.

Recomputation is not counted.

**The delta rule's calls** (`kda_call_cost`) — the least one `kda_*` call
needs on one device, by the CELL's shapes and not the kernel's operands, so
that a later change of implementation is read against the same work: the
products above (forward once; the backward's, twice the forward's, once),
and q, k, v, g (4 bytes: the decay is float32) and o once forward; q, k, v,
g, dO and the four gradients once backward; the state entering each chunk
(`c·D` a chunk at 2 bytes) written once forward and read once backward.
What the kernels form beside in VMEM (the sub-block products again in the
backward, the triangular inverse) is the implementation's own. Memory-bound
by this count.
"""

from __future__ import annotations


def mixer_layers(cfg: dict) -> tuple[int, int]:
    """(KDA layers, MLA layers) of the stack."""
    linear = cfg["linear_attn_config"]
    layers = range(1, cfg["num_hidden_layers"] + 1)
    kda = sum(i in linear["kda_layers"] for i in layers)
    return kda, cfg["num_hidden_layers"] - kda


def flops_by_part(
    cfg: dict, seq_len: int, rows_held_a_token: float, chunk: int
) -> dict:
    """Model FLOPs a token by part of the step."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    linear = cfg["linear_attn_config"]
    hl, c = linear["num_heads"], linear["head_dim"]
    wide = hl * c
    kvl = cfg["kv_lora_rank"]
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    kda, mla = mixer_layers(cfg)
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    ff = cfg["moe_intermediate_size"]
    projections = 4 * d * wide + 2 * (d * c + c * wide) + d * hl
    latent = d * h * (dn + dr) + d * (kvl + dr) + kvl * h * (dn + dv) + h * dv * d
    return {
        "kda_projections": 6.0 * kda * projections,
        "kda_scan": kda * 3.0 * hl * (6 * c * c + 4 * chunk * c),
        "mla_projections": 6.0 * mla * latent,
        "mla_attention": mla * 3.0 * h * (seq_len + 1) / 2 * (
            2 * (dn + dr) + 2 * dv
        ),
        "dense_mlp": 6.0 * dense * 3 * d * cfg["intermediate_size"],
        "shared_and_router": 6.0 * sparse * d * (
            cfg["experts_routed"] + 3 * cfg["num_shared_experts"] * ff
        ),
        "routed_experts": 6.0 * sparse * 3 * d * ff * rows_held_a_token,
        "head": 6.0 * cfg["vocab_size"] * d,
    }


def kda_flops_per_token(
    cfg: dict, seq_len: int, rows_held_a_token: float, chunk: int
) -> float:
    return float(sum(
        flops_by_part(cfg, seq_len, rows_held_a_token, chunk).values()
    ))


def kda_kernel_kind(event_name: str) -> str | None:
    """"fwd" or "bwd" for a trace event of a delta-rule kernel, else None."""
    for kind in ("fwd", "bwd"):
        if event_name.startswith(f"kda_{kind}"):
            return kind
    return None


def kda_call_cost(
    kind: str, cfg: dict, *, batch: int, seq_len: int, chunk: int,
    dtype_bytes: int = 2,
) -> tuple[float, float]:
    """(FLOP, bytes) one call of the kernel `kind` needs on one device."""
    linear = cfg["linear_attn_config"]
    hl, c = linear["num_heads"], linear["head_dim"]
    tokens = batch * seq_len
    forward = tokens * hl * (6.0 * c * c + 4.0 * chunk * c)
    states = batch * -(-seq_len // chunk) * c * hl * c * dtype_bytes
    lanes = tokens * hl * c
    if kind == "fwd":  # q, k, v, o at 2 bytes, g at 4
        return forward, float(lanes * (4 * dtype_bytes + 4) + states)
    # q, k, v, dO, dq, dk, dv at 2 bytes, g and dg at 4
    return 2 * forward, float(lanes * (7 * dtype_bytes + 8) + states)
